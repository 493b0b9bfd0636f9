"""Roofline share of the decode steps in the traced stretch: for each
step the least time the chip could take (the larger of the FLOPs it needs
over the bf16 peak and the bytes it needs over the HBM bandwidth, from
bench/flops.py), summed, over the device time of the decode program."""


def read(rec):
    tr = rec.get("trace")
    dev = (tr or {}).get("module_s", {}).get(rec.get("decode_module"))
    if not dev or not rec.get("trace_steps"):
        return None
    p = rec["peaks"]
    need = sum(max(f / p["bf16_flops_per_s"], b / p["hbm_bytes_per_s"])
               for f, b in rec["trace_steps"])
    return 100.0 * need / dev
