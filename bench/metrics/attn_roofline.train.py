"""Roofline share of the training attention over the traced steps: the
least time the chip could take (the larger of the FLOPs the forward and
backward need over the bf16 peak and the bytes they need over the HBM
bandwidth, bench/attn.py) over the device time under the attention's
tag."""
from bench import attn


def read(rec):
    tr = rec.get("traffic") or {}
    steps = tr.get("trace_steps")
    s = attn.device_seconds() if rec.get("trace") and steps else None
    if s is None:
        return None
    c, p = rec["config"], rec["peaks"]
    need = steps * max(
        attn.train_flops(c, tr["rows"], tr["seq"]) / p["bf16_flops_per_s"],
        attn.train_bytes(c, tr["rows"], tr["seq"]) / p["hbm_bytes_per_s"])
    return 100.0 * need / s
