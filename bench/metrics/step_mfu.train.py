"""Share of the chip's bf16 peak that the trained tokens need: model
FLOPs per token (bench/flops.py, no recompute) x tokens/s / peak."""


def read(rec):
    if not rec.get("tokens_per_s"):
        return None
    return (100.0 * rec["tokens_per_s"] * rec["flops_per_token"]
            / rec["peaks"]["bf16_flops_per_s"])
