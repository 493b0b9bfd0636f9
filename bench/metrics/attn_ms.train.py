"""Device milliseconds per traced train step under the training
attention's tag, forward (its recomputation included) and backward
(bench/attn.py)."""
from bench import attn


def read(rec):
    steps = (rec.get("traffic") or {}).get("trace_steps")
    s = attn.device_seconds() if rec.get("trace") and steps else None
    return None if s is None else 1e3 * s / steps
