"""Device idle time under the engine's ``serve.step`` spans, per traced
engine step: the idle stretches of the traced window that a
``serve.step`` span covers, at any depth, over the number of such spans
(bench/spans.py). None for a program that opens no such span."""
from bench import spans


def read(rec):
    r = spans.of_run(rec)
    steps = (r or {}).get("count", {}).get("serve.step")
    if not steps:
        return None
    return 1e3 * r["idle_under"].get("serve.step", 0.0) / steps
