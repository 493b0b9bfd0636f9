"""Device idle time under the engine's ``serve.program`` spans (record,
lower and execute the per-step collective program: the host->device
broadcasts and the gather of the previous step's tokens), per traced
engine step (bench/spans.py). None for a program that opens no such
span."""
from bench import spans


def read(rec):
    r = spans.of_run(rec)
    steps = (r or {}).get("count", {}).get("serve.step")
    if not steps:
        return None
    return 1e3 * r["idle_under"].get("serve.program", 0.0) / steps
