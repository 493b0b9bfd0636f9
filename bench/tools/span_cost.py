"""What a program span costs on this host: ``repro.telemetry.spans``'s
``maybe_span`` with nothing listening, with a profile recording, and with
a ``Tracer`` active, in microseconds per span (a loop of empty spans less
the same loop without them). Prints one JSON line.

    python3 bench/tools/span_cost.py [--n 200000] [--trace-dir <dir>]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path[:0] = [os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src")]


def per_span_us(n: int, args: bool) -> float:
    """Best of five: (loop with a span each turn - empty loop) / n."""
    from repro.telemetry.spans import maybe_span

    def spans():
        t = time.perf_counter()
        if args:
            for i in range(n):
                with maybe_span("serve.step", step=i):
                    pass
        else:
            for _ in range(n):
                with maybe_span("serve.wait"):
                    pass
        return time.perf_counter() - t

    def empty():
        t = time.perf_counter()
        for _ in range(n):
            pass
        return time.perf_counter() - t
    return 1e6 * (min(spans() for _ in range(5))
                  - min(empty() for _ in range(5))) / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--trace-dir")
    args = ap.parse_args(argv)
    import jax
    import repro.compat  # noqa: F401  (installs the profiler sink)
    from repro import telemetry
    out = {"platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind,
           "n": args.n}
    out["off_us"] = per_span_us(args.n, False)
    out["off_step_arg_us"] = per_span_us(args.n, True)
    with telemetry.Tracer():
        out["tracer_us"] = per_span_us(args.n // 10, False)
    d = args.trace_dir or tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    try:
        out["profiling_us"] = per_span_us(args.n // 10, False)
        out["profiling_step_arg_us"] = per_span_us(args.n // 10, True)
    finally:
        jax.profiler.stop_trace()
        if not args.trace_dir:
            shutil.rmtree(d, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
