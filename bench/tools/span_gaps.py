"""Print where a traced run's device idle time went, by the program's
spans and the harness's (bench/spans.py), and, for a collective cell,
each scope's device time per call against the planner's estimate.

    python3 bench/tools/span_gaps.py [trace dir] [--cell <workload>] [--json <file>]

The trace directory defaults to the one the last ``--trace 1`` run left
(``.bench_trace``). ``--cell`` names the workload whose configuration
gives the cube for the estimates.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import statistics
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), os.path.join(os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "src")]

from bench import harness, spans  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", nargs="?", default=str(harness.TRACE_DIR))
    ap.add_argument("--cell")
    ap.add_argument("--json")
    args = ap.parse_args(argv)
    r = spans.reduce_planes(spans.planes_of(args.trace_dir,
                                            scopes=bool(args.cell)))
    idle = sum(r["idle_by_span"].values())
    print(f"window {r['window_s']} s, idle {idle} s")
    print("idle by innermost span:")
    for k, v in r["idle_by_span"].items():
        print(f"  {v:.6f} s  {100 * v / idle if idle else 0:5.1f}%  {k}")
    print("idle under each span (any depth), spans in the window:")
    for k, v in r["idle_under"].items():
        n = r["count"].get(k, 0)
        print(f"  {v:.6f} s  {n:4d}  {1e3 * v / n if n else 0:.3f} ms each"
              f"  {k}")
    out = dict(r)
    if args.cell:
        q = spans.plan_ratios(r, harness.load_cell(args.cell).config)
        out["meas_over_est"] = q
        by = collections.defaultdict(list)
        for scope, v in q.items():
            t = r["scopes"][scope]
            print(f"  {scope}: {t['calls']} calls, "
                  f"{1e6 * t['seconds'] / t['calls']:.1f} us per call, "
                  f"meas/est {v:.3f}")
            by[scope.split(".")[1]].append(v)
        for prim, vs in sorted(by.items()):
            print(f"{prim}: median meas/est {statistics.median(vs):.3f}")
        if q:
            print("plan_error (median |log2 meas/est|): "
                  f"{statistics.median(abs(math.log2(v)) for v in q.values())}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
