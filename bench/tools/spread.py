"""Spread of each end-to-end metric over sets of benchmark runs.

    python3 bench/tools/spread.py <set 1 files> -- <set 2 files> [-- ...]

Each file holds a run's standard output; its last line is the result.
For each set and metric it prints the median and the spread: the distance
between the first and third quartiles (``statistics.quantiles(n=4)``) as a
share of the median. The bound a metric takes is about five times the
widest spread of the sets, and never under 1%.
"""
from __future__ import annotations

import json
import statistics
import sys


def results(paths):
    out = []
    for p in paths:
        with open(p) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        out.append(json.loads(lines[-1]))
    return out


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv) -> int:
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    per_set = [results(s) for s in sets if s]
    names = sorted({m for rs in per_set for r in rs for m in r["metrics"]})
    for m in names:
        widest = 0.0
        for i, rs in enumerate(per_set):
            vals = [r["metrics"][m]["value"] for r in rs if m in r["metrics"]]
            if len(vals) < 2:
                continue
            sp = spread(vals)
            widest = max(widest, sp)
            print(f"{m} set {i + 1}: n {len(vals)} median "
                  f"{statistics.median(vals)!r} spread {sp!r} "
                  f"correct {sum(r['correct'] for r in rs)}/{len(rs)}")
        print(f"{m}: widest spread {widest!r}, 5x = {5 * widest!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
