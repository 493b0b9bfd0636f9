"""Print the structure of a JAX profiler trace: its planes, their lines,
and the longest events of each line with their stats.

    python3 bench/tools/dump_trace.py <trace dir or .xplane.pb> [events]
"""
from __future__ import annotations

import collections
import glob
import os
import sys


def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def main(argv) -> int:
    from jax.profiler import ProfileData
    n_top = int(argv[1]) if len(argv) > 1 else 12
    pd = ProfileData.from_file(find_xplane(argv[0]))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  line {line.name!r}: {len(evs)} events, "
                  f"span {t0}..{t1} ({(t1 - t0) / 1e9:.4f} s)")
            tot = collections.Counter()
            for e in evs:
                tot[e.name] += e.duration_ns
            for name, ns in tot.most_common(n_top):
                ex = next(e for e in evs if e.name == name)
                stats = {k: v for k, v in ex.stats}
                print(f"    {ns / 1e6:10.3f} ms  {name!r}  "
                      f"{str(stats)[:300]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
