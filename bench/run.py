"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, compiles, warm-up) is timed as ``setup_s``,
then the cell's traffic runs for ``--seconds``; with ``--trace 1`` a short
stretch after the window is profiled for the per-layer metrics. What ran
is then checked against the configuration's plain reference. The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and ``checks``: every number
compared with its limit); the checks are also the last lines of standard
error. Off TPU, or with fewer chips than the cell asks for, it prints no
result and exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

from bench import compare, harness  # noqa: E402


class Context:
    """What a runner is given, and how it marks its window."""

    def __init__(self, cell, seed, seconds, trace, devices, counter):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.devices, self.counter = trace, devices, counter
        self.setup_s = None
        self.window_compiles = None

    def start_window(self):
        self.setup_s = harness.now() - T_START
        self._mark = self.counter.mark()

    def end_window(self):
        c, t = self.counter.mark()
        self.window_compiles = {"compiles": c - self._mark[0],
                                "traces": t - self._mark[1]}

    @staticmethod
    def note(msg: str):
        print(msg, file=sys.stderr, flush=True)

    def phase(self, name: str):
        """Mark the end of a set-up phase on standard error."""
        self.note(f"setup phase {name} done at {harness.now() - T_START} s")


def run_cell(cell, *, seed: int, seconds: float, trace: bool, devices,
             peaks: dict | None = None, limits: dict | None = None):
    """Drive one cell on ``devices``; returns the result object.
    ``peaks``: the device's row of ``peaks.json`` (looked up by kind);
    ``limits``: the cell's (``limits/<cell>.json``) unless given."""
    ctx = Context(cell, seed, seconds, trace, devices, harness.CompileCounter())
    out = cell.runner().run(ctx)
    lim = limits or compare.limits(cell.name)
    checks = compare.checks(out["numbers"], lim)
    ctx.note(f"setup_s {ctx.setup_s} of which compile "
             f"{ctx.counter.seconds} s; {ctx.counter.compiles} programs, "
             f"{ctx.counter.misses} not in the persistent cache; in the window "
             f"{ctx.window_compiles}; peak {out['peak_bytes']} B")
    values = dict(out["values"], setup_s=ctx.setup_s)
    rec = dict(out["rec"], config=cell.config, traffic=cell.traffic,
               peaks=peaks or harness.peaks(devices[0].device_kind))
    device = dict(harness.describe(devices),
                  memory_peak_bytes=out["peak_bytes"])
    result = harness.report(
        cell, trace_on=trace,
        correct=compare.passed(checks) and not out["failed"],
        attempted=out["attempted"], failed=out["failed"], values=values,
        rec=rec, device=device, checks=checks)
    result["window_compiles"] = ctx.window_compiles
    result.update(out.get("extra", {}))
    result["checks"] = result.pop("checks")        # last key
    harness.print_checks(checks)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    try:
        devices = harness.chips(cell.chips)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices)
    print(harness.line(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
