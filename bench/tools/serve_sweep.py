"""One sweep of offered rates for a serving cell, on the chip: the rate a
serve cell offers is fixed in its traffic file, and this finds the knee it
is set from.

    python3 bench/tools/serve_sweep.py --workload <cell> \
        --rates 0.96,1.2,1.44 [--seconds 30] [--ramp 20] [--seed 1]

The engine is built and warmed once. The rates run in the order given as
one unbroken arrival stream, the cell's own open loop: at each rate a
ramp of ``--ramp`` seconds, in which the lanes settle to that rate's
occupancy, then a window of ``--seconds``. Each line printed is
``rate <r> <json>``, over the requests due in that rate's window: how many
were due and admitted into a lane by its close, the backlog (queued and in
lanes) at its close, the wait for a lane (mean and 95th percentile, over
those admitted), the lanes in use and the engine's mean step.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), os.path.join(os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--ramp", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    devices = harness.chips(cell.chips)
    mod = cell.runner()
    c, tr = cell.config, cell.traffic
    engine = mod.build_engine(c, tr, args.seed, devices)
    mod.warm(engine, tr)

    rates = [float(r) for r in args.rates.split(",")]
    span = args.ramp + args.seconds
    stretches, arrivals = [], []
    for j, rate in enumerate(rates):
        at = dict(tr, rate_per_s=rate, table_seed=tr["table_seed"] + j)
        ramp = mod.stretch(at, args.seed, mod.RAMP, j * span, args.ramp,
                           c["vocab_size"])
        window = mod.stretch(at, args.seed, mod.WINDOW, j * span + args.ramp,
                             args.seconds, c["vocab_size"])
        stretches.append(window)
        arrivals += ramp + window
    for rid, t in enumerate(arrivals):
        t.req.rid = rid
    loop = mod.Loop(engine, c, arrivals)
    for j, (rate, window) in enumerate(zip(rates, stretches)):
        loop.run(j * span + args.ramp)
        engine.reset_metrics()
        k0 = len(loop.busy)
        loop.run((j + 1) * span)
        h = engine.metrics.histogram("serve.step_seconds")
        busy = loop.busy[k0:]
        wait = [t.admitted - (loop.t0 + t.due) for t in window
                if not math.isnan(t.admitted)]
        print("rate", rate, json.dumps({
            "due": len(window), "admitted": len(wait),
            "backlog_at_close": len(engine.queue) + int(
                engine.active_h.sum()),
            "wait_s_mean": statistics.fmean(wait) if wait else None,
            "wait_s_p95": mod.quantile(wait, 0.95) if wait else None,
            "lanes_in_use_mean": statistics.fmean(busy) if busy else 0.0,
            "lanes": tr["lanes"],
            "engine_step_ms": 1e3 * h.sum / max(h.count, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
