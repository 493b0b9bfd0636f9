"""Readings that the limits in ``bench/limits/`` are set from, on the chip,
many seeds in one process.

    python3 bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--what program|control|half_batch] [--seconds 2]

``program`` runs the cell as a benchmark run does (a short window) and
prints the numbers it compares. ``control`` puts the reference, computed
one precision lower, in the program's place; ``half_batch`` (training)
puts the reference fed half of each batch there. Each line printed is
``reading <what> <seed> <json of numbers>``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), os.path.join(os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "src")]

from bench import compare, harness  # noqa: E402


def train_variant(cell, seed: int, what: str) -> dict:
    from bench.runners.train import rows_at
    c, tr = cell.config, cell.traffic
    batches = [rows_at(seed, s, tr["rows"], tr["seq"], c["vocab_size"])
               for s in range(tr["checked_steps"])]
    ref = cell.reference
    want = ref.train(c, tr["optimizer"], seed, batches)
    if what == "control":
        got = ref.train(c, tr["optimizer"], seed, batches, mode="fp8")
    else:
        got = ref.train(c, tr["optimizer"], seed, batches,
                        rows=max(1, tr["rows"] // 2))
    return compare.train_numbers(got, want)


def coll_control(cell, seed: int, devices) -> dict:
    """The collective cell with each call made in bfloat16."""
    import jax.numpy as jnp
    from bench.runners import collective
    mod = cell.runner()

    def bf16_call(comm, prim, v, nd):
        return collective.program_call(comm, prim, v.astype(jnp.bfloat16),
                                       nd).astype(jnp.float32)
    mod.CALL = bf16_call
    return _run_kind(cell, mod, seed, 2.0, devices)["numbers"]


def serve_control(cell, seed: int, devices, seconds: float) -> dict:
    """The served sequences of a short run, read by the fp8 reference: the
    gap of the token it puts first, in the float32 reference."""
    mod = cell.runner()
    seen = {}
    orig = mod.check

    def check(ref, c, seed, rows, s_ctx):
        import numpy as np
        seen["program"] = orig(ref, c, seed, rows, s_ctx)
        toks, first, last = mod.tokens_and_first(rows, s_ctx)
        lo = ref.teacher_forced(c, seed, toks, mode="fp8")
        hi = ref.teacher_forced(c, seed, toks,
                                probe=lo[:, :, 1].astype(np.int32))
        gap = 0.0
        for n in range(len(rows)):
            z = hi[n, first[n]:last[n] + 1]
            gap = max(gap, float(np.max(z[:, 0] - z[:, 3])))
        seen["control"] = gap
        return seen["program"]
    mod.check = check
    _run_kind(cell, mod, seed, seconds, devices)
    return {"logit_gap": seen["control"], "program_logit_gap":
            seen["program"]}


def _run_kind(cell, mod, seed, seconds, devices):
    import bench.run as R
    ctx = R.Context(cell, seed, seconds, False, devices,
                    harness.CompileCounter())
    return mod.run(ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program",
                    choices=("program", "control", "half_batch"))
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    devices = harness.chips(cell.chips)
    kind = cell.traffic["kind"]
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.what == "program":
            nums = _run_kind(cell, cell.runner(), seed, args.seconds,
                             devices)["numbers"]
        elif kind == "train":
            nums = train_variant(cell, seed, args.what)
        elif kind == "collective":
            nums = coll_control(cell, seed, devices)
        else:
            nums = serve_control(cell, seed, devices, args.seconds)
        print("reading", args.what, seed, json.dumps(nums), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
