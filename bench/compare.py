"""The comparisons that decide ``correct``, and their limits.

Each cell's limits sit in ``limits/<cell>.json`` beside the readings they
were set from; a run compares each number with its limit and is correct
only when every number is at or under it.
"""
from __future__ import annotations

import math
import statistics

from bench.harness import BENCH, load_json


def limits(cell: str) -> dict:
    return load_json(BENCH / "limits" / f"{cell}.json")["limits"]


def rel_gap(a: float, b: float) -> float:
    """|a - b| / |b|, and infinity where a is not a finite number."""
    if not math.isfinite(a):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-30)


def worst_rel(prog, ref) -> float:
    if len(prog) != len(ref):
        return math.inf
    return max(rel_gap(a, b) for a, b in zip(prog, ref))


def worst_leaf(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    """The widest gap between two norms of one leaf, over the leaves:
    |program's norm - reference's norm| against the larger of the
    reference's norm of that leaf and of the median leaf."""
    med = statistics.median(ref.values())
    worst, at = 0.0, ""
    for k, r in ref.items():
        if k in skip:
            continue
        p = prog.get(k, math.nan)
        g = (abs(p - r) / max(r, med, 1e-30) if math.isfinite(p)
             else math.inf)
        if g > worst or not at:
            worst, at = g, k
    return worst, at


def negligible(first_grad: dict, share: float = 1e-3) -> set:
    """Leaves whose reference gradient is nought to rounding: under
    ``share`` of the median leaf's. Adam moves them by round-off alone, so
    their change is not compared."""
    med = statistics.median(first_grad.values())
    return {k for k, v in first_grad.items() if v < share * med}


def train_numbers(prog: dict, ref: dict) -> dict:
    """The four numbers a training cell compares (see PERF.md)."""
    skip = negligible(ref["first_grad"])
    grad, grad_at = worst_leaf(prog["first_grad"], ref["first_grad"])
    upd, upd_at = worst_leaf(prog["change"], ref["change"], skip)
    return {"loss_gap": worst_rel(prog["losses"], ref["losses"]),
            "gnorm_gap": worst_rel(prog["gnorms"], ref["gnorms"]),
            "grad_gap": grad, "update_gap": upd,
            "_where": {"grad_gap": grad_at, "update_gap": upd_at,
                       "skipped": sorted(skip)}}


def checks(numbers: dict, lim: dict) -> list:
    return [(k, numbers[k], lim[k]) for k in lim]


def passed(chk: list) -> bool:
    return all(v <= lim for _, v, lim in chk)
