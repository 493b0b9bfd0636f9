"""How far the planner's estimate lies from the device: for each traced
collective shape, the chips' device time per call under its ``comm.*``
scope over the planner's estimate for that scope (bench/spans.py
``plan_ratios``), as |log2| of the ratio; the median over the shapes.
None for a program whose ops carry no such scope."""
import math
import statistics

from bench import spans


def read(rec):
    q = spans.plan_ratios(spans.of_run(rec, scopes=True), rec["config"])
    if not q:
        return None
    return statistics.median(abs(math.log2(v)) for v in q.values())
