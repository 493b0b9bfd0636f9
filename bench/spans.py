"""The program's own spans on the device trace's clock: each stretch of
device idle time named by the innermost span that covers it, and device
time per collective scope.

``bench/trace.py`` names idle time by the harness's spans (``bench.*``)
alone. While the profiler records, the program opens its own spans
(``repro.telemetry.spans``: ``serve.step``, ``serve.program``,
``train.wait``, ``host.gc``, ...) in the same host planes, and every
collective's device ops carry the planner's key as a named scope
(``comm.<primitive>.<bitmap>.<flow>.<payload bytes>``,
``repro.core.comm.scope_name``). This reduction reads the same trace,
within the same window (``bench.window``):

* each chip's idle time (the window less the union of its ops) is cut at
  every span edge; a piece is named by the innermost (shortest) program
  span that covers it, else by the innermost harness span, else
  ``host (no span)`` (``idle_by_span``);
* ``idle_under``: for each span name, the idle time that spans of that
  name cover at any depth; ``count``: how many of them the window holds;
  ``span_s``: their summed length within the window;
* ``scopes``: for each ``comm.*`` scope, the chips' device time under it
  (the union of its ops, an asynchronous collective from start to done)
  and the program runs it ran in.

Every time is in seconds, averaged over the chips. A trace of a program
without these spans or scopes gives empty tables, never an error.
"""
from __future__ import annotations

import bisect
import collections
import math
import re

from bench import trace

PROGRAM_SPAN = re.compile(r"^(serve|train|host|checkpoint|program)\.\w")
SCOPE = re.compile(r"comm\.[a-z_]+\.[01]+\.[a-z_]+\.\d+")
NO_SPAN = "host (no span)"


def _is_span(name: str) -> bool:
    return name.startswith("bench.") or bool(PROGRAM_SPAN.match(name))


def _name(cover) -> str:
    """The innermost program span among ``cover``, else the innermost
    harness span, else NO_SPAN."""
    prog = [x for x in cover if not x[0].startswith("bench.")]
    pick = prog or cover
    return min(pick, key=lambda x: x[2] - x[1])[0] if pick else NO_SPAN


def segments(spans, lo: int, hi: int):
    """[(a, b, covering spans)]: [lo, hi) cut at every span edge, by one
    sweep over the edges."""
    edges = collections.defaultdict(lambda: ([], []))   # t: (opens, closes)
    for k, (_, s, e) in enumerate(spans):
        if e > s:
            edges[max(s, lo)][0].append(k)
            edges[min(e, hi)][1].append(k)
    cuts = sorted(set(edges) | {lo, hi})
    out, open_ = [], {}
    for a, b in zip(cuts, cuts[1:]):
        opens, closes = edges.get(a, ((), ()))
        for k in closes:
            del open_[k]
        open_.update((k, spans[k]) for k in opens)
        out.append((a, b, list(open_.values())))
    return out


def _overlaps(gaps, segs):
    """ns of the disjoint, sorted ``gaps`` inside each of ``segs``
    (sorted, disjoint), by one sweep."""
    out, i = [0] * len(segs), 0
    for s, e in gaps:
        while i < len(segs) and segs[i][1] <= s:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < e:
            out[j] += min(e, segs[j][1]) - max(s, segs[j][0])
            j += 1
    return out


def scope_of(name: str, stats: dict):
    """The ``comm.*`` scope in an op's name or in one of its stats (the
    HLO ``op_name``), or None."""
    m = SCOPE.search(name)
    if m:
        return m.group(0)
    for v in stats.values():
        m = SCOPE.search(v) if isinstance(v, str) else None
        if m:
            return m.group(0)
    return None


def _scope_time(ops, modules, lo: int, hi: int):
    """{scope: (ns under it, program runs it ran in)} on one chip;
    ``ops``: (name, start, end, scope)."""
    by = collections.defaultdict(list)
    for op in ops:
        if op[3]:
            by[op[3]].append(op[:3])
    starts = [m[1] for m in modules]
    out = {}
    for scope, sops in by.items():
        iv = [(s, e) for _, s, e in sops] + trace.collective_intervals(sops)
        runs = set()
        for _, s, e in sops:
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and e <= modules[k][2]:
                runs.add(k)
        out[scope] = (trace.covered(trace.clip(iv, lo, hi)), len(runs))
    return out


def _lengths(spans, lo: int, hi: int) -> collections.Counter:
    out = collections.Counter()
    for name, s, e in spans:
        out[name] += min(e, hi) - max(s, lo)
    return out


def reduce_planes(planes) -> dict:
    """``planes``: (plane name, {line name: [(event name, start ns, end ns)
    or (..., scope)]}), as ``bench.trace.reduce_planes`` takes them, a
    device op with its ``comm.*`` scope as a fourth item where it has
    one."""
    spans, chips = [], {}
    for pname, lines in planes:
        m = trace.DEVICE_PLANE.match(pname)
        if m:
            chips[int(m.group(1))] = {
                k: sorted(lines.get(k, []), key=lambda x: x[1])
                for k in (trace.OPS_LINE, trace.MODULES_LINE)}
        else:
            spans += [x[:3] for evs in lines.values() for x in evs
                      if _is_span(x[0])]
    win = [x for x in spans if x[0] == trace.WINDOW]
    if not win or not chips:
        raise ValueError("trace has no window span or no TPU ops")
    lo, hi = min(x[1] for x in win), max(x[2] for x in win)
    inner = [x for x in spans if x[0] != trace.WINDOW
             and x[2] > lo and x[1] < hi]
    segs = segments(inner, lo, hi)
    idle = [0] * len(segs)
    scope_ns = collections.Counter()
    scope_runs = collections.Counter()
    for i in sorted(chips):
        ops = [x for x in chips[i][trace.OPS_LINE] if x[2] > lo and x[1] < hi]
        merged = trace.union(trace.clip([x[1:3] for x in ops], lo, hi))
        edges = [lo] + [t for s, e in merged for t in (s, e)] + [hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        idle = [a + b for a, b in zip(idle, _overlaps(gaps, segs))]
        ops4 = [x if len(x) > 3 else x + (None,) for x in ops]
        for scope, (ns, runs) in _scope_time(
                ops4, chips[i][trace.MODULES_LINE], lo, hi).items():
            scope_ns[scope] += ns
            scope_runs[scope] = max(scope_runs[scope], runs)
    n = len(chips)
    by_span, under = collections.Counter(), collections.Counter()
    for (_, _, cover), ns in zip(segs, idle):
        if ns:
            by_span[_name(cover)] += ns
            for name in {x[0] for x in cover}:
                under[name] += ns
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_by_span": {k: v / n / 1e9 for k, v in by_span.most_common()},
        "idle_under": {k: v / n / 1e9 for k, v in under.most_common()},
        "count": dict(collections.Counter(x[0] for x in inner)),
        "span_s": {k: v / 1e9 for k, v in _lengths(inner, lo, hi).items()},
        "scopes": {k: {"seconds": scope_ns[k] / n / 1e9,
                       "calls": scope_runs[k]} for k in sorted(scope_ns)},
    }


def planes_of(path, scopes: bool):
    """The planes of the trace under the directory ``path``; with
    ``scopes``, each device op found under a ``comm.*`` scope carries it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(trace.find_xplane(str(path)))
    out = []
    for p in pd.planes:
        ops = scopes and trace.DEVICE_PLANE.match(p.name)
        lines = {}
        for ln in p.lines:
            if ops and ln.name == trace.OPS_LINE:
                lines[ln.name] = [
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     scope_of(ev.name, dict(ev.stats)))
                    for ev in ln.events]
            else:
                lines[ln.name] = [
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in ln.events]
        out.append((p.name, lines))
    return out


_CACHE: dict = {}


def of_run(rec: dict, scopes: bool = False):
    """The reduction of this run's traced stretch (the trace the harness
    left in its trace directory), or None when the run was not traced."""
    if not rec.get("trace"):
        return None
    from bench import harness
    key = (trace.find_xplane(str(harness.TRACE_DIR)), scopes)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = reduce_planes(planes_of(harness.TRACE_DIR, scopes))
    return _CACHE[key]


def plan_ratios(reduction, config: dict) -> dict:
    """{scope: device seconds per call / the planner's estimate of it
    (``repro.core.comm.scope_estimate``)} on the configuration's cube;
    empty without scopes."""
    if not reduction or not reduction["scopes"]:
        return {}
    import jax
    from repro.compat import make_mesh
    from repro.core.comm import scope_estimate
    from repro.core.hypercube import Hypercube
    names = tuple(config["mesh_axes"])
    dims = tuple(config["dims"][n] for n in names)
    mesh = make_mesh(dims, names, devices=jax.devices()[:math.prod(dims)])
    cube = Hypercube.build(mesh, dict(config["dims"]))
    out = {}
    for scope, t in reduction["scopes"].items():
        est = scope_estimate(cube, scope).seconds
        if t["calls"] and est > 0:
            out[scope] = t["seconds"] / t["calls"] / est
    return out
