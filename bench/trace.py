"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

A TPU trace (``.xplane.pb``) has one plane per chip (``/device:TPU:<i>``)
whose ``XLA Ops`` line holds every operation the chip ran (named by its
HLO text, ``%<instruction> = <type> <opcode>(...)``; a ``while`` spans its
body's ops), ``Async XLA Ops`` the asynchronous ones from start to done,
and ``XLA Modules`` each program run; the host plane's lines hold the
harness's ``TraceAnnotation`` spans, on the same clock. The reduction
keeps to the traced stretch, the span named ``WINDOW``:

* busy time of a chip: the union of its operations' intervals;
* collective time of a chip: the union of its collective operations'
  intervals, an asynchronous collective counting from its ``-start`` to
  its ``-done`` (the done op alone is only the wait at its end): paired
  in ``XLA Ops``, as one event in ``Async XLA Ops``;
* device time per program (module) and per kind of operation (the
  instruction's name without its number; a fusion with its kind);
* the chip's idle time, each piece named by the innermost harness span
  (``bench.*``) that covers it.
"""
from __future__ import annotations

import collections
import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = ("while", "conditional", "call")   # they span their bodies' ops
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# a collective, by its HLO opcode or by the JAX name XLA gives the op
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|ragged-all-to-all|psum|pmax|pmin|all_gather|"
    r"reduce_scatter|all_to_all|ppermute)(-start|-done)?([._-].*)?$")
TOP = 10


def find_xplane(path: str) -> str:
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals; returns them sorted and disjoint."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def instruction(text: str) -> str:
    """``%fusion.8 = bf16[..] fusion(..)`` -> ``fusion.8``."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


def opcode(text: str) -> str:
    """``%x = bf16[..]{..} all-reduce(..)`` -> ``all-reduce``: the first
    word after the type that opens a parenthesis ('' for other names)."""
    m = re.search(r" = .*?\s([a-z][a-z0-9-]*)\(", text)
    return m.group(1) if m else ""


def is_collective(text: str):
    """The collective's kind and phase (-start, -done or None), or None."""
    for name in (opcode(text), instruction(text)):
        m = COLLECTIVE.match(name)
        if m:
            return m.group(1), m.group(2)
    return None


def family(text: str) -> str:
    """An op's kind: its instruction without the number (``fusion.8`` ->
    ``fusion``), a fusion with its kind (``fusion:kOutput``)."""
    base = re.sub(r"([.-]\d+)+$", "", instruction(text))
    kind = re.search(r"kind=(k\w+)", text)
    return f"{base}:{kind.group(1)}" if kind and "fusion" in base else base


def collective_intervals(ops) -> list[tuple[int, int]]:
    """(start, end) of each collective among ``ops`` ((name, start, end),
    in time order): a ``-start`` op opens an interval that the next
    ``-done`` of the same collective kind closes."""
    out, open_ = [], collections.defaultdict(list)
    for name, s, e in ops:
        m = is_collective(name)
        if not m:
            continue
        kind, phase = m
        if phase == "-start":
            open_[kind].append(s)
        elif phase == "-done":
            start = open_[kind].pop(0) if open_[kind] else s
            out.append((start, e))
        else:
            out.append((s, e))
    return out


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def reduce_planes(planes) -> dict:
    """``planes``: iterable of (plane name, {line name: [(event name,
    start ns, end ns)]}). Returns busy, window and collective seconds
    (averaged over chips) and the breakdown."""
    spans, chips = [], {}
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        if m:
            chips[int(m.group(1))] = {
                k: sorted(lines.get(k, []), key=lambda x: x[1])
                for k in (OPS_LINE, ASYNC_LINE, MODULES_LINE)}
        else:
            for evs in lines.values():
                spans += [x for x in evs if x[0].startswith("bench.")]
    win = [x for x in spans if x[0] == WINDOW]
    if not win or not chips:
        raise ValueError("trace has no window span or no TPU ops")
    lo, hi = min(x[1] for x in win), max(x[2] for x in win)
    inner = [x for x in spans if x[0] != WINDOW]
    busy, coll, op_time = [], [], collections.Counter()
    mod_time, gaps = collections.Counter(), collections.Counter()
    for i in sorted(chips):
        inside = {k: [x for x in v if x[2] > lo and x[1] < hi]
                  for k, v in chips[i].items()}
        ops = inside[OPS_LINE]
        iv = clip([(s, e) for _, s, e in ops], lo, hi)
        busy.append(covered(iv))
        coll.append(covered(clip(
            collective_intervals(ops)
            + [(s, e) for n, s, e in inside[ASYNC_LINE]
               if is_collective(n)], lo, hi)))
        for name, s, e in ops:
            f = family(name)
            if f not in CONTAINERS:
                op_time[f] += min(e, hi) - max(s, lo)
        for name, s, e in inside[MODULES_LINE]:
            mod_time[name.split("(")[0]] += min(e, hi) - max(s, lo)
        merged = union(iv)
        edges = [lo] + [t for s, e in merged for t in (s, e)] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                for name, ns in _host_doing(inner, s, e):
                    gaps[name] += ns
    n = len(chips)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "collective_s": sum(coll) / n / 1e9,
        "chips": n,
        "op_s": {k: v / n / 1e9 for k, v in op_time.items()},
        "module_s": {k: v / n / 1e9 for k, v in mod_time.items()},
        "breakdown": {
            "device_ops": [[k, v / n / 1e9]
                           for k, v in op_time.most_common(TOP)],
            "idle_gaps": [[k, v / n / 1e9]
                          for k, v in gaps.most_common(TOP)]},
    }


def _host_doing(spans, s: int, e: int):
    """[(span name, ns)]: the gap [s, e) cut at the spans' edges, each
    piece named by the innermost (shortest) harness span covering it."""
    near = [x for x in spans if x[2] > s and x[1] < e]
    cuts = sorted({s, e} | {t for _, a, b in near for t in (a, b)
                             if s < t < e})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        cover = [x for x in near if x[1] <= a and x[2] >= b]
        name = (min(cover, key=lambda x: x[2] - x[1])[0] if cover
                else "host (no span)")
        out.append((name, b - a))
    return out


def reduce_dir(path) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(str(path)))
    return reduce_planes(
        (p.name, {ln.name: _events(ln) for ln in p.lines})
        for p in pd.planes)
