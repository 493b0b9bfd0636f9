"""The training attention's work and its device time.

Work: what the mathematics needs, from shapes alone (as in
``bench/flops.py``): the forward's q.k and p.v over the keys each query
sees, 4 x heads x head_dim FLOPs per visible key, and the backward 2.5x
that, as FlashAttention-2 counts it (five matmuls against the forward's
two); the forward run again by the layer remat is not counted, so the
count is the same whatever implements it. Bytes: q, k, v read and the output written in
the forward; q, k, v, the output and its gradient read and dq, dk, dv
written in the backward, in bfloat16, with the float32 log-sum-exp per
row and head written once and read once.

Device time: the program names every device op of the training
attention with an ``attn_scope`` frontend attribute (``attn.fwd`` /
``attn.bwd``, ``repro.models.layers._attn_scope``), which the op's HLO
text in the trace carries, and the scope in its ``op_name``; the time is
the union of those ops' intervals within the traced stretch (a tagged
``while`` spans its body), averaged over the chips. A trace of a program
without the tag gives None.
"""
from __future__ import annotations

import re

from bench import flops, trace

TAG = re.compile(r"attn\.(fwd|bwd)")
BWD_OVER_FWD = 2.5


def train_flops(c: dict, rows: int, seq: int) -> float:
    """Forward and backward FLOPs of every layer's attention for one step
    of ``rows`` rows of ``seq`` tokens."""
    return rows * (1 + BWD_OVER_FWD) * flops.attention_flops_fwd(c, seq)


def train_bytes(c: dict, rows: int, seq: int) -> float:
    """Bytes every layer's attention must move in one step."""
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    per_tok = 2 * (6 * H * hd + 6 * KV * hd) + 2 * 4 * H
    return c["num_hidden_layers"] * rows * seq * per_tok


def tagged(name: str, stats: dict) -> bool:
    """Whether a device op (its HLO text and its stats) is the training
    attention's."""
    return bool(TAG.search(name)) or any(
        isinstance(v, str) and TAG.search(v) for v in stats.values())


def reduce_planes(planes):
    """Seconds under the tag within ``bench.window``, averaged over the
    chips, or None. ``planes``: (plane name, {line name: [(event name,
    start ns, end ns, stats)]})."""
    win, chips = [], {}
    for pname, lines in planes:
        m = trace.DEVICE_PLANE.match(pname)
        if m:
            chips[int(m.group(1))] = [
                (s, e) for n, s, e, st in lines.get(trace.OPS_LINE, ())
                if tagged(n, st)]
        else:
            win += [(s, e) for evs in lines.values() for n, s, e, _ in evs
                    if n == trace.WINDOW]
    if not win or not chips:
        return None
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    ns = [trace.covered(trace.clip(iv, lo, hi)) for iv in chips.values()]
    if not any(ns):
        return None
    return sum(ns) / len(ns) / 1e9


_CACHE: dict = {}


def device_seconds(path=None):
    """:func:`reduce_planes` of the trace under ``path`` (the harness's
    trace directory by default); None where there is none."""
    if path is None:
        from bench import harness
        path = harness.TRACE_DIR
    try:
        key = trace.find_xplane(str(path))
    except FileNotFoundError:
        return None
    if key not in _CACHE:
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(key)
        _CACHE.clear()
        _CACHE[key] = reduce_planes(
            (p.name, {ln.name: [(ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns,
                                 dict(ev.stats) if p.name.startswith(
                                     "/device:") else {})
                                for ev in ln.events] for ln in p.lines})
            for p in pd.planes)
    return _CACHE[key]
