"""Ring-rotation algorithm bodies for the collective-fused kernels.

Registered here, and only here: the CI import-surface grep pins every raw
``jax.lax`` use in ``kernels/collective`` to this module, so the ppermute
rings live inside registered algorithm bodies exactly like the core
``comm.py`` flows.

``ring_fused``   (all_gather)  one source block delivered per ppermute hop;
                 an optional ``consume_fn`` merges each block in flight
                 (ring attention's kv loop), so the gathered array never
                 materializes.  Without a consumer the body assembles the
                 gather -- pure movement, bit-identical to the direct flow.
``ag_prologue``  (all_gather)  ring gather with a per-block prologue map:
                 row-wise compute (norm / matmul) runs on each source block
                 as it arrives.  The identity map is a plain ring gather,
                 so the conformance cell is bit-identical.
``rs_epilogue``  (reduce_scatter)  ring reduce-scatter whose per-tile
                 contribution is produced on demand (``tile_fn``), fusing a
                 matmul epilogue: the full partial-sum activation never
                 materializes.  The ring's reduction order differs from the
                 native psum-scatter, so bit-identity holds exactly for
                 order-insensitive payloads (integer-valued fp32 -- the
                 conformance contract) and to documented tolerance
                 otherwise.

All three are ``stage="cm"`` / ``table_ii=False`` registry entries (the
§V-C ``compressed`` flow's precedent): fusing comm into compute is
cross-domain modulation in PID-Comm's taxonomy, but none of these widens
the paper's Table II applicability rows.  They dispatch like any other
registered algorithm (``comm.all_gather(x, axis=1,
algorithm="ring_fused")``), which is what lets the planner race them and
the microbench sweep price them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.comm import (
    CommEvent, _REDUCERS, _TRACES, _emit, _merge_front_blocks,
    _payload_bytes, _split_axis_to_front, get_algorithm, register_algorithm)

__all__ = ["dispatch_fused", "take_block"]


def take_block(x, t, size, *, axis):
    """Block ``t`` (length ``size``, possibly traced ``t``) of ``x`` along
    ``axis`` -- the lazy-tile helper the fused matmul wrappers use so they
    never touch ``jax.lax`` directly."""
    return lax.dynamic_slice_in_dim(x, t * size, size, axis=axis)


def _ring_deliveries(comm, block, consume, state):
    """Rotate ``block`` (any pytree) around the group's ring.  Every
    shard's block is delivered to every member exactly once: hop ``s``
    brings the block owned by shard ``(me - s) % g``.
    ``consume(state, src, block) -> state`` folds each delivery; hop 0 is
    the shard's own block, so compute on it overlaps the first transfer."""
    g, ax = comm.group_size, comm.ax
    me = lax.axis_index(ax)
    fwd = [(i, (i + 1) % g) for i in range(g)]
    cur = block
    state = consume(state, me, cur)
    for s in range(1, g):
        cur = jax.tree_util.tree_map(
            lambda a: lax.ppermute(a, ax, fwd), cur)
        state = consume(state, (me - s) % g, cur)
    return state


@register_algorithm("all_gather", "ring_fused", stage="cm", table_ii=False)
def _ag_ring_fused(comm, x, *, axis, consume_fn=None, init=None):
    """Ring all-gather.  With ``consume_fn`` (state, src, block) -> state,
    each delivered block is merged in flight from ``init`` and the merged
    state is returned -- the full gather never materializes (ring
    attention).  Without it, assembles the gathered array (bit-identical
    to the direct gather: pure movement)."""
    if consume_fn is not None:
        return _ring_deliveries(comm, x, consume_fn, init)
    g = comm.group_size

    def place(out, src, blk):
        return lax.dynamic_update_index_in_dim(out, blk, src, axis=0)

    out = _ring_deliveries(comm, x, place, jnp.zeros((g,) + x.shape, x.dtype))
    return _merge_front_blocks(out, axis)


@register_algorithm("all_gather", "ag_prologue", stage="cm", table_ii=False)
def _ag_prologue(comm, x, *, axis, block_fn=None):
    """Ring all-gather with a fused per-block prologue: ``block_fn`` maps
    each source block as it arrives, so row-wise downstream compute runs
    per hop instead of on the assembled array.  Because ``block_fn`` is
    row-wise, the assembled result is bit-identical to
    ``block_fn(all_gather(x))`` -- concatenation is exact."""
    g = comm.group_size
    if block_fn is None:
        block_fn = lambda b: b
    mapped = jax.eval_shape(block_fn, x)

    def place(out, src, blk):
        return lax.dynamic_update_index_in_dim(out, block_fn(blk), src,
                                               axis=0)

    out = _ring_deliveries(
        comm, x, place, jnp.zeros((g,) + mapped.shape, mapped.dtype))
    return _merge_front_blocks(out, axis)


@register_algorithm("reduce_scatter", "rs_epilogue", stage="cm",
                    table_ii=False)
def _rs_epilogue(comm, x, *, axis, op="add", tile_fn=None):
    """Ring reduce-scatter with lazily produced tiles: ``tile_fn(t)`` is
    this shard's contribution to output tile ``t`` (default: the ``t``-th
    block of ``x`` along ``axis``).  A matmul epilogue passes a ``tile_fn``
    that computes ``h[tile t] @ w`` on demand, so only one 1/G tile of the
    partial product is live per hop.

    Ring schedule (shifted so shard ``i`` finishes holding tile ``i``, the
    reduce_scatter placement contract): start from tile ``(me - 1) % g``;
    each of the ``g - 1`` hops forwards the running partial and folds in
    the local contribution to the tile just received."""
    g, ax = comm.group_size, comm.ax
    if tile_fn is None:
        blocks = _split_axis_to_front(x, axis, g)
        tile_fn = lambda t: lax.dynamic_index_in_dim(
            blocks, t, axis=0, keepdims=False)
    comb = _REDUCERS[op][2]
    me = lax.axis_index(ax)
    fwd = [(i, (i + 1) % g) for i in range(g)]
    cur = tile_fn((me - 1) % g)
    for s in range(g - 1):
        got = lax.ppermute(cur, ax, fwd)
        cur = comb(got, tile_fn((me - 2 - s) % g))
    return cur


def dispatch_fused(comm, primitive, flow, x, *, payload_bytes=None,
                   **kwargs):
    """Eagerly dispatch a compute-fused registry flow with the same
    planner-estimated :class:`~repro.core.comm.CommEvent` a plain dispatch
    emits. Callable-carrying flows cannot be recorded into a CommProgram,
    so they always run eagerly.

    ``x`` may be a pytree (ring attention rotates the ``(k, v)`` pair);
    payload accounting sums the leaves unless ``payload_bytes`` overrides
    it (a lazy-tile epilogue's logical buffer never exists, so its bytes
    are supplied by the wrapper)."""
    spec = get_algorithm(primitive, flow)
    if payload_bytes is None:
        payload_bytes = sum(
            _payload_bytes(leaf) for leaf in jax.tree_util.tree_leaves(x))
    if _TRACES:
        from repro.core import planner
        est = planner.estimate(comm.cube, primitive, comm.dims,
                               payload_bytes, algorithm=flow)
        _emit(CommEvent(
            primitive=primitive, bitmap=comm.bitmap, dims=comm.dims,
            algorithm=flow, flow=flow, stage=spec.stage,
            group_size=comm.group_size, num_instances=comm.num_instances,
            payload_bytes=payload_bytes, ici_bytes=est.ici_bytes,
            dcn_bytes=est.dcn_bytes, seconds=est.seconds,
            est_source=est.est_source))
    return spec.fn(comm, x, **kwargs)
