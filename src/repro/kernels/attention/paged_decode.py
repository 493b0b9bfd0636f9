"""Paged decode attention as a Pallas TPU kernel: each lane's one query row
attends the keys this shard holds for that lane, read in place from the
page pools.

A pool leaf keeps every layer, ``(n_units, pool_pages, page_size, KV,
hd)``, so one page of one layer is one contiguous ``page_size x KV x hd``
chunk. The kernel takes the leaf whole with the layer as a scalar (no slice
of the pool is ever materialised) and walks each lane's own blocks: one
grid step per lane, then a loop whose trip count is the lane's, over chunks
of ``pages_per_chunk`` blocks. A chunk's pages are DMAd whole (all kv heads
at once) into one of two VMEM buffers while the previous chunk is computed.
Only allocated blocks below the lane's position are read; a lane with none,
an idle lane for one, reads nothing and returns an empty partial.

One matmul scores every (query head, key head) pair of a chunk; the pairs
outside a head's GQA group are masked with the positions, so the
probabilities times the values sum only a head's own group. The arithmetic
is ``attn_decode``'s, in float32: the caller's scaled query, the scores,
and a running max, sum and output. The mask is
:func:`repro.models.layers.decode_mask`'s: what a lane sees is a run of
key positions that ends at its own, so the wrapper finds where the run
starts with it, and the kernel keeps the keys from there to just below
the lane's position (the current token's own key is the caller's to
merge).

The result is the shard's partial, ``(o, m, l)``: the output unnormalised,
with each head's running max and sum, for the flash-decode LSE combine
over the kv shards.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.models.layers import NEG_INF, decode_mask

Array = jax.Array

# score columns (keys x kv heads) per chunk: a chunk's pages are this many
# rows of head_dim in VMEM, ~128 KiB of bf16 keys at head_dim 128
CHUNK_ROWS = 512


def fits(kv_heads: int, head_dim: int, dtype) -> bool:
    """Whether the chip can DMA a page of this shape whole: its minor dims
    ``(kv_heads, head_dim)`` must fill the pool's tiles, head_dim whole
    rows of 128 lanes and the kv heads whole 32-bit sublanes (two or more
    bf16 heads). Interpret mode takes any shape."""
    return (head_dim % 128 == 0
            and kv_heads * jnp.dtype(dtype).itemsize % 4 == 0)


class PageWalk(NamedTuple):
    """What the kernel walks for every lane, the same for every layer of a
    step: ``pages`` (B * n_blk,) the local page id of each lane's block on
    this shard, -1 where unallocated; ``last`` (B,) one past the lane's
    last allocated block below its position; ``pos`` (B,) the lanes'
    positions; ``lo`` (1,) this shard's first slot."""
    pages: Array
    last: Array
    pos: Array
    lo: Array


def page_walk(pages: Array, pos: Array, lo, page_size: int) -> PageWalk:
    """The walk for one step from this shard's slice of the page table
    ``pages`` (B, n_blk) (-1 where unallocated), the lanes' positions and
    the shard's first slot ``lo``."""
    B, n_blk = pages.shape
    below = jnp.clip(pos - lo, 0, n_blk * page_size)
    upto = (below + page_size - 1) // page_size
    j = jnp.arange(n_blk)
    last = jnp.max(jnp.where((pages >= 0) & (j < upto[:, None]), j + 1, 0),
                   axis=1)
    return PageWalk(pages.reshape(-1).astype(jnp.int32),
                    last.astype(jnp.int32), pos.astype(jnp.int32),
                    jnp.reshape(lo, (1,)).astype(jnp.int32))


def _kernel(layer_ref, lo_ref, pos_ref, start_ref, first_ref, last_ref,
            pages_ref, cols_ref, rows_ref, q_ref, k_hbm, v_hbm,
            o_ref, m_ref, l_ref,
            k_buf, v_buf, sems, *, page_size: int, n_blk: int,
            pages_per_chunk: int):
    b = pl.program_id(0)
    P, ps = pages_per_chunk, page_size
    layer, lo = layer_ref[0], lo_ref[0]
    dq, start = pos_ref[b], start_ref[b]
    first, last = first_ref[b], last_ref[b]
    n_chunks = (jnp.maximum(last - first, 0) + P - 1) // P

    def pages(c):
        """(allocated, page id) of each block of chunk ``c``."""
        out = []
        for p in range(P):
            j = first + c * P + p
            pid = pages_ref[b * n_blk + jnp.minimum(j, n_blk - 1)]
            out.append(((j < last) & (pid >= 0), jnp.maximum(pid, 0)))
        return out

    def dmas(c, slot, go):
        for p, (ok, pid) in enumerate(pages(c)):
            @pl.when(ok)
            def _():
                for h, buf, n in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                    go(pltpu.make_async_copy(h.at[layer, pid], buf.at[slot, p],
                                             sems.at[n, slot]))

    @pl.when(b == 0)
    def _():
        # a chunk's unread pages keep what the buffer held: masked, but
        # their values still meet a zero probability, so never NaN
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(n_chunks > 0)
    def _():
        dmas(0, 0, lambda d: d.start())

    q = q_ref[0]                                          # (H, hd)
    key_col, kv_col, page_col = (cols_ref[i:i + 1] for i in range(3))
    group = kv_col == rows_ref[...]                       # (H, cols)
    rows = k_buf.shape[1] * k_buf.shape[2] * k_buf.shape[3]

    def body(c, carry):
        m, l, acc = carry
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            dmas(c + 1, 1 - slot, lambda d: d.start())

        dmas(c, slot, lambda d: d.wait())
        alloc = jnp.zeros_like(page_col)
        for p, (ok, _) in enumerate(pages(c)):
            alloc = jnp.where(page_col == p, ok.astype(jnp.int32), alloc)
        k = k_buf[slot].astype(jnp.float32).reshape(rows, -1)
        v = v_buf[slot].astype(jnp.float32).reshape(rows, -1)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        dk = lo + (first + c * P) * ps + key_col           # (1, cols)
        ok = group & (alloc > 0) & (dk >= start) & (dk < dq)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        a = jnp.exp(m - m_new)
        l = a * l + p.sum(axis=1, keepdims=True)
        acc = a * acc + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    H, hd = q.shape
    m, l, acc = lax.fori_loop(
        0, n_chunks, body,
        (jnp.full((H, 1), NEG_INF, jnp.float32),
         jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, hd), jnp.float32)))
    o_ref[0] = acc
    m_ref[0] = m
    l_ref[0] = l


def paged_decode_attention(q: Array, k_pool: Array, v_pool: Array, layer,
                           window, walk: PageWalk, *,
                           interpret: bool = False):
    """q: (B, H, hd) float32, already scaled by ``hd ** -0.5``; ``k_pool``,
    ``v_pool``: this shard's pool leaves ``(n_units, pool_pages, page_size,
    KV, hd)``; ``layer``: the unit to read (int or traced scalar);
    ``window``: the layer's window (negative for none; int or traced).

    Returns float32 ``o`` (B, H, hd), unnormalised, and ``m``, ``l`` (B,
    H): each head's max score and sum of ``exp(score - m)`` over the keys
    the lane holds on this shard below its position (``m`` is ``NEG_INF``
    and ``l`` zero where there are none)."""
    B, H, hd = q.shape
    _, _, ps, KV, _ = k_pool.shape
    n_blk = walk.pages.shape[0] // B
    G = H // KV
    P = max(1, min(n_blk, CHUNK_ROWS // (ps * KV)))
    c = np.arange(P * ps * KV)
    cols = jnp.asarray(np.stack([c // KV, c % KV, c // (ps * KV)]),
                       jnp.int32)
    rows = jnp.asarray((np.arange(H) // G)[:, None], jnp.int32)
    # decode_mask sees a run of positions that ends at the query's: the
    # keys below it on this shard are those from the run's first on
    dk = walk.lo + jnp.arange(n_blk * ps)
    seen = (decode_mask(walk.pos[:, None], dk[None], window)
            & (dk[None] < walk.pos[:, None]))
    start = jnp.where(seen.any(axis=1), dk[jnp.argmax(seen, axis=1)],
                      walk.pos)
    first = (jnp.maximum(start - walk.lo, 0) // ps).astype(jnp.int32)
    scalars = (jnp.reshape(layer, (1,)).astype(jnp.int32), walk.lo,
               walk.pos, start.astype(jnp.int32), first, walk.last,
               walk.pages)
    const = lambda b, *_: (0, 0)
    lane = lambda b, *_: (b, 0, 0)
    kernel = functools.partial(_kernel, page_size=ps, n_blk=n_blk,
                               pages_per_chunk=P)
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B,),
            in_specs=[pl.BlockSpec(cols.shape, const),
                      pl.BlockSpec(rows.shape, const),
                      pl.BlockSpec((1, H, hd), lane),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, H, hd), lane),
                       pl.BlockSpec((1, H, 1), lane),
                       pl.BlockSpec((1, H, 1), lane)],
            scratch_shapes=[
                pltpu.VMEM((2, P, ps, KV, hd), k_pool.dtype),
                pltpu.VMEM((2, P, ps, KV, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=[jax.ShapeDtypeStruct((B, H, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(*scalars, cols, rows, q.astype(jnp.float32), k_pool, v_pool)
    return o, m[..., 0], l[..., 0]
