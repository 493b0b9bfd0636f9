"""Per-shard layer math shared by every architecture family.

``chunked_attention`` is the pure-jnp flash-attention formulation (blockwise
log-sum-exp accumulation). It doubles as the oracle for the Pallas kernel in
``repro.kernels.attention`` and keeps the dry-run's peak memory honest (no
S x S score materialization in the HLO). Its gradient is the
FlashAttention-2 backward (``_attention_bwd``): each block's probabilities
are recomputed from the forward's per-row log-sum-exp, so neither pass
keeps a score matrix.
"""
from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.xla_metadata import set_xla_metadata

from repro import compat

Array = jax.Array

NEG_INF = -1e30

# Cost-probe mode (see launch/dryrun.py run_probe): XLA cost_analysis counts
# a while-loop body once regardless of trip count, so probe lowerings unroll
# every scan (and cap inner-chunk trip counts at 4 -- identical FLOPs).
COST_PROBE = False

# Low-precision-stats mode (§Perf variants): 0 = off; 1 = bf16 operands with
# f32 dot accumulation ("lowp"); 2 = additionally keep the attention
# score/probability space in bf16, f32 only for the running max/denominator
# ("lowp2" -- what the fused Pallas kernel does in VMEM on real TPU).
LOWP = 0


def pscan(f, init, xs, unroll_hint: int = 1):
    return lax.scan(f, init, xs, unroll=True if COST_PROBE else unroll_hint)


def probe_trips(n: int) -> int:
    """Cap sequential trips in probe mode (FLOPs-preserving re-chunk)."""
    return min(n, 4) if COST_PROBE else n


def pvary_like(x, *refs):
    """Promote ``x``'s varying-axes (shard_map vma) to the union of the
    refs' -- needed for scan carries initialized from constants."""
    want = frozenset()
    for r in refs:
        want = want | compat.vma_of(r)
    need = tuple(sorted(want - compat.vma_of(x)))
    return compat.pvary(x, need) if need else x


def pvary_axes(x, axes):
    """Mark ``x`` as varying over the mesh ``axes`` it does not vary over
    yet (inside shard_map; an axis not bound there raises)."""
    need = tuple(a for a in axes if a not in compat.vma_of(x))
    return compat.pvary(x, need) if need else x


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    if LOWP >= 1 and dt == jnp.bfloat16:
        # f32 only in the reduction; the (.., D) tensor never converts
        var = jnp.mean(jnp.square(x).astype(jnp.float32), axis=-1,
                       keepdims=True)
        r = lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
        return x * r.astype(dt)
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))
            ).astype(dt)


def rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embedding. x: (..., S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs      # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                            # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: Array, wg: Array, wu: Array, wd: Array) -> Array:
    h = jax.nn.silu(x @ wg) * (x @ wu)
    return h @ wd


def _mask(q_pos: Array, k_pos: Array, causal: bool, window) -> Array:
    """(Sq, Sk) boolean visibility mask. window: python int or traced scalar;
    negative = full attention. Negative key positions (banded-path padding)
    are never visible."""
    dq = q_pos[:, None]
    dk = k_pos[None, :]
    ok = dk >= 0
    if causal:
        ok &= dk <= dq
    w = jnp.asarray(window)
    ok &= jnp.where(w < 0, True, (dq - dk) < w)
    return ok


def decode_mask(dq, dk, window):
    """Which keys a decoding query sees: key positions ``dk`` that are not
    negative, not after the query's ``dq``, and within ``window`` of it
    (negative: no window; a python int or traced scalar). Broadcasts.
    ``attn_decode`` and the paged decode kernel both mask with it."""
    ok = (dk <= dq) & (dk >= 0)
    wnd = jnp.asarray(window)
    return ok & jnp.where(wnd < 0, True, (dq - dk) < wnd)


def chunked_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                      window=-1, q_offset=0, k_offset=0,
                      chunk: int = 1024, partial: bool = False):
    """Blockwise (flash) attention with GQA, sliding window, offsets.

    q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) with H % KV == 0.
    ``q_offset``/``k_offset`` are the global positions of q[0]/k[0] (ints or
    traced scalars) -- used by context-parallel prefill and decode.

    Static sliding windows on aligned self-attention take the *banded* path:
    each query block only visits the (window + block) keys it can see,
    cutting attention FLOPs/bytes by ~Sk/(window+block) (mixtral SWA-4096 at
    32k prefill: ~6.4x).

    Returns (B, Sq, H, hd); if ``partial``, returns (acc, m, l) unnormalized
    so callers can LSE-combine partial results across shards (flash-decode).
    Differentiating the full result takes the FlashAttention-2 backward
    (``_attention_bwd``), which needs every query row to see at least one
    key.
    """
    if partial:
        return _chunked_attention(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, k_offset=k_offset,
                                  chunk=chunk, partial=True)
    banded = (isinstance(window, int) and window > 0 and causal
              and q.shape[1] == k.shape[1] and q.shape[1] > window
              and isinstance(q_offset, int) and q_offset == 0
              and isinstance(k_offset, int) and k_offset == 0)
    pos = (window, q_offset, k_offset)
    st = _AttnStatic(causal, chunk, banded,
                     *(x if isinstance(x, int) else None for x in pos))
    dyn = tuple(None if isinstance(x, int) else x for x in pos)
    return _attention(q, k, v, dyn, st)


def banded_attention(q: Array, k: Array, v: Array, *, window: int,
                     chunk: int = 1024, with_lse: bool = False):
    """Causal sliding-window attention visiting only the in-band keys.

    Scans over query blocks; each block attends to a static-size
    (window_pad + block) key slice ending at its last position.
    ``with_lse``: return (out in float32, per-row log-sum-exp) instead.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    Cq = min(chunk, S)
    nq = S // Cq              # vmapped (batched), not scanned: probe-exact
    W = min(window, S)
    # pad keys on the left so every block's band is a static-size slice
    Wp = ((W - 1) // Cq + 1) * Cq                   # band rounded to blocks
    band = Wp + Cq
    kp = jnp.pad(k, ((0, 0), (Wp, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (Wp, 0), (0, 0), (0, 0)))
    qb = q.reshape(B, nq, Cq, H, hd)

    def block(qi, i):
        # keys for block i: global positions [i*Cq - Wp, i*Cq + Cq)
        kb = lax.dynamic_slice_in_dim(kp, i * Cq, band, axis=1)
        vb = lax.dynamic_slice_in_dim(vp, i * Cq, band, axis=1)
        o = _chunked_attention(
            qi, kb, vb, causal=True, window=window,
            q_offset=i * Cq, k_offset=i * Cq - Wp, chunk=band)
        return o

    if with_lse:
        # the training forward: rows in sub-blocks of Cr, one at a time, so
        # that at most FWD_SCORE_BYTES of scores exist at once; each row
        # still sees its block's whole band, as in the batched path
        Cr = _block(Cq, max(1, FWD_SCORE_BYTES // (B * H * band * 4)))

        def rows(_, t):
            i = t * Cr // Cq
            kb = lax.dynamic_slice_in_dim(kp, i * Cq, band, axis=1)
            vb = lax.dynamic_slice_in_dim(vp, i * Cq, band, axis=1)
            return None, _chunked_attention(
                lax.dynamic_slice_in_dim(q, t * Cr, Cr, axis=1), kb, vb,
                causal=True, window=window, q_offset=t * Cr,
                k_offset=i * Cq - Wp, chunk=band, with_lse=True)
        _, (outs, lse) = pscan(rows, None, jnp.arange(S // Cr))
        return (jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd),
                jnp.moveaxis(lse, 0, 3).reshape(B, KV, G, S))
    outs = jax.vmap(block, in_axes=(1, 0), out_axes=1)(
        qb, jnp.arange(nq))
    return outs.reshape(B, S, H, hd)


def _chunked_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                       window=-1, q_offset=0, k_offset=0,
                       chunk: int = 1024, partial: bool = False,
                       with_lse: bool = False):
    """The forward. ``with_lse``: return (out in float32, per-row
    log-sum-exp (B, KV, G, Sq)) instead of out in q's dtype."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    scale = hd ** -0.5
    nc = probe_trips(max(Sk // min(chunk, Sk), 1))
    C = Sk // nc
    if LOWP >= 1 and q.dtype == jnp.bfloat16:
        # bf16 operands, f32 accumulation inside the dots -- no (B,S,..)
        # converts / f32 spills of q,k,v
        qf = (q * scale).reshape(B, Sq, KV, G, hd)
        kc = k.reshape(B, nc, C, KV, hd)
        vc = v.reshape(B, nc, C, KV, hd)
    else:
        qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KV, G, hd)
        kc = k.astype(jnp.float32).reshape(B, nc, C, KV, hd)
        vc = v.astype(jnp.float32).reshape(B, nc, C, KV, hd)
    q_pos = q_offset + jnp.arange(Sq)

    bf16_scores = LOWP >= 2 and q.dtype == jnp.bfloat16

    def step(carry, inp):
        acc, m, l = carry
        ci, kb, vb = inp
        k_pos = k_offset + ci * C + jnp.arange(C)
        if bf16_scores:
            # score/probability space stays bf16 (as the fused TPU kernel
            # keeps it in VMEM); only m/l/acc accumulate in f32
            s = jnp.einsum("bqkgh,bckh->bkgqc", qf, kb)          # bf16
            msk = _mask(q_pos, k_pos, causal, window)
            s = jnp.where(msk[None, None, None], s,
                          jnp.bfloat16(NEG_INF))
            m_new = jnp.maximum(m, s.max(axis=-1).astype(jnp.float32))
            p = jnp.exp(s - m_new[..., None].astype(jnp.bfloat16))
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1, dtype=jnp.float32)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqc,bckh->bkgqh", p, vb,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None
        s = jnp.einsum("bqkgh,bckh->bkgqc", qf, kb,
                       preferred_element_type=jnp.float32)      # scores
        msk = _mask(q_pos, k_pos, causal, window)               # (Sq, C)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))                  # (B,KV,G,Sq)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqc,bckh->bkgqh", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (acc_new, m_new, l_new), None

    acc0 = pvary_like(jnp.zeros((B, KV, G, Sq, hd), jnp.float32), qf, kc, vc)
    m0 = pvary_like(jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32),
                    qf, kc, vc)
    l0 = pvary_like(jnp.zeros((B, KV, G, Sq), jnp.float32), qf, kc, vc)
    idx = jnp.arange(nc)
    kb = jnp.moveaxis(kc, 1, 0)
    vb = jnp.moveaxis(vc, 1, 0)
    (acc, m, l), _ = pscan(step, (acc0, m0, l0), (idx, kb, vb))
    if partial:
        return acc, m, l
    l = jnp.maximum(l, 1e-30)
    out = acc / l[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, hd)         # (B,Sq,KV,G,hd)
    if with_lse:
        return out, m + jnp.log(l)
    return out.astype(q.dtype)


# ------------------------------------------------ attention's gradient
BWD_BLOCK = 512           # query rows and keys per block of the backward
FWD_SCORE_BYTES = 2 ** 27     # the banded training forward's scores at once


class _AttnStatic(NamedTuple):
    """What the attention's forward and backward fix at trace time; the
    window and the offsets are None where they are traced."""
    causal: bool
    chunk: int
    banded: bool
    window: int | None
    q_offset: int | None
    k_offset: int | None


@contextlib.contextmanager
def _attn_scope(scope: str):
    """Name the device ops made inside by ``scope`` (``attn.fwd`` or
    ``attn.bwd``): in their ``op_name`` and in an ``attn_scope`` frontend
    attribute, which the op's HLO text in a device trace carries."""
    with jax.named_scope(scope), set_xla_metadata(attn_scope=scope):
        yield


def _positions(st, dyn):
    """(window, q_offset, k_offset): the static value where there is one,
    else the traced one."""
    return tuple(d if s is None else s
                 for s, d in zip((st.window, st.q_offset, st.k_offset), dyn))


def _forward(q, k, v, dyn, st, with_lse=False):
    if st.banded:
        return banded_attention(q, k, v, window=st.window, chunk=st.chunk,
                                with_lse=with_lse)
    window, q_off, k_off = _positions(st, dyn)
    return _chunked_attention(
        q, k, v, causal=st.causal, window=window, q_offset=q_off,
        k_offset=k_off, chunk=st.chunk, with_lse=with_lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _attention(q, k, v, dyn, st):
    return _forward(q, k, v, dyn, st)


def _attention_fwd(q, k, v, dyn, st):
    with _attn_scope("attn.fwd"):
        out, lse = _forward(q, k, v, dyn, st, with_lse=True)
        return out.astype(q.dtype), (q, k, v, out, lse, dyn)


def _block(n: int, target: int) -> int:
    """The largest block of at most ``target`` that divides ``n``."""
    return max(b for b in range(1, min(n, target) + 1) if n % b == 0)


def _block_pairs(st, Sq, Sk, Cq, Ck):
    """(query block, key block) pairs that hold a visible (query, key):
    all of them where an offset or the window is traced."""
    w, qo, ko = st.window, st.q_offset, st.k_offset
    pairs = []
    for i in range(Sq // Cq):
        for j in range(Sk // Ck):
            if qo is not None and ko is not None:
                q_lo, k_lo = qo + i * Cq, ko + j * Ck
                q_hi, k_hi = q_lo + Cq - 1, k_lo + Ck - 1
                if k_hi < 0 or (st.causal and k_lo > q_hi):
                    continue
                if w is not None and w > 0 and q_lo - k_hi >= w:
                    continue
            pairs.append((i, j))
    return jnp.asarray(pairs, jnp.int32).reshape(-1, 2)


def _attention_bwd(st, res, g):
    """FlashAttention-2's backward: for each block pair inside the causal
    triangle and the band, the probabilities again from the saved
    log-sum-exp, then dv += p^T do, ds = p (do v^T - rowsum(do o)),
    dq += ds k, dk += ds^T q, accumulated in float32. Only one block's
    scores exist at a time."""
    q, k, v, out, lse, dyn = res
    window, q_off, k_off = _positions(st, dyn)
    with _attn_scope("attn.bwd"):
        B, Sq, H, hd = q.shape
        _, Sk, KV, _ = k.shape
        G = H // KV
        scale = hd ** -0.5
        dt = (jnp.bfloat16 if LOWP >= 1 and q.dtype == jnp.bfloat16
              else jnp.float32)
        cdt = q.dtype                        # operands of the grad matmuls
        Cq, Ck = _block(Sq, BWD_BLOCK), _block(Sk, BWD_BLOCK)
        qg = q.reshape(B, Sq, KV, G, hd)
        dog = g.reshape(B, Sq, KV, G, hd)
        # rowsum(do * o): the softmax's own term in ds
        dsum = jnp.einsum("bqkgh,bqkgh->bkgq", dog.astype(jnp.float32),
                          out.reshape(B, Sq, KV, G, hd))

        def step(carry, ij):
            dq, dk, dv = carry
            i0, j0 = ij[0] * Cq, ij[1] * Ck
            qi = lax.dynamic_slice_in_dim(qg, i0, Cq, axis=1)
            doi = lax.dynamic_slice_in_dim(dog, i0, Cq, axis=1)
            kj = lax.dynamic_slice_in_dim(k, j0, Ck, axis=1)
            vj = lax.dynamic_slice_in_dim(v, j0, Ck, axis=1)
            # the forward's scores, as the forward computed them
            s = jnp.einsum("bqkgh,bckh->bkgqc",
                           qi.astype(dt) * jnp.asarray(scale, dt),
                           kj.astype(dt),
                           preferred_element_type=jnp.float32)
            msk = _mask(q_off + i0 + jnp.arange(Cq),
                        k_off + j0 + jnp.arange(Ck), st.causal, window)
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            lse_i = lax.dynamic_slice_in_dim(lse, i0, Cq, axis=3)
            p = jnp.exp(s - lse_i[..., None])
            dv_j = jnp.einsum("bkgqc,bqkgh->bckh", p.astype(cdt), doi,
                              preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqkgh,bckh->bkgqc", doi, vj,
                            preferred_element_type=jnp.float32)
            d_i = lax.dynamic_slice_in_dim(dsum, i0, Cq, axis=3)
            ds = (p * (dp - d_i[..., None])).astype(cdt)
            dq_i = jnp.einsum("bkgqc,bckh->bqkgh", ds, kj,
                              preferred_element_type=jnp.float32) * scale
            dk_j = jnp.einsum("bkgqc,bqkgh->bckh", ds, qi,
                              preferred_element_type=jnp.float32) * scale
            add = lambda acc, x, at: lax.dynamic_update_slice_in_dim(
                acc, lax.dynamic_slice_in_dim(acc, at, x.shape[1], axis=1)
                + x, at, axis=1)
            return (add(dq, dq_i, i0), add(dk, dk_j, j0),
                    add(dv, dv_j, j0)), None

        zeros = lambda shape: pvary_like(jnp.zeros(shape, jnp.float32),
                                         q, k, v, g)
        init = (zeros((B, Sq, KV, G, hd)), zeros((B, Sk, KV, hd)),
                zeros((B, Sk, KV, hd)))
        (dq, dk, dv), _ = pscan(step, init,
                                _block_pairs(st, Sq, Sk, Cq, Ck))
        dq = dq.reshape(B, Sq, H, hd).astype(q.dtype)
        return dq, dk.astype(k.dtype), dv.astype(v.dtype), None


_attention.defvjp(_attention_fwd, _attention_bwd)


def reference_attention(q, k, v, *, causal=True, window=-1, q_offset=0,
                        k_offset=0):
    """Naive O(S^2)-memory oracle (tests only)."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, Sq, KV, G, hd) * hd ** -0.5
    s = jnp.einsum("bqkgh,bskh->bkgqs", qf, k.astype(jnp.float32))
    msk = _mask(q_offset + jnp.arange(Sq), k_offset + jnp.arange(Sk),
                causal, window)
    s = jnp.where(msk[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bkgqh", p, v.astype(jnp.float32))
    return jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, hd).astype(q.dtype)
