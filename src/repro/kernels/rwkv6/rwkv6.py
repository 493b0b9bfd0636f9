"""RWKV6 (Finch) chunked linear attention as a Pallas TPU kernel.

The data-dependent-decay recurrence S_t = diag(w_t) S_{t-1} + k_t v_t^T is
evaluated in chunks: the (K x V) per-head matrix state lives in VMEM scratch
across the chunk grid dimension, and all intra-chunk work is (C x K)-(K x C)
MXU matmuls -- the TPU-native re-blocking of an inherently sequential GPU
kernel (hardware adaptation per DESIGN.md).

Grid: (B*H, n_chunks), chunk dim sequential (state carried in scratch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref, *,
                 chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)          # (C, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)          # (C, V)
    lw = lw_ref[0].astype(jnp.float32)        # (C, K) log decays (<= 0)
    u = u_ref[0].astype(jnp.float32)          # (1, K) bonus

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum over the chunk as a lower-triangular matmul:
    # the TPU kernel compiler has no cumsum lowering
    tri = jnp.where(s_idx <= t_idx, 1.0, 0.0)
    cum = jax.lax.dot(tri, lw, precision=jax.lax.Precision.HIGHEST)
    # cross-chunk: o_cross[t] = (r_t * prod_{i<t} w) @ S0
    qd = r * jnp.exp(cum - lw)
    o_cross = qd @ s_ref[...]
    # intra-chunk: A[t,s] = <r_t e^{cum_t - l_t}, k_s e^{-cum_s}> for s < t
    kd = k * jnp.exp(-cum)
    A = qd @ kd.T                             # (C, C)
    A = jnp.where(s_idx < t_idx, A, 0.0)
    diag = jnp.sum(r * u * k, axis=1)         # bonus, s == t
    o = o_cross + A @ v + diag[:, None] * v
    o_ref[0] = o.astype(o_ref.dtype)

    # state update: S <- diag(e^{tot}) S + sum_s e^{tot - cum_s} k_s v_s^T
    # (the chunk's total decay as a row and as a column: reductions, since
    # the kernel compiler lowers neither cum[-1] nor a 1-D relayout)
    tot = jnp.sum(lw, axis=0, keepdims=True)               # (1, K)
    tot_col = jnp.sum(lw.T, axis=1, keepdims=True)         # (K, 1)
    kw = k * jnp.exp(tot - cum)
    s_ref[...] = jnp.exp(tot_col) * s_ref[...] + kw.T @ v


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_chunked(r, k, v, logw, u, *, chunk: int = 64,
                  interpret: bool = False):
    """r,k,v,logw: (B, S, H, K/V); u: (H, K). Returns (B, S, H, V)."""
    B, S, H, K = r.shape
    V = v.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    n = S // chunk

    def lay(x, d):
        return jnp.moveaxis(x, 2, 1).reshape(B * H, S, d)

    rr, kk, lww = lay(r, K), lay(k, K), lay(logw, K)
    vv = lay(v, V)
    uu = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, 1, K)

    kernel = functools.partial(_rwkv_kernel, chunk=chunk, n_chunks=n)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n),
        in_specs=[
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, V), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, K), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, V), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, V), r.dtype),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
    )(rr, kk, vv, lww, uu)
    return jnp.moveaxis(out.reshape(B, H, S, V), 1, 2)
