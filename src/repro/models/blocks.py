"""Sharded transformer / SSM / MoE blocks (manual SPMD, per-shard code).

Every cross-device transfer in these blocks goes through a topology-bound
:class:`repro.core.comm.Communicator` (``topo.comm(axes)``) --
AllGather/ReduceScatter implement Megatron-style sequence-parallel tensor
parallelism, AlltoAll implements expert-parallel MoE dispatch, and additive/
max all-reduces implement flash-decode LSE combines. Dispatch defaults to
``algorithm="auto"`` (the planner's pick at trace time); the
``topo.comm_algorithm`` knob swaps every collective onto the paper's
``naive`` (host-mediated analogue) flows for end-to-end application
ablations (paper Fig. 15/16), and a :class:`repro.core.comm.CommTrace`
observes every dispatched transfer.

Training-path activations are sequence-sharded over ``topo.sp`` between
blocks; decode-path activations are replicated over the model axes with the
KV cache sequence-sharded (flash-decode).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.hypercube import Hypercube
from repro.models import ssm
from repro.models.config import ModelConfig, FULL_WINDOW
from repro.models.layers import (
    rms_norm, rope, chunked_attention, decode_mask, NEG_INF)
from repro.models.params import kv_is_sharded, dt_rank, COMPUTE_DTYPE
from repro.models.topology import Topology

Array = jax.Array


# ------------------------------------------------------------- param gather
def gather_params(w: dict, specs: dict, topo: Topology) -> dict:
    """FSDP: bf16-cast then AllGather each leaf over the ``data`` axis.

    Casting *before* the gather halves FSDP traffic (fp32 master, bf16 wire).
    The AllGather's autodiff transpose reduce-scatters gradients back to the
    ZeRO shards.
    """
    out = {}
    for k, v in w.items():
        spec = tuple(specs[k])
        v = v.astype(COMPUTE_DTYPE)
        if "data" in spec:
            axis = spec.index("data")
            v = topo.comm(("data",)).all_gather(v, axis=axis)
        out[k] = v
    return out


def _tp_rank(topo: Topology) -> Array:
    return lax.axis_index(topo.tp)


# ---------------------------------------------------------------- attention
def _split_qkv(cfg: ModelConfig, topo: Topology, hn_q, hn_kv, w, prefix=""):
    """Project and reshape q/k/v with GQA head bookkeeping.

    Returns q: (B,Sq,Hl,hd), k,v: (B,Sk,KVl,hd), group count handled inside
    chunked_attention via shapes.
    """
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    t = topo.tp_size
    Hl = H // t
    q = (hn_q @ w[prefix + "wq"])
    B, Sq, _ = q.shape
    q = q.reshape(B, Sq, Hl, hd)
    # wkv columns are laid out (KV, 2, hd) -- whole kv heads stay contiguous
    # so column-sharding over tp slices whole (k,v) head pairs.
    kvp = hn_kv @ w[prefix + "wkv"]
    Sk = kvp.shape[1]
    if kv_is_sharded(cfg, topo):
        KVl = KV // t
        kv = kvp.reshape(B, Sk, KVl, 2, hd)
        k, v = kv[:, :, :, 0], kv[:, :, :, 1]
    else:
        kv = kvp.reshape(B, Sk, KV, 2, hd)
        kf, vf = kv[:, :, :, 0], kv[:, :, :, 1]
        G = H // KV
        me = _tp_rank(topo)
        if Hl >= G:
            cnt = Hl // G
            lo = me * cnt
        else:
            cnt = 1
            lo = (me * Hl) // G
        k = lax.dynamic_slice_in_dim(kf, lo, cnt, axis=2)
        v = lax.dynamic_slice_in_dim(vf, lo, cnt, axis=2)
    return q, k, v


def attn_block(cfg: ModelConfig, topo: Topology, w: dict, x_sp: Array, *,
               window, causal=True, cross_src: Array | None = None,
               prefix: str = "", out_cache: bool = False):
    """Sequence-parallel attention block. x_sp: (B, S_sp, D).

    cross_src: encoder output (B, S_enc, D) full -- used as KV source for
    cross-attention (whisper decoder). Returns new x_sp (and optionally the
    full-seq K/V for prefill caching).

    ``cfg.fused_comm`` reroutes the collectives through
    ``repro.kernels.collective``: the tp gather fuses the pre-attention
    norm into its ring (bit-identical), the context-parallel full-sequence
    gather is replaced by ring attention (kv blocks rotate over the cp
    ring, within the documented tolerance), and the out-projection's
    reduce_scatter becomes a lazy-tile matmul epilogue.
    """
    tpc = topo.comm(topo.tp)
    fused = getattr(cfg, "fused_comm", False) and cross_src is None \
        and not out_cache
    if fused:
        from repro.kernels.collective import (
            all_gather_matmul, matmul_reduce_scatter, ring_attention)
        # gather seq over tp with the norm fused into the ring; the cp
        # gather disappears entirely -- k/v stay chunk-local and rotate
        hn = all_gather_matmul(
            tpc, x_sp, axis=1,
            block_fn=lambda b: rms_norm(b, w[prefix + "ln"], cfg.norm_eps))
        kv_src = hn                                           # (B, S_cp, D)
    else:
        # gather seq over tp (within the cp chunk)
        h = tpc.all_gather(x_sp, axis=1)                      # (B, S_cp, D)
        hn = rms_norm(h, w[prefix + "ln"], cfg.norm_eps)
        if cross_src is not None:
            kv_src = cross_src
            causal = False
            window = FULL_WINDOW
        elif topo.cp:
            full = topo.comm(topo.cp).all_gather(h, axis=1)   # (B, S, D)
            kv_src = rms_norm(full, w[prefix + "ln"], cfg.norm_eps)
        else:
            kv_src = hn
    q, k, v = _split_qkv(cfg, topo, hn, kv_src, w, prefix)
    B, Sq = q.shape[:2]
    if cfg.qk_norm and not prefix:
        q = rms_norm(q, w["q_norm"], cfg.norm_eps)
        k = rms_norm(k, w["k_norm"], cfg.norm_eps)
    q_off = 0
    if topo.cp:
        q_off = lax.axis_index(topo.cp) * Sq
    if cross_src is None:
        q = rope(q, q_off + jnp.arange(Sq), cfg.rope_theta)
        # fused: k is this shard's chunk, so its positions carry the same
        # global offset as q; unfused: k is the assembled sequence from 0
        k_off = q_off if fused else 0
        k = rope(k, k_off + jnp.arange(k.shape[1]), cfg.rope_theta)
    if fused and topo.cp:
        o = ring_attention(topo.comm(topo.cp), q, k, v,
                           causal=causal, window=window)
    else:
        o = chunked_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_off)
    o = o.reshape(B, Sq, -1)
    if fused:
        out = matmul_reduce_scatter(tpc, o, w[prefix + "wo"], axis=1)
    else:
        out = o @ w[prefix + "wo"]                 # partial over tp
        out = tpc.reduce_scatter(out, axis=1)
    y = x_sp + out
    if out_cache:
        # cache layout: sequence-sharded over sp, local kv heads
        sp_n = topo.size(topo.sp)
        S_loc = k.shape[1] // sp_n
        me = lax.axis_index(topo.sp)
        k_c = lax.dynamic_slice_in_dim(k, me * S_loc, S_loc, axis=1)
        v_c = lax.dynamic_slice_in_dim(v, me * S_loc, S_loc, axis=1)
        return y, (k_c, v_c)
    return y


def attn_decode(cfg: ModelConfig, topo: Topology, w: dict, x: Array,
                c: dict, pos: Array, *,
                window, kv_axes, rolling: bool, prefix: str = "",
                cross: bool = False, keys=("k", "v")):
    """Flash-decode one token. x: (B, D) replicated over model axes.

    c[keys[0]]/c[keys[1]]: (B, S_loc, KVc, hd) cache, sequence-sharded over
    ``kv_axes``; optional c[key+"_s"] per-(slot, head) scales mark an int8
    cache (8-bit cross-domain modulation, paper §V-C, applied to KV).
    pos: (B,) int32 per-request positions. ``rolling``: cache length <
    context (sliding window), slot = pos % S_cache.
    Returns (out (B, D), updated cache dict).

    Self-attention may instead find the layer's K/V left in the page pools
    under ``c["pages"]`` (:class:`repro.serving.pages.PagedLayer`): its
    ``attend`` gives this shard's partial over each lane's keys below its
    position, the current key is merged into it here, and ``c[keys]`` come
    back as the lanes' new rows for the caller to write.
    """
    kk, vk = keys
    pages = None if cross else c.get("pages")
    cache_k, cache_v = c.get(kk), c.get(vk)
    int8_cache = (kk + "_s") in c
    tpc = topo.comm(topo.tp)
    kvc = topo.comm(kv_axes)
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B = x.shape[0]
    hn = rms_norm(x[:, None], w[prefix + "ln"], cfg.norm_eps)  # (B,1,D)

    # q: local columns -> gather flat then reshape (supports tp > heads)
    q = hn @ w[prefix + "wq"]                                  # (B,1,cols)
    q = tpc.all_gather(q, axis=2).reshape(B, 1, H, hd)
    if not cross:
        kvp = hn @ w[prefix + "wkv"]
        if kv_is_sharded(cfg, topo):
            kvp = tpc.all_gather(kvp, axis=2)
        kvp = kvp.reshape(B, 1, KV, 2, hd)
        k_new, v_new = kvp[:, 0, :, 0], kvp[:, 0, :, 1]        # (B,KV,hd)
        if cfg.qk_norm and not prefix:
            q = rms_norm(q, w["q_norm"], cfg.norm_eps)
            k_new = rms_norm(k_new, w["k_norm"], cfg.norm_eps)
        q = _rope_decode(q, pos, cfg.rope_theta)
        k_new = _rope_decode(k_new[:, None], pos, cfg.rope_theta)[:, 0]

        # write into my cache chunk
        n_shards = topo.size(kv_axes)
        S_loc = cache_k.shape[1] if pages is None else pages.S_loc
        S_cache = S_loc * n_shards
        my_lo, idx, in_rng = decode_slot(pos, S_loc, n_shards, kv_axes,
                                         rolling)
        if pages is not None:          # the pools are the caller's to write
            o, k_row, v_row = _attend_pages(pages, q, k_new, v_new, pos,
                                            in_rng, window, kvc)
            return (_decode_out_proj(cfg, topo, w, x, o, prefix),
                    {**c, kk: k_row, vk: v_row})
        bidx = jnp.arange(B)
        if int8_cache:
            ks = jnp.maximum(jnp.abs(k_new).max(-1), 1e-6) / 127.0
            vs = jnp.maximum(jnp.abs(v_new).max(-1), 1e-6) / 127.0
            k_q = jnp.round(k_new / ks[..., None]).astype(jnp.int8)
            v_q = jnp.round(v_new / vs[..., None]).astype(jnp.int8)
            c[kk + "_s"] = c[kk + "_s"].at[bidx, idx].set(
                jnp.where(in_rng[:, None], ks.astype(jnp.float32),
                          c[kk + "_s"][bidx, idx]))
            c[vk + "_s"] = c[vk + "_s"].at[bidx, idx].set(
                jnp.where(in_rng[:, None], vs.astype(jnp.float32),
                          c[vk + "_s"][bidx, idx]))
            k_new, v_new = k_q, v_q
        upd_k = jnp.where(in_rng[:, None, None],
                          k_new.astype(cache_k.dtype), cache_k[bidx, idx])
        upd_v = jnp.where(in_rng[:, None, None],
                          v_new.astype(cache_v.dtype), cache_v[bidx, idx])
        cache_k = cache_k.at[bidx, idx].set(upd_k)
        cache_v = cache_v.at[bidx, idx].set(upd_v)
        # key positions of my slots
        slots = my_lo + jnp.arange(S_loc)                      # (S_loc,)
        if rolling:
            k_pos = pos[:, None] - (pos[:, None] - slots[None]) % S_cache
        else:
            k_pos = jnp.broadcast_to(slots[None], (B, S_loc))
    else:
        # cross-attention: cache holds precomputed encoder K/V, all valid
        k_pos = jnp.broadcast_to(
            jnp.arange(cache_k.shape[1])[None], (B, cache_k.shape[1]))
        my_lo = 0

    # partial attention over my chunk (all heads), LSE-combined over shards
    G = H // cache_k.shape[2]
    qf = q.reshape(B, H, hd).astype(jnp.float32) * hd ** -0.5
    kf = cache_k.astype(jnp.float32)
    if int8_cache:
        kf = kf * c[kk + "_s"][..., None]
    s = _decode_scores(qf, kf, G)
    if cross:
        ok = jnp.ones_like(s, bool)
    else:
        ok = decode_mask(pos[:, None, None], k_pos[:, None, :], window)
    s = jnp.where(ok, s, NEG_INF)

    def weights(m_all):
        p = jnp.exp(s - m_all[..., None])
        return p, p.sum(-1)

    def values(p):
        vf = cache_v.astype(jnp.float32)
        if int8_cache:
            vf = vf * c[vk + "_s"][..., None]
        return _decode_out(p, vf, G)                           # (B,H,hd)

    o = _lse_combine(kvc, s.max(axis=-1), weights, values)
    c = dict(c)
    c[kk], c[vk] = cache_k, cache_v
    return _decode_out_proj(cfg, topo, w, x, o, prefix), c


def _decode_out_proj(cfg, topo, w, x, o, prefix):
    """x plus the attention output ``o`` (B, H, hd) projected out."""
    B, H, hd = o.shape
    # out projection: my slice of the flattened head dim (wo row shard)
    me = _tp_rank(topo)
    rows = (H * hd) // topo.tp_size
    o_flat = o.reshape(B, H * hd).astype(COMPUTE_DTYPE)
    o_loc = lax.dynamic_slice_in_dim(o_flat, me * rows, rows, axis=1)
    out = o_loc @ w[prefix + "wo"]
    out = topo.comm(topo.tp).all_reduce(out)
    return x + out.astype(x.dtype)


def _attend_pages(pages, q, k_new, v_new, pos, in_rng, window, kvc):
    """Attention of each lane's query over the keys its pages hold on this
    shard (the kernel's partial, positions below the lane's) and its own
    new key (on the shard whose slots hold it), LSE-combined over the kv
    shards. Returns (output, the new K and V rows in the pools' dtype)."""
    B, _, H, hd = q.shape
    G = H // k_new.shape[1]
    qf = q.reshape(B, H, hd).astype(jnp.float32) * hd ** -0.5
    k_row = k_new.astype(pages.k.dtype)
    v_row = v_new.astype(pages.v.dtype)
    o_p, m_p, l_p = pages.attend(qf, window)
    s = _decode_scores(qf, k_row[:, None].astype(jnp.float32), G)[..., 0]
    ok = in_rng[:, None] & decode_mask(pos[:, None], pos[:, None], window)
    s = jnp.where(ok, s, NEG_INF)                              # (B,H)

    def weights(m_all):
        a, e = jnp.exp(m_p - m_all), jnp.exp(s - m_all)
        return (a, e), l_p * a + e

    def values(ae):
        a, e = ae
        own = _decode_out(e[..., None], v_row[:, None].astype(jnp.float32),
                          G)
        return o_p * a[..., None] + own

    o = _lse_combine(kvc, jnp.maximum(m_p, s), weights, values)
    return o, k_row, v_row


def _lse_combine(kvc, m, weights, values):
    """Flash-decode combine over the kv shards of ``kvc``: ``m`` (B, H) is
    this shard's max score; ``weights(m_all)`` gives its softmax numerators
    against the max over every shard and their sum (B, H), and
    ``values(weights)`` their weighted values (B, H, hd). Returns the
    normalised output (B, H, hd)."""
    m_all = kvc.all_reduce(m, op="max")
    p, l = weights(m_all)
    l = kvc.all_reduce(l)
    return kvc.all_reduce(values(p)) / jnp.maximum(l, 1e-30)[..., None]


def decode_slot(pos: Array, S_loc: int, n_shards: int, kv_axes,
                rolling: bool):
    """Where decode writes each lane's new K/V row in this shard's chunk of
    ``S_loc`` slots (of ``S_loc * n_shards``, sequence-sharded over
    ``kv_axes``). Returns (the chunk's first slot, the lane's slot within
    the chunk clipped into it (B,), whether the slot is in the chunk (B,))."""
    S_cache = S_loc * n_shards
    my_lo = lax.axis_index(kv_axes) * S_loc
    slot = (pos % S_cache) if rolling else pos             # (B,)
    loc = slot - my_lo
    in_rng = (loc >= 0) & (loc < S_loc)
    idx = jnp.clip(loc, 0, S_loc - 1)
    return my_lo, idx, in_rng


def _rope_decode(q, pos, theta):
    """q: (B, 1, H, hd), per-row positions (B,)."""
    B = q.shape[0]
    return rope(q.reshape(B, 1, -1, q.shape[-1]), pos[:, None], theta)


def _decode_scores(qf, kf, G):
    """qf: (B,H,hd); kf: (B,S,KVc,hd) -> scores (B,H,S) with GQA groups."""
    B, H, hd = qf.shape
    KVc = kf.shape[2]
    q_g = qf.reshape(B, KVc, G, hd)
    return jnp.einsum("bkgd,bskd->bkgs", q_g, kf).reshape(B, H, -1)


def _decode_out(p, vf, G):
    B, H, S = p.shape
    KVc = vf.shape[2]
    p_g = p.reshape(B, KVc, G, S)
    o = jnp.einsum("bkgs,bskd->bkgd", p_g, vf)
    return o.reshape(B, H, -1)


# --------------------------------------------------------------------- FFNs
def dense_ffn(cfg, topo, w, x_sp, keys=("fln", "wg", "wu", "wd")):
    tpc = topo.comm(topo.tp)
    ln, wg, wu, wd = (w[k] for k in keys)
    if getattr(cfg, "fused_comm", False):
        from repro.kernels.collective import (
            all_gather_matmul, matmul_reduce_scatter)

        def up(b):
            bn = rms_norm(b, ln, cfg.norm_eps)
            return jax.nn.silu(bn @ wg) * (bn @ wu)

        # norm + up-projection fused into the gather ring (row-wise, so
        # bit-identical); the down-projection's partial sum is scattered
        # tile-by-tile without ever materializing (B, S_cp, D) in full
        h_act = all_gather_matmul(tpc, x_sp, axis=1, block_fn=up)
        out = matmul_reduce_scatter(tpc, h_act, wd, axis=1)
        return x_sp + out
    h = tpc.all_gather(x_sp, axis=1)
    hn = rms_norm(h, ln, cfg.norm_eps)
    out = (jax.nn.silu(hn @ wg) * (hn @ wu)) @ wd
    out = tpc.reduce_scatter(out, axis=1)
    return x_sp + out


def dense_ffn_decode(cfg, topo, w, x, keys=("fln", "wg", "wu", "wd")):
    ln, wg, wu, wd = (w[k] for k in keys)
    hn = rms_norm(x, ln, cfg.norm_eps)
    out = (jax.nn.silu(hn @ wg) * (hn @ wu)) @ wd
    return x + topo.comm(topo.tp).all_reduce(out).astype(x.dtype)


def _route(cfg, hn2d, router):
    """Top-k routing. hn2d: (T, D). Returns (topi, topv) (T, k)."""
    logits = hn2d @ router
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = lax.top_k(probs, cfg.top_k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return topi, topv.astype(hn2d.dtype), probs


def moe_ffn(cfg, topo, w, x_sp):
    """Expert-parallel MoE with PID-Comm AlltoAll dispatch (paper's flagship
    primitive, used exactly like DLRM embedding exchange, Fig. 11).

    Returns (new_x_sp, aux_loss)."""
    ep_size = topo.size(topo.ep)
    etp_size = topo.size(topo.etp)
    Ep = cfg.n_experts_padded
    E_loc = Ep // ep_size

    x_e = x_sp
    if etp_size > 1:
        x_e = topo.comm(topo.etp).all_gather(x_sp, axis=1)
    B, S_e, D = x_e.shape
    hn = rms_norm(x_e, w["fln"], cfg.norm_eps)
    T = B * S_e
    h2 = hn.reshape(T, D)
    topi, topv, probs = _route(cfg, h2, w["router"])

    # aux load-balance loss (switch-style), over the real experts only
    pe = probs[:, :cfg.n_experts].mean(0)
    fe = jnp.zeros(cfg.n_experts, jnp.float32).at[
        jnp.clip(topi.reshape(-1), 0, cfg.n_experts - 1)].add(
        1.0 / (T * cfg.top_k))
    aux = cfg.n_experts * jnp.sum(pe * fe)

    C = int(math.ceil(T * cfg.top_k / Ep * cfg.capacity_factor))
    flat_e = topi.reshape(-1)                                  # (T*k,)
    tok = jnp.repeat(jnp.arange(T), cfg.top_k)
    if cfg.moe_dispatch == "sort":
        # PE-assisted reordering (paper §V-A1) applied to dispatch: sort the
        # (token, expert) pairs so the buffer build is one contiguous gather
        # instead of a scatter-add into a zero-initialized buffer -- the
        # AlltoAll then moves pre-ordered tiles (cf. kernels/reorder).
        order = jnp.argsort(flat_e)                            # stable
        sorted_e = flat_e[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(Ep))    # (Ep,)
        slot_idx = starts[:, None] + jnp.arange(C)[None]       # (Ep, C)
        in_seg = slot_idx < jnp.append(starts[1:], T * cfg.top_k)[:, None]
        src = jnp.where(in_seg, order[jnp.clip(slot_idx, 0, T * cfg.top_k - 1)],
                        0)
        disp = jnp.where(in_seg[..., None], h2[tok[src]], 0)   # (Ep, C, D)
        # slot of each (token,choice) for the combine gather
        rank_in_seg = jnp.zeros((T * cfg.top_k,), jnp.int32).at[order].set(
            jnp.arange(T * cfg.top_k, dtype=jnp.int32) - starts[sorted_e])
        pos_in_e = rank_in_seg
        keep = pos_in_e < C
    else:
        # baseline: one-hot cumsum slots + scatter-add ("host modulation")
        oh = jax.nn.one_hot(flat_e, Ep, dtype=jnp.int32)
        pos_in_e = (jnp.cumsum(oh, axis=0) - oh)[
            jnp.arange(T * cfg.top_k), flat_e]
        keep = pos_in_e < C
        disp = jnp.zeros((Ep, C, D), h2.dtype)
        disp = disp.at[flat_e, jnp.clip(pos_in_e, 0, C - 1)].add(
            jnp.where(keep[:, None], h2[tok], 0))

    # AlltoAll over the expert dimension of the hypercube
    epc = topo.comm(topo.ep)
    recv = epc.all_to_all(disp, split_axis=0, concat_axis=1)   # (E_loc, ep*C, D)
    hh = jnp.einsum("ecd,edf->ecf", recv, w["we_g"])
    hh = jax.nn.silu(hh) * jnp.einsum("ecd,edf->ecf", recv, w["we_u"])
    oo = jnp.einsum("ecf,efd->ecd", hh, w["we_d"])
    if etp_size > 1:
        oo = topo.comm(topo.etp).all_reduce(oo)
    back = epc.all_to_all(oo, split_axis=1, concat_axis=0)     # (Ep, C, D)

    vals = back[flat_e, jnp.clip(pos_in_e, 0, C - 1)]          # (T*k, D)
    vals = jnp.where(keep[:, None], vals, 0) * topv.reshape(-1)[:, None]
    out = jnp.zeros((T, D), vals.dtype).at[tok].add(vals).reshape(B, S_e, D)

    if cfg.n_shared_experts:
        out = out + (jax.nn.silu(hn @ w["ws_g"]) * (hn @ w["ws_u"])) @ w["ws_d"]

    if etp_size > 1:
        me = lax.axis_index(topo.etp)
        S_sp = x_sp.shape[1]
        out = lax.dynamic_slice_in_dim(out, me * S_sp, S_sp, axis=1)
    return x_sp + out, aux


def moe_ffn_decode(cfg, topo, w, x):
    """Decode-path MoE: tokens replicated over model axes; dispatch over ep."""
    epc = topo.comm(topo.ep)
    ep_size = topo.size(topo.ep)
    etp_size = topo.size(topo.etp)
    Ep = cfg.n_experts_padded
    B, D = x.shape
    hn = rms_norm(x, w["fln"], cfg.norm_eps)
    topi, topv, _ = _route(cfg, hn, w["router"])
    C = max(int(math.ceil(B * cfg.top_k / Ep * cfg.capacity_factor)), 1)
    flat_e = topi.reshape(-1)
    oh = jax.nn.one_hot(flat_e, Ep, dtype=jnp.int32)
    pos_in_e = (jnp.cumsum(oh, axis=0) - oh)[jnp.arange(flat_e.size), flat_e]
    keep = pos_in_e < C
    tok = jnp.repeat(jnp.arange(B), cfg.top_k)
    disp = jnp.zeros((Ep, C, D), hn.dtype).at[
        flat_e, jnp.clip(pos_in_e, 0, C - 1)].add(
        jnp.where(keep[:, None], hn[tok], 0))
    recv = epc.all_to_all(disp, split_axis=0, concat_axis=1)
    hh = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, w["we_g"]))
    hh = hh * jnp.einsum("ecd,edf->ecf", recv, w["we_u"])
    oo = jnp.einsum("ecf,efd->ecd", hh, w["we_d"])
    if etp_size > 1:
        oo = topo.comm(topo.etp).all_reduce(oo)
    back = epc.all_to_all(oo, split_axis=1, concat_axis=0)
    vals = back[flat_e, jnp.clip(pos_in_e, 0, C - 1)]
    vals = jnp.where(keep[:, None], vals, 0) * topv.reshape(-1)[:, None]
    out = jnp.zeros((B, D), vals.dtype).at[tok].add(vals)
    if cfg.n_shared_experts:
        out = out + (jax.nn.silu(hn @ w["ws_g"]) * (hn @ w["ws_u"])) @ w["ws_d"]
    return x + out.astype(x.dtype), None


def rwkv_channel_mix(cfg, topo, w, x_sp, out_cache: bool = False):
    tpc = topo.comm(topo.tp)
    h = tpc.all_gather(x_sp, axis=1)                           # (B, S, D)
    hn = rms_norm(h, w["fln"], cfg.norm_eps)
    prev = jnp.pad(hn, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    xk = hn + w["cm_mu"][0] * (prev - hn)
    xr = hn + w["cm_mu"][1] * (prev - hn)
    kk = jnp.square(jax.nn.relu(xk @ w["cm_k"]))
    out = kk @ w["cm_v"]                                       # partial (tp)
    out = tpc.reduce_scatter(out, axis=1)
    gate = jax.nn.sigmoid(xr @ w["cm_r"])                      # (B,S,D) repl.
    me = _tp_rank(topo)
    S_sp = x_sp.shape[1]
    gate = lax.dynamic_slice_in_dim(gate, me * S_sp, S_sp, axis=1)
    y = x_sp + out * gate.astype(out.dtype)
    if out_cache:
        return y, hn[:, -1]
    return y


def rwkv_channel_mix_decode(cfg, topo, w, x, prev):
    hn = rms_norm(x, w["fln"], cfg.norm_eps)
    xk = hn + w["cm_mu"][0] * (prev - hn)
    xr = hn + w["cm_mu"][1] * (prev - hn)
    kk = jnp.square(jax.nn.relu(xk @ w["cm_k"]))
    out = topo.comm(topo.tp).all_reduce(kk @ w["cm_v"])
    gate = jax.nn.sigmoid(xr @ w["cm_r"])
    return x + (out * gate).astype(x.dtype), hn


# ------------------------------------------------------------------ mixers
def rwkv_mix(cfg, topo, w, x_sp, out_cache: bool = False):
    """RWKV6 time-mix. Training path: x_sp (B, S_sp, D)."""
    spc = topo.comm(topo.sp)
    h = spc.all_gather(x_sp, axis=1)                           # (B, S, D)
    hn = rms_norm(h, w["ln"], cfg.norm_eps)
    hprev = jnp.pad(hn, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    mu = w["mu"]
    xr, xk, xv, xg, xw = (hn + mu[i] * (hprev - hn) for i in range(5))
    hd = cfg.rwkv_head_dim
    Dl = w["wr"].shape[1]
    Hl = Dl // hd
    B, S = hn.shape[:2]
    r = (xr @ w["wr"]).reshape(B, S, Hl, hd)
    k = (xk @ w["wk"]).reshape(B, S, Hl, hd)
    v = (xv @ w["wv"]).reshape(B, S, Hl, hd)
    g = jax.nn.silu(xg @ w["wg"])
    wdd = w["decay_w0"] + jnp.tanh(xw @ w["w_lora_a"]) @ w["w_lora_b"]
    logw = -jnp.exp(wdd.astype(jnp.float32)).reshape(B, S, Hl, hd)
    u = w["bonus_u"].reshape(Hl, hd)
    o, state = ssm.rwkv6_chunked(r, k, v, logw, u)
    out = (o.reshape(B, S, Dl) * g) @ w["wo"]                  # partial (tp)
    out = spc.reduce_scatter(out, axis=1)
    y = x_sp + out
    if out_cache:
        return y, (state, hn[:, -1])
    return y


def rwkv_mix_decode(cfg, topo, w, x, state, prev):
    """x: (B, D); state: (B, Hl, hd, hd); prev: (B, D) previous hidden."""
    hn = rms_norm(x, w["ln"], cfg.norm_eps)
    mu = w["mu"]
    xr, xk, xv, xg, xw = (hn + mu[i] * (prev - hn) for i in range(5))
    hd = cfg.rwkv_head_dim
    Dl = w["wr"].shape[1]
    Hl = Dl // hd
    B = hn.shape[0]
    r = (xr @ w["wr"]).reshape(B, Hl, hd)
    k = (xk @ w["wk"]).reshape(B, Hl, hd)
    v = (xv @ w["wv"]).reshape(B, Hl, hd)
    g = jax.nn.silu(xg @ w["wg"])
    wdd = w["decay_w0"] + jnp.tanh(xw @ w["w_lora_a"]) @ w["w_lora_b"]
    logw = -jnp.exp(wdd.astype(jnp.float32)).reshape(B, Hl, hd)
    u = w["bonus_u"].reshape(Hl, hd)
    o, state = ssm.rwkv6_step(r, k, v, logw, u, state)
    out = (o.reshape(B, Dl) * g) @ w["wo"]
    out = topo.comm(topo.tp).all_reduce(out)
    return x + out.astype(x.dtype), state, hn


def mamba_mix(cfg, topo, w, x_sp, out_cache: bool = False):
    spc = topo.comm(topo.sp)
    h = spc.all_gather(x_sp, axis=1)                           # (B, S, D)
    hn = rms_norm(h, w["ln"], cfg.norm_eps)
    B, S = hn.shape[:2]
    # in_proj columns laid out (din, 2): (x, z) stay paired per channel so
    # column-sharding over tp slices whole channels.
    xz = hn @ w["in_proj"]                                     # (B,S,2*din_l)
    din_l = xz.shape[-1] // 2
    xz = xz.reshape(B, S, din_l, 2)
    xc_raw, z = xz[..., 0], xz[..., 1]
    xc, conv_tail = ssm.causal_conv1d(xc_raw, w["conv_w"], w["conv_b"])
    xc = jax.nn.silu(xc)
    R = dt_rank(cfg)
    n = cfg.d_state
    dbc = xc @ w["x_proj"]                                     # partial (tp)
    dbc = topo.comm(topo.tp).all_reduce(dbc)                   # (B,S,R+2n)
    dt = jax.nn.softplus(dbc[..., :R] @ w["dt_proj"] + w["dt_bias"])
    Bm, Cm = dbc[..., R:R + n], dbc[..., R + n:]
    A = -jnp.exp(w["a_log"])
    y, state = ssm.mamba_scan_chunked(xc, dt, A, Bm, Cm)
    out = (y * jax.nn.silu(z) + xc * w["d_skip"]) @ w["out_proj"]
    out = spc.reduce_scatter(out, axis=1)
    y_sp = x_sp + out
    if out_cache:
        return y_sp, (state, conv_tail)
    return y_sp


def mamba_mix_decode(cfg, topo, w, x, ssm_state, conv_tail):
    """x: (B, D); ssm_state: (B, din_l, N); conv_tail: (B, K-1, din_l)."""
    hn = rms_norm(x, w["ln"], cfg.norm_eps)
    xz = hn[:, None] @ w["in_proj"]
    din_l = xz.shape[-1] // 2
    xz = xz.reshape(xz.shape[0], 1, din_l, 2)
    xc, z = xz[..., 0], xz[..., 1]
    xc, conv_tail = ssm.causal_conv1d(xc, w["conv_w"], w["conv_b"], conv_tail)
    xc = jax.nn.silu(xc)[:, 0]
    z = z[:, 0]
    R = dt_rank(cfg)
    n = cfg.d_state
    tpc = topo.comm(topo.tp)
    dbc = tpc.all_reduce(xc @ w["x_proj"])
    dt = jax.nn.softplus(dbc[..., :R] @ w["dt_proj"] + w["dt_bias"])
    Bm, Cm = dbc[..., R:R + n], dbc[..., R + n:]
    A = -jnp.exp(w["a_log"])
    y, ssm_state = ssm.mamba_step(xc, dt, A, Bm, Cm, ssm_state)
    out = (y * jax.nn.silu(z) + xc * w["d_skip"]) @ w["out_proj"]
    out = tpc.all_reduce(out)
    return x + out.astype(x.dtype), ssm_state, conv_tail
