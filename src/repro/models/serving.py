"""Serving paths: prefill (cache-building forward) and flash-decode.

Decode runs on the serve topology (maximal model sharding, see
``build_serve_topology``): activations are replicated over the model axes,
the KV cache is sequence-sharded over them, and every layer's partial
attention is LSE-combined with a pidcomm psum -- the TPU translation of
PID-Comm's "entangled group works in unison" rule (all shards cooperate on
every token instead of idling).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import blocks
from repro.models.config import (
    ModelConfig, ATTN, MAMBA, RWKV, DENSE, MOE, RWKVCM, FULL_WINDOW)
from repro.models.layers import rms_norm, pscan
from repro.models.lm import Model
from repro.models.params import COMPUTE_DTYPE, dt_rank, vocab_padded
from repro.models.topology import Topology

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ServePlan:
    """Static decode-cell geometry."""
    S_ctx: int                  # context length (max position + 1)
    S_cache: int                # allocated cache length (< S_ctx if rolling)
    global_batch: int
    batch_axes: tuple[str, ...]  # axes sharding the batch ('' = replicated)
    kv_axes: tuple[str, ...]     # axes sharding the cache sequence
    cache_dtype: str = "bf16"    # "bf16" | "int8" (8-bit CM on the KV cache)


def make_serve_plan(cfg: ModelConfig, topo: Topology, *, S_ctx: int,
                    global_batch: int, cache_dtype: str = "bf16"
                    ) -> ServePlan:
    if cache_dtype not in ("bf16", "int8"):
        raise ValueError(
            f"cache_dtype must be 'bf16' or 'int8', got {cache_dtype!r} "
            "(the KV cache is either compute-dtype or the §V-C 8-bit "
            "cross-domain-modulated layout; nothing else has a decode path)")
    pods = topo.size(("pod",)) if "pod" in topo.cube.dim_names else 1
    batch_axes: tuple[str, ...] = ()
    b = global_batch
    if pods > 1 and b % pods == 0 and b >= pods:
        batch_axes += ("pod",)
        b //= pods
    dsz = topo.cube.size("data") if "data" in topo.cube.dim_names else 1
    if dsz > 1 and b % dsz == 0 and b >= dsz:
        batch_axes += ("data",)
        b //= dsz
    # uniform static sliding window => rolling cache bounded by the window
    wins = cfg.windows()
    S_cache = S_ctx
    if (wins >= 0).all() and len(set(wins.tolist())) == 1:
        S_cache = min(S_ctx, int(wins[0]))
    kv_axes = topo.tp
    # pad cache length to shard evenly
    n = topo.size(kv_axes)
    S_cache = int(np.ceil(S_cache / n) * n)
    return ServePlan(S_ctx=S_ctx, S_cache=S_cache, global_batch=global_batch,
                     batch_axes=batch_axes, kv_axes=kv_axes,
                     cache_dtype=cache_dtype)


# ------------------------------------------------------------- cache layout
def cache_defs(cfg: ModelConfig, topo: Topology, plan: ServePlan):
    """(global shape, spec, dtype) tree for the decode cache."""
    unit = cfg.unit()
    n_units = cfg.n_layers // unit
    B = plan.global_batch
    ba = plan.batch_axes or None
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    din = cfg.mamba_expand * cfg.d_model
    tree = {}
    for p, (mixer, ffn) in enumerate(zip(cfg.mixers()[:unit],
                                         cfg.ffns()[:unit])):
        d = {}
        if mixer == ATTN:
            cdt = jnp.int8 if plan.cache_dtype == "int8" else COMPUTE_DTYPE
            shp = (n_units, B, plan.S_cache, KV, hd)
            spec = P(None, ba, plan.kv_axes, None, None)
            d["k"] = (shp, spec, cdt)
            d["v"] = (shp, spec, cdt)
            if plan.cache_dtype == "int8":
                s_shp = (n_units, B, plan.S_cache, KV)
                s_spec = P(None, ba, plan.kv_axes, None)
                d["k_s"] = (s_shp, s_spec, jnp.float32)
                d["v_s"] = (s_shp, s_spec, jnp.float32)
            if cfg.is_encoder_decoder:
                xshp = (n_units, B, plan.S_ctx, KV, hd)
                d["xk"] = (xshp, P(None, ba, plan.kv_axes, None, None),
                           COMPUTE_DTYPE)
                d["xv"] = (xshp, P(None, ba, plan.kv_axes, None, None),
                           COMPUTE_DTYPE)
        elif mixer == MAMBA:
            d["ssm"] = ((n_units, B, din, cfg.d_state),
                        P(None, ba, topo.tp, None), jnp.float32)
            d["conv"] = ((n_units, B, cfg.conv_kernel - 1, din),
                         P(None, ba, None, topo.tp), COMPUTE_DTYPE)
        elif mixer == RWKV:
            H = cfg.d_model // cfg.rwkv_head_dim
            d["state"] = ((n_units, B, H, cfg.rwkv_head_dim,
                           cfg.rwkv_head_dim),
                          P(None, ba, topo.tp, None, None), jnp.float32)
            d["shift"] = ((n_units, B, cfg.d_model),
                          P(None, ba, None), COMPUTE_DTYPE)
        if ffn == RWKVCM:
            d["cm_shift"] = ((n_units, B, cfg.d_model),
                             P(None, ba, None), COMPUTE_DTYPE)
        tree[f"p{p}"] = d
    return tree


def cache_structs(cfg, topo, plan):
    defs = cache_defs(cfg, topo, plan)
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(
            d[0], d[2], sharding=topo.cube.sharding(d[1])),
        defs, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))


def cache_specs(cfg, topo, plan):
    defs = cache_defs(cfg, topo, plan)
    return jax.tree.map(
        lambda d: d[1], defs,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))


def init_cache(cfg, topo, plan):
    """Zero cache (smoke-scale only)."""
    defs = cache_defs(cfg, topo, plan)
    return jax.tree.map(
        lambda d: jnp.zeros(d[0], d[2]), defs,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))


# ------------------------------------------------------------------ decode
def _layer_in(key, cin):
    return dict(cin)


def _layer_out(key, c):
    return c


class Server:
    def __init__(self, cfg: ModelConfig, topo: Topology, plan: ServePlan,
                 resident: bool = False):
        self.cfg, self.topo, self.plan = cfg, topo, plan
        self.model = Model(cfg, topo, resident=resident)

    def decode_shard(self, params, cache, tokens: Array, pos: Array, *,
                     layer_cache=_layer_in, layer_out=_layer_out):
        """One decode step. tokens, pos: (B_l,) int32. Returns
        (logits (B_l, V_local), what the layer scan stacked).

        The scan slices ``cache`` per unit. ``layer_cache(key, cin)`` turns
        mixer ``key``'s slice into the cache dict the layer decodes on, and
        ``layer_out(key, c)`` picks what the scan stacks from the updated
        dict. Both default to the identity: the contiguous cache in, the
        new cache out. :class:`repro.serving.pages.PagedServer` passes
        hooks that read a layer's view from the page pools and emit only
        the row each lane wrote."""
        cfg, topo, plan = self.cfg, self.topo, self.plan
        m = self.model
        emb_l = m._gather_embed(params)
        x = topo.comm(topo.tp).all_reduce(
            m._embed_tokens(emb_l, tokens[:, None]))[:, 0]

        def unit_fn(x, slices):
            xs, cin = slices
            cout = {}
            for p in range(m.unit):
                key = f"p{p}"
                w = blocks.gather_params(xs[key], m.unit_specs[key], topo)
                window = m.static_window[p]
                if window is None:
                    window = xs["windows"][key]
                mixer = m.mixers[p]
                c = layer_cache(key, cin[key])
                if mixer == ATTN:
                    rolling = plan.S_cache < plan.S_ctx
                    x, c = blocks.attn_decode(
                        cfg, topo, w, x, c, pos,
                        window=window, kv_axes=plan.kv_axes, rolling=rolling)
                    if cfg.is_encoder_decoder:
                        x, c = blocks.attn_decode(
                            cfg, topo, w, x, c, pos,
                            window=FULL_WINDOW, kv_axes=plan.kv_axes,
                            rolling=False, prefix="x", cross=True,
                            keys=("xk", "xv"))
                elif mixer == MAMBA:
                    x, c["ssm"], c["conv"] = blocks.mamba_mix_decode(
                        cfg, topo, w, x, c["ssm"], c["conv"])
                elif mixer == RWKV:
                    x, c["state"], shift = blocks.rwkv_mix_decode(
                        cfg, topo, w, x, c["state"], c["shift"])
                    c["shift"] = shift.astype(c["shift"].dtype)
                ffn = m.ffns[p]
                if ffn == DENSE:
                    x = blocks.dense_ffn_decode(cfg, topo, w, x)
                elif ffn == MOE:
                    x, _ = blocks.moe_ffn_decode(cfg, topo, w, x)
                elif ffn == RWKVCM:
                    x, shift = blocks.rwkv_channel_mix_decode(
                        cfg, topo, w, x, c["cm_shift"])
                    c["cm_shift"] = shift.astype(c["cm_shift"].dtype)
                cout[key] = layer_out(key, c)
            return x, cout

        xs = dict(params["units"])
        if m.window_xs:
            xs["windows"] = m.window_xs
        x, new_cache = pscan(unit_fn, x, (xs, cache))
        fn = blocks.gather_params(
            {"n": params["final_norm"]}, {"n": m.specs["final_norm"]},
            topo)["n"]
        hn = rms_norm(x, fn, cfg.norm_eps)
        logits = (hn @ m._head(params)).astype(jnp.float32)
        return logits, new_cache

    # ------------------------------------------------------------- prefill
    def prefill_shard(self, params, batch):
        """Forward over the full prompt, emitting an sp-sharded cache and the
        last-position logits. Runs on a *training-style* topology."""
        cfg, topo = self.cfg, self.topo
        m = self.model
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = m.encode(params, batch["frames"])
        x_sp = m.embed_input(params, batch)

        def unit_fn(x_sp, xs):
            cout = {}
            for p in range(m.unit):
                key = f"p{p}"
                w = blocks.gather_params(xs[key], m.unit_specs[key], topo)
                window = m.static_window[p]
                if window is None:
                    window = xs["windows"][key]
                mixer = m.mixers[p]
                c = {}
                if mixer == ATTN:
                    x_sp, (c["k"], c["v"]) = blocks.attn_block(
                        cfg, topo, w, x_sp, window=window, out_cache=True)
                    if enc_out is not None:
                        x_sp, (c["xk"], c["xv"]) = blocks.attn_block(
                            cfg, topo, w, x_sp, window=FULL_WINDOW,
                            cross_src=enc_out, prefix="x", out_cache=True)
                elif mixer == MAMBA:
                    x_sp, (c["ssm"], c["conv"]) = blocks.mamba_mix(
                        cfg, topo, w, x_sp, out_cache=True)
                elif mixer == RWKV:
                    x_sp, (c["state"], c["shift"]) = blocks.rwkv_mix(
                        cfg, topo, w, x_sp, out_cache=True)
                ffn = m.ffns[p]
                if ffn == DENSE:
                    x_sp = blocks.dense_ffn(cfg, topo, w, x_sp)
                elif ffn == MOE:
                    x_sp, _ = blocks.moe_ffn(cfg, topo, w, x_sp)
                elif ffn == RWKVCM:
                    x_sp, c["cm_shift"] = blocks.rwkv_channel_mix(
                        cfg, topo, w, x_sp, out_cache=True)
                cout[f"p{p}"] = c
            return x_sp, cout

        xs = dict(params["units"])
        if m.window_xs:
            xs["windows"] = m.window_xs
        x_sp, cache = pscan(unit_fn, x_sp, xs)
        full = topo.comm(topo.sp).all_gather(x_sp, axis=1)
        fn = blocks.gather_params(
            {"n": params["final_norm"]}, {"n": m.specs["final_norm"]},
            topo)["n"]
        hn = rms_norm(full[:, -1:], fn, cfg.norm_eps)
        logits = (hn[:, 0] @ m._head(params)).astype(jnp.float32)
        return logits, cache
