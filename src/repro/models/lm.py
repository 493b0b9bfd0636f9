"""The unified model: embedding -> scanned unit stack -> vocab-parallel loss,
plus prefill / flash-decode serving paths. All per-shard (manual SPMD) code;
callers wrap entry points in shard_map over ``topo.cube.mesh``.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import compat
from repro.models import blocks, layers
from repro.models.config import (
    ModelConfig, ATTN, MAMBA, RWKV, DENSE, MOE, RWKVCM, FULL_WINDOW)
from repro.models.layers import rms_norm
from repro.models.params import (
    param_defs, param_specs, vocab_padded, COMPUTE_DTYPE, ParamDef)
from repro.models.topology import Topology

Array = jax.Array
AUX_COEF = 0.01
CE_CHUNK = 512


class Model:
    def __init__(self, cfg: ModelConfig, topo: Topology,
                 resident: bool = False):
        """``resident``: serve-time weights replicated over the data axis
        (no per-step FSDP regather; see params.drop_axis)."""
        self.cfg = cfg
        self.topo = topo
        self.specs = param_specs(cfg, topo)
        if resident:
            from repro.models.params import drop_axis
            self.specs = drop_axis(self.specs)
        self.unit = cfg.unit()
        self.n_units = cfg.n_layers // self.unit
        self.mixers = cfg.mixers()[: self.unit]
        self.ffns = cfg.ffns()[: self.unit]
        # per-position window: static if identical across units, else traced
        wins = cfg.windows().reshape(self.n_units, self.unit)
        self.static_window = [
            int(wins[0, p]) if (wins[:, p] == wins[0, p]).all() else None
            for p in range(self.unit)]
        self.window_xs = {
            f"p{p}": jnp.asarray(wins[:, p])
            for p in range(self.unit) if self.static_window[p] is None}
        # per-position specs without the unit-stack dim (for FSDP gather)
        self.unit_specs = {
            pos: {k: jax.sharding.PartitionSpec(*tuple(s)[1:])
                  for k, s in self.specs["units"][pos].items()}
            for pos in self.specs["units"]}
        if cfg.is_encoder_decoder:
            self.enc_specs = {
                k: jax.sharding.PartitionSpec(*tuple(s)[1:])
                for k, s in self.specs["enc_units"]["p0"].items()}

    # ------------------------------------------------------------ embedding
    def _gather_embed(self, params):
        emb = params["embed"].astype(COMPUTE_DTYPE)
        spec = tuple(self.specs["embed"])
        if "data" in spec:
            emb = self.topo.comm(("data",)).all_gather(
                emb, axis=spec.index("data"))
        return emb

    def _embed_tokens(self, emb_l, tokens):
        """Vocab-parallel lookup -> partial (B, S, D) (needs psum over tp)."""
        Vl = emb_l.shape[0]
        me = lax.axis_index(self.topo.tp)
        ids = tokens - me * Vl
        valid = (ids >= 0) & (ids < Vl)
        x = jnp.take(emb_l, jnp.clip(ids, 0, Vl - 1), axis=0)
        return jnp.where(valid[..., None], x, 0)

    def _to_sp(self, x_partial):
        """Partial-over-tp full-seq (B,S,D) -> sequence-sharded (B,S_sp,D)."""
        topo = self.topo
        if topo.cp:
            S_cp = x_partial.shape[1] // topo.size(topo.cp)
            me = lax.axis_index(topo.cp)
            x_partial = lax.dynamic_slice_in_dim(x_partial, me * S_cp, S_cp, 1)
        return topo.comm(topo.tp).reduce_scatter(x_partial, axis=1)

    def _slice_sp(self, x_full):
        """Replicated full-seq -> my sp chunk (no reduction)."""
        topo = self.topo
        S_sp = x_full.shape[1] // topo.size(topo.sp)
        me = lax.axis_index(topo.sp)
        return lax.dynamic_slice_in_dim(x_full, me * S_sp, S_sp, axis=1)

    def embed_input(self, params, batch):
        """-> x_sp (B, S_sp, D) for the decoder/self stack."""
        cfg, topo = self.cfg, self.topo
        emb_l = self._gather_embed(params)
        x = self._embed_tokens(emb_l, batch["tokens"])
        if cfg.frontend == "patch":
            wf = blocks.gather_params(
                {"w": params["frontend_proj"]},
                {"w": self.specs["frontend_proj"]}, topo)["w"]
            patches = (batch["patches"].astype(COMPUTE_DTYPE) @ wf)
            F = patches.shape[1]
            me = lax.axis_index(topo.tp)
            patch_part = jnp.where(me == 0, patches, 0)
            x = x.at[:, :F].set(patch_part.astype(x.dtype))
        return self._to_sp(x)

    # ------------------------------------------------------------ the trunk
    def _position_fn(self, x_sp, w_shards, window, *, p, enc_out=None):
        """One layer (mixer + ffn) at unit position ``p``, from sharded
        params. Checkpointed individually so the backward working set is one
        layer's gathered weights + activations (not a whole unit's)."""
        cfg, topo = self.cfg, self.topo
        key = f"p{p}"
        w = blocks.gather_params(w_shards, self.unit_specs[key], topo)
        aux = jnp.zeros((), jnp.float32)
        mixer = self.mixers[p]
        if mixer == ATTN:
            x_sp = blocks.attn_block(cfg, topo, w, x_sp, window=window)
            if enc_out is not None:
                x_sp = blocks.attn_block(cfg, topo, w, x_sp,
                                         window=FULL_WINDOW,
                                         cross_src=enc_out, prefix="x")
        elif mixer == MAMBA:
            x_sp = blocks.mamba_mix(cfg, topo, w, x_sp)
        elif mixer == RWKV:
            x_sp = blocks.rwkv_mix(cfg, topo, w, x_sp)
        ffn = self.ffns[p]
        if ffn == DENSE:
            x_sp = blocks.dense_ffn(cfg, topo, w, x_sp)
        elif ffn == MOE:
            x_sp, a = blocks.moe_ffn(cfg, topo, w, x_sp)
            aux = aux + a
        elif ffn == RWKVCM:
            x_sp = blocks.rwkv_channel_mix(cfg, topo, w, x_sp)
        return x_sp, aux

    def _unit_fn(self, x_sp, xs, *, enc_out=None, remat=False):
        """Apply one unit (``self.unit`` layers). xs: per-position params
        (+ traced windows). Returns (x_sp, aux)."""
        aux = jnp.zeros((), jnp.float32)
        for p in range(self.unit):
            key = f"p{p}"
            window = self.static_window[p]
            if window is None:
                # traced per-layer window (gemma local:global pattern)
                def f(x, ws, win, _p=p):
                    return self._position_fn(x, ws, win, p=_p,
                                             enc_out=enc_out)
                args = (x_sp, xs[key], xs["windows"][key])
            else:
                # static window stays static through the checkpoint wrapper
                def f(x, ws, _p=p, _w=window):
                    return self._position_fn(x, ws, _w, p=_p,
                                             enc_out=enc_out)
                args = (x_sp, xs[key])
            if remat:
                f = jax.checkpoint(f)
            x_sp, a = f(*args)
            aux = aux + a
        return x_sp, aux

    def trunk(self, params, x_sp, *, enc_out=None, remat=True):
        """Scan the unit stack. Returns (x_sp, total_aux)."""
        xs = dict(params["units"])
        if self.window_xs:
            xs["windows"] = self.window_xs

        def body(carry, xs_slice):
            return self._unit_fn(carry, xs_slice, enc_out=enc_out,
                                 remat=remat)

        x_sp, auxs = layers.pscan(body, x_sp, xs)
        return x_sp, auxs.sum()

    def encode(self, params, frames):
        """Whisper encoder. frames: (B, S_enc, fdim). Returns full (B,S,D)."""
        cfg, topo = self.cfg, self.topo
        wf = blocks.gather_params(
            {"w": params["frontend_proj"]},
            {"w": self.specs["frontend_proj"]}, topo)["w"]
        x = frames.astype(COMPUTE_DTYPE) @ wf                  # replicated
        x_sp = self._slice_sp(x)

        def body(carry, xs_slice):
            w = blocks.gather_params(xs_slice, self.enc_specs, topo)
            x = blocks.attn_block(cfg, topo, w, carry, window=FULL_WINDOW,
                                  causal=False)
            x = blocks.dense_ffn(cfg, topo, w, x)
            return x, None

        body = jax.checkpoint(body)
        x_sp, _ = layers.pscan(body, x_sp, params["enc_units"]["p0"])
        full = topo.comm(topo.sp).all_gather(x_sp, axis=1)
        fn = blocks.gather_params(
            {"n": params["enc_final_norm"]},
            {"n": self.specs["enc_final_norm"]}, topo)["n"]
        return rms_norm(full, fn, cfg.norm_eps)

    # ------------------------------------------------------------- the loss
    def _head(self, params):
        topo = self.topo
        if self.cfg.tie_embeddings:
            return self._gather_embed(params).T                # (D, Vl)
        return blocks.gather_params(
            {"h": params["lm_head"]}, {"h": self.specs["lm_head"]}, topo)["h"]

    def loss_shard(self, params, batch):
        """Per-shard training loss (scalar, replicated). batch["tokens"],
        batch["labels"]: (B_l, S); labels < 0 are masked out."""
        cfg, topo = self.cfg, self.topo
        assert not topo.cp, "context parallelism is an inference-only path"
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = self.encode(params, batch["frames"])
        x_sp = self.embed_input(params, batch)
        x_sp, aux = self.trunk(params, x_sp, enc_out=enc_out)
        full = topo.comm(topo.sp).all_gather(x_sp, axis=1)
        fn = blocks.gather_params(
            {"n": params["final_norm"]}, {"n": self.specs["final_norm"]},
            topo)["n"]
        hn = rms_norm(full, fn, cfg.norm_eps)
        head = self._head(params)
        labels = batch["labels"]
        if cfg.frontend == "patch":
            F = cfg.frontend_tokens
            pos_ids = jnp.arange(labels.shape[1])[None]
            labels = jnp.where(pos_ids < F, -1, labels)

        Vl = head.shape[1]
        lo = lax.axis_index(topo.tp) * Vl
        B, S, D = hn.shape
        nck = layers.probe_trips(max(S // min(CE_CHUNK, S), 1))
        Ck = S // nck

        @jax.checkpoint  # recompute the (B,Ck,Vl) logits chunk in bwd
        def ce(carry, i):
            tot, cnt = carry
            hc = lax.dynamic_slice_in_dim(hn, i * Ck, Ck, axis=1)
            lc = lax.dynamic_slice_in_dim(labels, i * Ck, Ck, axis=1)
            logits = (hc @ head).astype(jnp.float32)           # (B,Ck,Vl)
            m = topo.comm(topo.tp).all_reduce(
                lax.stop_gradient(logits.max(-1)), op="max")
            se = compat.replicated_psum(
                jnp.exp(logits - m[..., None]).sum(-1), topo.tp)
            lse = jnp.log(se) + m
            ids = lc - lo
            ok = (ids >= 0) & (ids < Vl)
            tl = jnp.take_along_axis(
                logits, jnp.clip(ids, 0, Vl - 1)[..., None], axis=-1)[..., 0]
            tl = compat.replicated_psum(jnp.where(ok, tl, 0.0), topo.tp)
            msk = (lc >= 0).astype(jnp.float32)
            tot = tot + ((lse - tl) * msk).sum()
            cnt = cnt + msk.sum()
            return (tot, cnt), None

        # the carry varies over the batch axes only: every term the body
        # adds is reduced over tp (the max through a tp all_reduce, which is
        # typed invariant over tp at any group size, the sums by psum)
        zero = layers.pvary_axes(jnp.zeros(()), topo.dp)
        (tot, cnt), _ = layers.pscan(ce, (zero, zero), jnp.arange(nck))
        tot = compat.replicated_psum(tot, topo.dp)
        cnt = compat.replicated_psum(cnt, topo.dp)
        loss = tot / jnp.maximum(cnt, 1.0)
        aux = layers.pvary_axes(aux, topo.dp + topo.tp)
        aux_all = compat.replicated_psum(aux, topo.dp + topo.tp) / (
            topo.dp_size * topo.tp_size)
        metrics = {"ce_loss": loss, "aux_loss": aux_all, "tokens": cnt}
        return loss + AUX_COEF * aux_all, metrics

    def forward_logits(self, params, batch):
        """Full-sequence logits (tests / tiny eval). Returns (B, S, Vl)."""
        cfg, topo = self.cfg, self.topo
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = self.encode(params, batch["frames"])
        x_sp = self.embed_input(params, batch)
        x_sp, _ = self.trunk(params, x_sp, enc_out=enc_out, remat=False)
        full = topo.comm(topo.sp).all_gather(x_sp, axis=1)
        fn = blocks.gather_params(
            {"n": params["final_norm"]}, {"n": self.specs["final_norm"]},
            topo)["n"]
        hn = rms_norm(full, fn, cfg.norm_eps)
        return (hn @ self._head(params)).astype(jnp.float32)
