"""The distributed train step and training loop driver.

The step is one shard_map over the architecture's hypercube:

  fwd/bwd (FSDP AllGather / ReduceScatter + TP AllGather/ReduceScatter +
  EP AlltoAll, all dispatched through topology-bound communicators with
  ``algorithm="auto"``; the gradient reductions are the ones
  ``shard_map(check_vma=True)`` autodiff inserts) -> global-norm clip ->
  AdamW(8-bit moments).

The loop driver adds microbatch accumulation, per-step deadlines (straggler
mitigation) and checkpoint/restart.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
from repro.compat import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import pvary_axes
from repro.models.lm import Model
from repro.models.params import param_defs, param_specs, ParamDef
from repro.models.topology import Topology
from repro.optim import adamw
from repro.telemetry import metrics as _telemetry
from repro.telemetry import spans as _spans


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10000
    clip_norm: float = 1.0
    adamw: adamw.AdamWConfig = adamw.AdamWConfig()
    step_deadline_s: float = 0.0       # 0 = no straggler deadline
    # Diagnostics mode for the telemetry step-time split: run the step as
    # two separately-jitted phases (fwd+bwd / clip+opt) and time each into
    # the ``train.*_seconds`` histograms, plus a separately-timed
    # forward-only pass so the backward share is attributable
    # (reverse-mode AD fuses fwd and bwd into one computation; the forward
    # re-run is extra compute, which is why this is opt-in and not the
    # production path).
    telemetry_split: bool = False


def _spec_axes(spec) -> set:
    """Mesh axes a PartitionSpec shards over."""
    present = set()
    for entry in tuple(spec):
        if entry is None:
            continue
        for ax in (entry if isinstance(entry, tuple) else (entry,)):
            present.add(ax)
    return present


def _replication_factor(spec, topo: Topology) -> int:
    present = _spec_axes(spec)
    repl = 1
    for name, size in zip(topo.cube.dim_names, topo.cube.dim_sizes):
        if name not in present:
            repl *= size
    return repl


def _clip_and_update(params, opt_state, grads, specs, topo: Topology,
                     tc: TrainConfig):
    """Global-norm clip, then AdamW: returns ``(params, opt_state, gnorm,
    lr)``. The norm is replication-aware: each leaf's local sum of squares
    is divided by its replication degree, then summed over the full cube."""
    sq = 0.0
    flat, tdef = jax.tree.flatten(grads)
    sflat = tdef.flatten_up_to(specs)
    for g, s in zip(flat, sflat):
        sq = sq + jnp.sum(jnp.square(g.astype(jnp.float32))
                          ) / _replication_factor(s, topo)
    sq = pvary_axes(sq, topo.cube.dim_names)
    gnorm = jnp.sqrt(topo.comm(topo.cube.dim_names).all_reduce(sq))
    scale = jnp.minimum(1.0, tc.clip_norm / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    lr = adamw.cosine_schedule(tc.lr, tc.warmup, tc.total_steps)(
        opt_state["step"])
    params, opt_state = adamw.update(params, opt_state, grads,
                                     lr=lr, cfg=tc.adamw)
    return params, opt_state, gnorm, lr


def make_train_step(cfg: ModelConfig, topo: Topology, tc: TrainConfig):
    """Returns (jitted step fn, batch_specs-less). Step signature:
    (params, opt_state, batch) -> (params, opt_state, metrics)."""
    model = Model(cfg, topo)
    specs = param_specs(cfg, topo)

    def step_shard(params, opt_state, batch):
        # Gradient reductions are inserted by shard_map's vma-aware autodiff
        # (check_vma=True): the FSDP AllGather transposes to a ReduceScatter
        # over `data`, and replicated-parameter gradients (norms, routers,
        # replicated KV, cross-pod) get their psums from the varying-axes
        # tracker -- the hierarchical schedule of paper §IX-A falls out of
        # the sharding structure.
        (loss, metrics), grads = jax.value_and_grad(
            model.loss_shard, has_aux=True)(params, batch)
        params, opt_state, gnorm, lr = _clip_and_update(
            params, opt_state, grads, specs, topo, tc)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
        return params, opt_state, metrics

    opt_specs = _opt_specs(cfg, topo, tc)
    batch_specs = input_batch_specs(cfg, topo)
    metric_specs = {k: P() for k in
                    ("ce_loss", "aux_loss", "tokens", "loss", "grad_norm",
                     "lr")}
    fn = shard_map(
        step_shard, mesh=topo.cube.mesh,
        in_specs=(specs, opt_specs, batch_specs),
        out_specs=(specs, opt_specs, metric_specs),
        check_vma=True)
    return jax.jit(fn, donate_argnums=(0, 1))


def make_split_train_step(cfg: ModelConfig, topo: Topology,
                          tc: TrainConfig):
    """The train step as separately-jitted phases, for the telemetry
    step-time split (``TrainConfig.telemetry_split``).

    Returns ``(fwd, fwd_bwd, opt)``:

    * ``fwd(params, batch) -> (loss, aux)`` -- forward only, timed so the
      backward share of ``fwd_bwd`` is attributable (bwd = fwd_bwd - fwd);
    * ``fwd_bwd(params, batch) -> (loss, aux, grads)`` -- the gradient
      reductions autodiff inserts run inside this phase;
    * ``opt(params, opt_state, grads) -> (params, opt_state, metrics)`` --
      global-norm clip + AdamW.

    Phase boundaries materialize intermediates the fused step would keep
    on-device, so the *sum* of phase times brackets, rather than equals,
    the fused step time -- the split is for attribution, not for the
    ``train_step`` bench rows.
    """
    model = Model(cfg, topo)
    specs = param_specs(cfg, topo)
    mesh = topo.cube.mesh
    opt_specs = _opt_specs(cfg, topo, tc)
    batch_specs = input_batch_specs(cfg, topo)
    aux_specs = {k: P() for k in ("ce_loss", "aux_loss", "tokens")}

    def fwd_shard(params, batch):
        return model.loss_shard(params, batch)

    def fwd_bwd_shard(params, batch):
        (loss, aux), grads = jax.value_and_grad(
            model.loss_shard, has_aux=True)(params, batch)
        return loss, aux, grads

    def opt_shard(params, opt_state, grads):
        params, opt_state, gnorm, lr = _clip_and_update(
            params, opt_state, grads, specs, topo, tc)
        return params, opt_state, {"grad_norm": gnorm, "lr": lr}

    fwd = jax.jit(shard_map(
        fwd_shard, mesh=mesh, in_specs=(specs, batch_specs),
        out_specs=(P(), aux_specs), check_vma=True))
    fwd_bwd = jax.jit(shard_map(
        fwd_bwd_shard, mesh=mesh, in_specs=(specs, batch_specs),
        out_specs=(P(), aux_specs, specs), check_vma=True))
    opt = jax.jit(shard_map(
        opt_shard, mesh=mesh, in_specs=(specs, opt_specs, specs),
        out_specs=(specs, opt_specs, {"grad_norm": P(), "lr": P()}),
        check_vma=True))
    return fwd, fwd_bwd, opt


def init_opt_state(cfg, topo, tc: TrainConfig):
    """Optimizer state for :func:`make_train_step`, zero and placed on the
    cube: AdamW moments (8-bit scales sized per last-dim shard, see
    :func:`adamw.state_defs`)."""
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype, device=s.sharding),
        opt_structs(cfg, topo, tc))


def _opt_specs(cfg, topo, tc: TrainConfig):
    defs = param_defs(cfg, topo)
    sd = adamw.state_defs(defs, tc.adamw,
                          is_leaf=lambda x: isinstance(x, ParamDef),
                          cube=topo.cube)
    return jax.tree.map(
        lambda d: d[1], sd,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
        and not isinstance(x[0], dict))


def opt_specs(cfg, topo, tc: TrainConfig):
    """Placement specs for :func:`init_opt_state`'s tree -- the opt half of
    a topology-bound :class:`~repro.checkpoint.CheckpointManager`'s
    ``specs={"params": ..., "opt": ...}`` binding."""
    return _opt_specs(cfg, topo, tc)


def opt_structs(cfg, topo, tc: TrainConfig):
    defs = param_defs(cfg, topo)
    sd = adamw.state_defs(defs, tc.adamw,
                          is_leaf=lambda x: isinstance(x, ParamDef),
                          cube=topo.cube)
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d[0], d[2],
                                       sharding=topo.cube.sharding(d[1])),
        sd, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
        and not isinstance(x[0], dict))


def input_batch_specs(cfg: ModelConfig, topo: Topology):
    dp = topo.dp
    specs = {"tokens": P(dp, None), "labels": P(dp, None)}
    if cfg.frontend == "patch":
        specs["patches"] = P(dp, None, None)
    if cfg.is_encoder_decoder:
        specs["frames"] = P(dp, None, None)
    return specs


# ------------------------------------------------------------------ driver
class Trainer:
    """Training loop with microbatch accumulation, straggler deadlines and
    checkpoint/restart hooks."""

    def __init__(self, cfg, topo, tc: TrainConfig, checkpointer=None):
        self.cfg, self.topo, self.tc = cfg, topo, tc
        self.step_fn = make_train_step(cfg, topo, tc)
        self.split_fns = (make_split_train_step(cfg, topo, tc)
                          if tc.telemetry_split else None)
        self.checkpointer = checkpointer
        self.slow_steps = 0

    def _record_step_telemetry(self, dt: float, straggler: bool) -> None:
        """Per-step metric updates (also the enabled-path payload the
        ``telemetry_overhead`` bench row measures)."""
        _telemetry.inc("train.steps")
        _telemetry.observe("train.step_seconds", dt)
        if straggler:
            _telemetry.inc("train.straggler_steps")

    def _run_split_step(self, params, opt_state, batch):
        """telemetry_split mode: phase-timed fwd / fwd+bwd / opt."""
        fwd, fwd_bwd, opt = self.split_fns
        t0 = time.monotonic()
        jax.block_until_ready(fwd(params, batch))
        t1 = time.monotonic()
        loss, aux, grads = fwd_bwd(params, batch)
        jax.block_until_ready(grads)
        t2 = time.monotonic()
        params, opt_state, om = opt(params, opt_state, grads)
        jax.block_until_ready((params, opt_state))
        t3 = time.monotonic()
        _telemetry.observe("train.fwd_seconds", t1 - t0)
        _telemetry.observe("train.fwd_bwd_seconds", t2 - t1)
        _telemetry.observe("train.opt_seconds", t3 - t2)
        metrics = dict(aux, loss=loss, **om)
        return params, opt_state, metrics

    def run(self, params, opt_state, batches, *, start_step=0,
            checkpoint_every=0, log_every=1, log=print):
        """Train on ``batches``; returns (params, opt_state, history).
        Spans: ``train.step`` around ``train.dispatch``, ``train.wait``,
        ``train.fetch`` (metrics to host) and ``train.checkpoint``."""
        step = start_step
        history = []
        for batch in batches:
            with _spans.maybe_span("train.step", step=step):
                params, opt_state, metrics, dt = self._step(
                    params, opt_state, batch)
                step += 1
                history.append(metrics)
                if log_every and step % log_every == 0:
                    log(f"step {step}: loss={metrics['loss']:.4f} "
                        f"gnorm={metrics['grad_norm']:.3f} {dt*1e3:.0f}ms")
                if (checkpoint_every and self.checkpointer
                        and step % checkpoint_every == 0):
                    # gather-at-dispatch: save() snapshots params/opt to
                    # host before returning (the jitted step donates both
                    # buffers), then overlaps serialization + disk writes
                    # with the next steps
                    from repro.checkpoint.manager import TrainState
                    with _spans.maybe_span("train.checkpoint", step=step):
                        self.checkpointer.save(
                            step, TrainState(params=params, opt=opt_state))
        return params, opt_state, history

    def _step(self, params, opt_state, batch):
        """One step through to its metrics on the host; returns (params,
        opt_state, metrics, wall seconds)."""
        t0 = time.monotonic()
        with _spans.maybe_span("train.dispatch"):
            # getattr: tests drive partially-constructed Trainers
            # (object.__new__) through run()
            if getattr(self, "split_fns", None) is not None:
                params, opt_state, metrics = self._run_split_step(
                    params, opt_state, batch)
            else:
                params, opt_state, metrics = self.step_fn(
                    params, opt_state, batch)
        # block on the step's real outputs before reading the clock: the
        # param/opt_state updates are not data-dependent on the logged
        # metrics, so coercing metrics alone lets async dispatch leak their
        # compute out of dt -- the straggler deadline and the logged
        # per-step ms would undercount
        with _spans.maybe_span("train.wait"):
            jax.block_until_ready((params, opt_state))
        with _spans.maybe_span("train.fetch"):
            metrics = {k: float(v) for k, v in metrics.items()}
        dt = time.monotonic() - t0
        straggler = bool(self.tc.step_deadline_s
                         and dt > self.tc.step_deadline_s)
        if straggler:
            # straggler mitigation: record and continue -- on a real
            # cluster this triggers the runtime's slow-host report
            self.slow_steps += 1
            metrics["straggler"] = 1.0
        if _telemetry.enabled():
            self._record_step_telemetry(dt, straggler)
        return params, opt_state, metrics, dt
