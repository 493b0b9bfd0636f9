"""The distributed train step and training loop driver.

The step is one shard_map over the architecture's hypercube:

  fwd/bwd (FSDP AllGather / ReduceScatter + TP AllGather/ReduceScatter +
  EP AlltoAll, all dispatched through topology-bound communicators with
  ``algorithm="auto"``) -> tagged gradient all-reduces -> cross-pod gradient
  all-reduce over the DCN axis (hierarchical §IX-A via the planner's pick;
  optionally int8 §V-C when ``compress_pod_grads`` is set) -> global-norm
  clip -> AdamW(8-bit moments).

The loop driver adds microbatch accumulation, per-step deadlines (straggler
mitigation) and checkpoint/restart.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
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
    # int8 DCN gradient hop (paper §V-C): pod-crossing replicated-gradient
    # all-reduces dispatch the registry's "compressed" algorithm (a
    # custom_vjp-bounded hierarchical flow whose DCN hop is blockwise-absmax
    # int8, core/compress.py). Effective on the explicit pre-vma gradient
    # sync path; on vma-tracking jax the autodiff-inserted psums already ran
    # and the flag is a no-op (make_train_step warns).
    compress_pod_grads: bool = False
    # Error feedback for the compressed hop: persist each leaf's int8
    # quantization residual in ``opt_state["ef"]`` and fold it into the next
    # step's gradient, so the lossy DCN compression's bias does not
    # accumulate (effective only with compress_pod_grads on the explicit
    # pre-vma sync path over a DCN-crossing cube -- see use_error_feedback).
    error_feedback: bool = True
    # Backward-overlapped gradient sync (ROADMAP open item #1): bucket the
    # replicated-leaf all-reduces by reverse-layer order and fire each
    # bucket's program *during* backward via custom_vjp hooks
    # (repro.runtime.overlap), instead of one barrier sync after backward
    # completes.  Bit-identical to the barrier path.  Effective on the
    # explicit pre-vma sync path without compressed pod gradients; the
    # compressed/error-feedback flow keeps the barrier sync (blockwise
    # int8 quantization is bucketing-sensitive), and on vma jax autodiff
    # already interleaves the reductions.
    overlap_grad_sync: bool = True
    step_deadline_s: float = 0.0       # 0 = no straggler deadline
    # Diagnostics mode for the telemetry step-time split: run the step as
    # three separately-jitted phases (fwd+bwd / grad-sync / clip+opt) and
    # time each into the ``train.*_seconds`` histograms, plus a
    # separately-timed forward-only pass so the backward share is
    # attributable (reverse-mode AD fuses fwd and bwd into one
    # computation; the forward re-run is extra compute, which is why this
    # is opt-in and not the production path).  Plain sync path only.
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


def replication_dims(spec, cube) -> tuple[str, ...]:
    """Cube axes a leaf with PartitionSpec ``spec`` is replicated over."""
    present = _spec_axes(spec)
    return tuple(d for d, n in zip(cube.dim_names, cube.dim_sizes)
                 if d not in present and n > 1)


def sync_replicated_grads(grads, specs, cube, *, compress_pod: bool = False,
                          ef=None):
    """Insert the gradient all-reduces that vma-aware autodiff
    (check_vma=True on jax 0.5+) derives automatically: each leaf's
    per-shard gradient must be summed over every cube axis its spec does
    not shard (its replication axes), because sharded compute feeding a
    replicated parameter leaves one partial contribution per shard.

    The per-leaf reductions are recorded into **one deferred CommProgram**
    (``cube.program()``): lowering coalesces the many small same-group
    all-reduces into bucketed dispatches and jointly plans the schedule, so
    a trainer with dozens of replicated leaves issues a handful of
    collectives instead of one per leaf -- bit-identically, since a psum of
    concatenated leaves equals the concatenation of per-leaf psums.  The
    recorded structure is identical every step (only the captured gradient
    tracers change), so the program lower cache
    (:mod:`repro.core.program` ``LOWER_STATS``) hands every sync after the
    first its already-built buckets and joint plan -- re-tracing does not
    re-run the rewrite passes.  Every
    dispatch still runs ``algorithm="auto"`` through the registry (a
    pod-crossing gradient sum executes the planner's hierarchical §IX-A
    pick) and is recorded by any active CommTrace with program provenance.

    With ``compress_pod`` the DCN-crossing reductions take the registry's
    "compressed" int8 flow (§V-C) instead.  ``ef`` (a dict of
    flat-leaf-index -> error-feedback buffer, see
    :func:`init_error_feedback`) additionally threads the compressed hop's
    quantization error across steps: the leaf gradient is pre-corrected by
    the stored error and the new residual is returned --
    ``(synced_grads, new_ef)`` when ``ef`` is given.

    No-op when the installed jax tracks varying axes in avals
    (compat.HAS_VMA): there the psums were already inserted by autodiff.
    """
    from repro import compat
    if compat.HAS_VMA:
        return grads if ef is None else (grads, ef)
    flat, tdef = jax.tree.flatten(grads)
    sflat = tdef.flatten_up_to(specs)
    out: list = [None] * len(flat)
    new_ef = dict(ef) if ef is not None else None
    deferred: list[tuple[int, object]] = []   # (leaf index, ProgramValue)
    prog = cube.program(name="grad-sync")
    with prog:
        for i, (g, s) in enumerate(zip(flat, sflat)):
            missing = replication_dims(s, cube)
            if not missing:
                out[i] = g
                continue
            comm = cube.comm(missing)
            if compress_pod and comm.crosses_dcn:
                if new_ef is not None and str(i) in new_ef:
                    # eager two-output flow: correct by the carried error,
                    # persist the fresh quantization residual
                    red, err = comm.all_reduce_with_error(
                        g.astype(jnp.float32), error=new_ef[str(i)][0])
                    out[i] = red.astype(g.dtype)
                    new_ef[str(i)] = err[jnp.newaxis]
                else:
                    deferred.append(
                        (i, comm.all_reduce(g, algorithm="compressed")))
            else:
                deferred.append((i, comm.all_reduce(g)))
        prog.output(*(v for _, v in deferred))
    if deferred:
        results = prog.execute()
        if len(deferred) == 1:
            results = (results,)
        for (i, _), r in zip(deferred, results):
            out[i] = r
    synced = jax.tree.unflatten(tdef, out)
    return synced if ef is None else (synced, new_ef)


def init_error_feedback(params, specs, cube):
    """Zero error-feedback buffers for the §V-C compressed gradient hop.

    One buffer per gradient leaf whose replication axes cross DCN: shape
    ``(n_slow, *leaf.shape)`` sharded ``P(dcn_dims, *leaf_spec)`` -- the
    quantization error is identical within a pod (it is all-gathered over
    the ICI group) but differs across pods, so the pod axis must be
    materialized.  Keyed by flattened leaf index (a string, so the dict is
    a plain pytree for checkpointing).
    """
    flat, tdef = jax.tree.flatten(params)
    sflat = tdef.flatten_up_to(specs)
    slow = cube.dcn_dims
    n_slow = int(np.prod([cube.size(d) for d in slow])) if slow else 1
    out = {}
    for i, (p, s) in enumerate(zip(flat, sflat)):
        missing = replication_dims(s, cube)
        if missing and any(d in cube.dcn_dims for d in missing):
            buf = jnp.zeros((n_slow,) + tuple(p.shape), jnp.float32)
            out[str(i)] = jax.device_put(
                buf, cube.sharding(P(slow, *tuple(s))))
    return out


def error_feedback_specs(cfg, topo, tc: "TrainConfig"):
    """PartitionSpecs matching :func:`init_error_feedback` (for shard_map
    in/out specs and dry-run structs)."""
    defs = param_defs(cfg, topo)
    flat, tdef = jax.tree.flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    specs = param_specs(cfg, topo)
    sflat = tdef.flatten_up_to(specs)
    cube = topo.cube
    out = {}
    for i, (d, s) in enumerate(zip(flat, sflat)):
        missing = replication_dims(s, cube)
        if missing and any(x in cube.dcn_dims for x in missing):
            out[str(i)] = P(cube.dcn_dims, *tuple(s))
    return out


def use_error_feedback(tc: "TrainConfig", cube) -> bool:
    """Whether this run threads an error-feedback buffer through opt_state:
    compressed pod gradients requested, the explicit (pre-vma) sync path is
    active, and the cube actually crosses DCN."""
    from repro import compat
    return bool(tc.compress_pod_grads and tc.error_feedback
                and not compat.HAS_VMA and cube.dcn_dims)


def make_train_step(cfg: ModelConfig, topo: Topology, tc: TrainConfig):
    """Returns (jitted step fn, batch_specs-less). Step signature:
    (params, opt_state, batch) -> (params, opt_state, metrics)."""
    model = Model(cfg, topo)
    specs = param_specs(cfg, topo)
    lr_fn = adamw.cosine_schedule(tc.lr, tc.warmup, tc.total_steps)
    from repro import compat
    if tc.compress_pod_grads and compat.HAS_VMA:
        import warnings
        warnings.warn(
            "compress_pod_grads is a no-op on vma-tracking jax: gradient "
            "reductions are inserted by autodiff before the trainer can "
            "route them through the compressed collective")

    with_ef = use_error_feedback(tc, topo.cube)
    # backward-overlapped sync: pre-vma explicit path only, and not under
    # the compressed/error-feedback flow (blockwise int8 quantization is
    # bucketing-sensitive; the barrier path keeps its accuracy contract)
    overlap_sync = (tc.overlap_grad_sync and not compat.HAS_VMA
                    and not with_ef and not tc.compress_pod_grads)
    if overlap_sync:
        from repro.runtime.overlap import with_backward_bucket_sync
        loss_overlapped = with_backward_bucket_sync(
            model.loss_shard, specs, topo.cube)

    def step_shard(params, opt_state, batch):
        # Gradient reductions are inserted by shard_map's vma-aware autodiff
        # (check_vma=True): the FSDP AllGather transposes to a ReduceScatter
        # over `data`, and replicated-parameter gradients (norms, routers,
        # replicated KV, cross-pod) get their psums from the varying-axes
        # tracker -- the hierarchical schedule of paper §IX-A falls out of
        # the sharding structure.
        if overlap_sync:
            # pre-vma jax, overlapped: per-bucket custom_vjp hooks fire
            # each bucket's grad-sync program during backward (reverse-
            # layer order), so grads come out already synced
            (loss, metrics), grads = jax.value_and_grad(
                loss_overlapped, has_aux=True)(params, batch)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                model.loss_shard, has_aux=True)(params, batch)
        # pre-vma jax, barrier path: restore the replicated-leaf
        # all-reduces by hand -- recorded as one coalesced CommProgram,
        # planner-dispatched (hierarchical across pods; int8 + error
        # feedback when enabled)
        if with_ef:
            grads, new_ef = sync_replicated_grads(
                grads, specs, topo.cube, compress_pod=True,
                ef=opt_state["ef"])
        elif not overlap_sync:
            grads = sync_replicated_grads(grads, specs, topo.cube,
                                          compress_pod=tc.compress_pod_grads)

        # global-norm clip (replication-aware: local sum-of-squares divided
        # by each leaf's replication degree, then summed over the full cube)
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

        lr = lr_fn(opt_state["step"])
        params, opt_state = adamw.update(params, opt_state, grads,
                                         lr=lr, cfg=tc.adamw)
        if with_ef:
            opt_state["ef"] = new_ef
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

    Returns ``(fwd, fwd_bwd, sync, opt)``:

    * ``fwd(params, batch) -> (loss, aux)`` -- forward only, timed so the
      backward share of ``fwd_bwd`` is attributable (bwd = fwd_bwd - fwd);
    * ``fwd_bwd(params, batch) -> (loss, aux, grads)``;
    * ``sync(grads) -> grads`` -- the explicit replicated-leaf gradient
      sync; ``None`` on vma-tracking jax (autodiff already inserted the
      reductions inside ``fwd_bwd``, so there is no separable phase);
    * ``opt(params, opt_state, grads) -> (params, opt_state, metrics)`` --
      global-norm clip + AdamW.

    Phase boundaries materialize intermediates the fused step would keep
    on-device, so the *sum* of phase times brackets, rather than equals,
    the fused step time -- the split is for attribution, not for the
    ``train_step`` bench rows.  Plain sync path only (no compressed pod
    gradients / error feedback).
    """
    from repro import compat
    if tc.compress_pod_grads:
        raise ValueError(
            "telemetry_split supports the plain gradient-sync path only "
            "(compress_pod_grads records inside the fused step)")
    model = Model(cfg, topo)
    specs = param_specs(cfg, topo)
    lr_fn = adamw.cosine_schedule(tc.lr, tc.warmup, tc.total_steps)
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

    def sync_shard(grads):
        return sync_replicated_grads(grads, specs, topo.cube)

    def opt_shard(params, opt_state, grads):
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
        lr = lr_fn(opt_state["step"])
        params, opt_state = adamw.update(params, opt_state, grads,
                                         lr=lr, cfg=tc.adamw)
        return params, opt_state, {"grad_norm": gnorm, "lr": lr}

    fwd = jax.jit(shard_map(
        fwd_shard, mesh=mesh, in_specs=(specs, batch_specs),
        out_specs=(P(), aux_specs), check_vma=True))
    fwd_bwd = jax.jit(shard_map(
        fwd_bwd_shard, mesh=mesh, in_specs=(specs, batch_specs),
        out_specs=(P(), aux_specs, specs), check_vma=True))
    sync = None
    if not compat.HAS_VMA:
        sync = jax.jit(shard_map(
            sync_shard, mesh=mesh, in_specs=(specs,), out_specs=specs,
            check_vma=False))
    opt = jax.jit(shard_map(
        opt_shard, mesh=mesh, in_specs=(specs, opt_specs, specs),
        out_specs=(specs, opt_specs, {"grad_norm": P(), "lr": P()}),
        check_vma=True))
    return fwd, fwd_bwd, sync, opt


def init_opt_state(cfg, topo, tc: TrainConfig):
    """Optimizer state for :func:`make_train_step`, zero and placed on the
    cube: AdamW moments (8-bit scales sized per last-dim shard, see
    :func:`adamw.state_defs`) plus the compressed-hop error-feedback
    buffers when this run threads them."""
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype, device=s.sharding),
        opt_structs(cfg, topo, tc))


def _opt_specs(cfg, topo, tc: TrainConfig):
    defs = param_defs(cfg, topo)
    sd = adamw.state_defs(defs, tc.adamw,
                          is_leaf=lambda x: isinstance(x, ParamDef),
                          cube=topo.cube)
    specs = jax.tree.map(
        lambda d: d[1], sd,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
        and not isinstance(x[0], dict))
    if use_error_feedback(tc, topo.cube):
        specs["ef"] = error_feedback_specs(cfg, topo, tc)
    return specs


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
    structs = jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d[0], d[2],
                                       sharding=topo.cube.sharding(d[1])),
        sd, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
        and not isinstance(x[0], dict))
    if use_error_feedback(tc, topo.cube):
        cube = topo.cube
        n_slow = int(np.prod([cube.size(d) for d in cube.dcn_dims]))
        flat, tdef = jax.tree.flatten(
            param_defs(cfg, topo), is_leaf=lambda x: isinstance(x, ParamDef))
        shapes = {str(i): (n_slow,) + tuple(d.shape)
                  for i, d in enumerate(flat)}
        structs["ef"] = {
            k: jax.ShapeDtypeStruct(shapes[k], jnp.float32,
                                    sharding=topo.cube.sharding(spec))
            for k, spec in error_feedback_specs(cfg, topo, tc).items()}
    return structs


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
        self._sync_priced = False

    def _record_step_telemetry(self, dt: float, straggler: bool) -> None:
        """Per-step metric updates (also the enabled-path payload the
        ``telemetry_overhead`` bench row measures)."""
        _telemetry.inc("train.steps")
        _telemetry.observe("train.step_seconds", dt)
        if straggler:
            _telemetry.inc("train.straggler_steps")

    def _price_sync_estimates(self, events) -> None:
        """Set the grad-sync planner-estimate gauges from the traced
        step's CommEvents: serial = every program-recorded sync second on
        the critical path; exposed = only the final bucket's, the one the
        overlap path cannot hide under backward."""
        by_prog: dict = {}
        for e in events:
            if e.program_id and str(e.program_id).startswith("grad-sync"):
                by_prog.setdefault(e.program_id, []).append(e)
        if not by_prog:
            return
        serial = sum(e.seconds for evs in by_prog.values() for e in evs)
        # overlap buckets are named grad-sync-b{k}; the highest k is the
        # final bucket.  The barrier path's single unsuffixed program is
        # then also the "last" -- fully exposed.
        last = max(by_prog, key=lambda pid: int(pid.rsplit("-b", 1)[1])
                   if "-b" in pid else -1)
        exposed = sum(e.seconds for e in by_prog[last])
        _telemetry.set_gauge("train.sync_serial_est_us", serial * 1e6)
        _telemetry.set_gauge("train.sync_exposed_est_us", exposed * 1e6)

    def _run_split_step(self, params, opt_state, batch):
        """telemetry_split mode: phase-timed fwd / fwd+bwd / sync / opt."""
        fwd, fwd_bwd, sync, opt = self.split_fns
        t0 = time.monotonic()
        jax.block_until_ready(fwd(params, batch))
        t1 = time.monotonic()
        loss, aux, grads = fwd_bwd(params, batch)
        jax.block_until_ready(grads)
        t2 = time.monotonic()
        if sync is not None:
            grads = sync(grads)
            jax.block_until_ready(grads)
        t3 = time.monotonic()
        params, opt_state, om = opt(params, opt_state, grads)
        jax.block_until_ready((params, opt_state))
        t4 = time.monotonic()
        _telemetry.observe("train.fwd_seconds", t1 - t0)
        _telemetry.observe("train.fwd_bwd_seconds", t2 - t1)
        _telemetry.observe("train.sync_seconds", t3 - t2)
        _telemetry.observe("train.opt_seconds", t4 - t3)
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
            elif (_telemetry.enabled()
                  and not getattr(self, "_sync_priced", True)):
                # first metered step: trace the grad-sync events once
                # to price the serial/exposed sync-estimate gauges
                from repro.core.comm import CommTrace
                with CommTrace() as ct:
                    params, opt_state, metrics = self.step_fn(
                        params, opt_state, batch)
                self._price_sync_estimates(ct.events)
                self._sync_priced = True
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
