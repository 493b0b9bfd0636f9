"""End-to-end train-step benchmark.

The primitive sweep prices collectives in isolation; this section prices a
whole training step (fwd -> bwd -> clip -> AdamW, the gradient reductions
inserted by ``shard_map(check_vma=True)`` autodiff) on the multi-pod CPU
substrate (2 pods x 2 data x 2 model), the accounting the PIM methodology
survey (arXiv:2205.14647) asks for.

The step contributes one ``train_step`` row to the ``programs`` section of
the bench trajectory: ``measured_us`` is the median wall time per step (the
regression-gate column), ``ops`` counts the collectives the first (traced)
call dispatched through the communicators, and ``plan_est_us`` /
``serial_est_us`` both sum those dispatches' planner estimates (they are
issued one by one, not as a jointly planned program).  Under the tuned
CommProfile of a ``--profile`` run the estimates are measured-sourced.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks._timing import bench, emit

ARCH = "qwen3-1.7b"
STEP_NAME = "train_step"

# Upscale the smoke config until the pod-crossing gradient reductions are a
# real fraction of the step (~25MB of replicated gradients): at pure smoke
# scale they are <1% of wall time and drown in step noise.
SCALE = dict(d_model=256, n_heads=8, head_dim=32, d_ff=1024, vocab_size=8192)


def _setup_train():
    from repro.configs import get
    from repro.launch.mesh import make_mesh
    from repro.models.topology import build_topology
    cfg = dataclasses.replace(get(ARCH).scaled_for_smoke(), tp=2, **SCALE)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    topo = build_topology(cfg, mesh)
    return cfg, topo


def _make_batch(cfg, B=8, S=32, seed=11):
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    return {
        "tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)),
                              jnp.int32),
        "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)),
                              jnp.int32),
    }


def _fresh_state(cfg, topo, tc):
    import jax
    import jax.numpy as jnp
    from repro.models.params import init_params
    from repro.runtime.trainer import opt_structs
    params = init_params(cfg, topo, seed=3)
    # moment shapes (8-bit quantization scale columns) depend on the mesh
    # sharding, so build them from the dry-run structs, not init_state
    opt = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                       opt_structs(cfg, topo, tc))
    return params, opt


def _step_timer(step_fn, params, opt_state, batch):
    """Per-call closure that threads the (donated) carry through."""
    import jax
    state = [params, opt_state]
    def call():
        p, o, _ = step_fn(state[0], state[1], batch)
        jax.block_until_ready((p, o))
        state[0], state[1] = p, o
    return call


def train_step_bench():
    """Emits the ``train_step`` program row, then the
    ``telemetry_overhead`` and ``ckpt_overlap`` rows on the same step."""
    from repro.core.comm import CommTrace
    from repro.runtime.trainer import TrainConfig, make_train_step

    cfg, topo = _setup_train()
    batch = _make_batch(cfg, B=8, S=64)
    tc = TrainConfig()
    step = make_train_step(cfg, topo, tc)

    # the first call is the one jax traces, so it is the call the CommTrace
    # must wrap to see the step's comm events
    params, opt_state = _fresh_state(cfg, topo, tc)
    with CommTrace() as tr:
        step(params, opt_state, batch)
    est_us = sum(e.seconds for e in tr.events) * 1e6
    sources = {e.est_source for e in tr.events}
    source = sources.pop() if len(sources) == 1 else ("mixed" if sources
                                                      else "analytic")

    params, opt_state = _fresh_state(cfg, topo, tc)
    us = bench(_step_timer(step, params, opt_state, batch), warmup=2, reps=7)
    row = {"name": STEP_NAME, "ops": len(tr.events),
           "measured_us": round(us, 2),
           "plan_est_us": round(est_us, 3),
           "serial_est_us": round(est_us, 3),
           "est_source": source}
    emit(f"train_step/{ARCH}", us,
         f"events={len(tr.events)};comm_est_us={est_us:.1f}"
         f";est_source={source}")

    overhead = telemetry_overhead_bench(cfg, topo, step, tc, batch,
                                        disabled_us=row["measured_us"])
    ckpt = ckpt_overlap_bench(cfg, topo, tc)
    return [row, overhead, ckpt]


def ckpt_overlap_bench(cfg, topo, tc):
    """``ckpt_overlap`` row: what an async checkpoint save costs the
    training loop per dispatch.

    ``measured_us`` is the median wall time of an async
    ``CheckpointManager.save()`` call -- the rooted-gather programs
    (device->host, must run at dispatch because the train step donates the
    buffers) plus the executor handoff; serialization and disk writes are
    off the timed path.  ``plan_est_us``/``serial_est_us`` price the
    recorded gather programs through :func:`planner.plan_program`
    (overlap-priced vs summed per-op estimates).  The derived cell carries
    the synchronous save wall time: ``sync_save_us - measured_us`` is the
    write time the async design hides under training.
    """
    import shutil
    import tempfile
    import time as _time

    from repro.checkpoint.manager import CheckpointManager, TrainState
    from repro.core import planner
    from repro.core.comm import CommTrace
    from repro.models.params import param_specs
    from repro.runtime.trainer import opt_specs

    params, opt_state = _fresh_state(cfg, topo, tc)
    state = TrainState(params=params, opt=opt_state)
    specs = {"params": param_specs(cfg, topo),
             "opt": opt_specs(cfg, topo, tc)}
    root = tempfile.mkdtemp(prefix="bench-ckpt-")
    try:
        mgr = CheckpointManager(root, topo=topo, specs=specs, keep_last=1)
        with CommTrace() as tr:   # first save records + lowers the gathers
            mgr.save(0, state)
        mgr.wait()

        by_prog: dict[str, list] = {}
        for e in tr.events:
            if e.program_id and e.program_id.startswith("ckpt-gather"):
                by_prog.setdefault(e.program_id, []).append(e)
        serial_s = sum(e.seconds for evs in by_prog.values() for e in evs)
        plans = {pid: planner.plan_program(topo.cube, [
            planner.ProgramOpSpec(op_id=i, primitive=e.primitive,
                                  dims=e.dims, payload_bytes=e.payload_bytes)
            for i, e in enumerate(evs)]) for pid, evs in by_prog.items()}
        plan_s = sum(p.seconds for p in plans.values())
        sources = {p.est_source for p in plans.values()}
        source = sources.pop() if len(sources) == 1 else "mixed"
        n_ops = sum(len(evs) for evs in by_prog.values())

        def timed_saves(manager, reps):
            times, step = [], manager.latest_step() or 0
            for _ in range(reps):
                step += 1
                manager.wait()    # drain OUTSIDE the timed window
                t0 = _time.perf_counter()
                manager.save(step, state)
                times.append(_time.perf_counter() - t0)
            manager.wait()
            times.sort()
            return times[len(times) // 2] * 1e6

        timed_saves(mgr, 2)                      # warmup (cache-hit path)
        async_us = timed_saves(mgr, 5)
        sync_mgr = CheckpointManager(root, topo=topo, specs=specs,
                                     keep_last=1, async_save=False)
        sync_us = timed_saves(sync_mgr, 5)
        emit(f"train_step/{ARCH}/ckpt_overlap", async_us,
             f"sync_save_us={sync_us:.1f}"
             f";hidden_write_us={sync_us - async_us:.1f}"
             f";gather_ops={n_ops};est_source={source}")
        return {"name": "ckpt_overlap", "ops": n_ops,
                "measured_us": round(async_us, 2),
                "plan_est_us": round(plan_s * 1e6, 3),
                "serial_est_us": round(serial_s * 1e6, 3),
                "est_source": source}
    finally:
        shutil.rmtree(root, ignore_errors=True)


def telemetry_overhead_bench(cfg, topo, step_fn, tc, batch, *,
                             disabled_us: float):
    """``telemetry_overhead`` row: the train step re-timed with metrics
    enabled and a Tracer active, including the per-step bookkeeping
    ``Trainer.run`` does on the enabled path (span + counter + histogram).
    ``measured_us`` is the enabled step; ``plan_est_us``/``serial_est_us``
    carry the disabled baseline (the already-gated ``train_step`` cell),
    so the gate tracks the enabled path and the ratio of the two columns
    is the relative overhead -- "disabled within noise of the pre-PR step"
    is enforced by the ``train_step`` row.

    The Tracer sees no CommEvents here (the step is already compiled;
    dispatch happens at trace time), so this prices exactly the
    steady-state cost a metered production loop pays per step.
    """
    from repro import telemetry

    params, opt_state = _fresh_state(cfg, topo, tc)
    inner = _step_timer(step_fn, params, opt_state, batch)

    def call():
        with telemetry.maybe_span("train.step", cat="wall"):
            inner()
        telemetry.inc("train.steps")
        telemetry.observe("train.step_seconds", 0.0)

    telemetry.enable_metrics()
    try:
        with telemetry.Tracer():
            us = bench(call, warmup=2, reps=7)
    finally:
        telemetry.disable_metrics()
        telemetry.REGISTRY.reset()
    emit(f"train_step/{ARCH}/telemetry_overhead", us,
         f"disabled_us={disabled_us:.1f}"
         f";overhead_ratio={us / disabled_us:.4f}")
    return {"name": "telemetry_overhead", "ops": 2,
            "measured_us": round(us, 2),
            "plan_est_us": round(disabled_us, 2),
            "serial_est_us": round(disabled_us, 2),
            "est_source": "measured"}


def run():
    from benchmarks import primitives
    primitives.PROGRAM_ROWS.extend(train_step_bench())
