"""End-to-end train-step benchmark: comm-visible vs comm-hidden grad sync.

The primitive sweep prices collectives in isolation; this section prices a
whole training step (fwd -> bwd -> gradient sync -> clip -> AdamW) on the
multi-pod CPU substrate (2 pods x 2 data x 2 model), the accounting the PIM
methodology survey (arXiv:2205.14647) asks for.  Two variants of the same
step run on identical params/batch:

  barrier (comm-visible)
      ``TrainConfig(overlap_grad_sync=False)``: backward completes, then
      one coalesced grad-sync program executes -- every wire microsecond
      lands on the critical path.

  overlap (comm-hidden)
      ``TrainConfig(overlap_grad_sync=True)``: reverse-layer bucket
      programs fire *during* backward via custom_vjp hooks
      (:mod:`repro.runtime.overlap`), so the head bucket's sync runs under
      the remaining backward compute.

Both step functions are checked bit-identical (same updated params from
the same inputs) before timing.  Each variant contributes a row to the
``programs`` section of the bench trajectory: ``measured_us`` is the
median wall time per step (the regression-gate column -- on this
substrate's in-process device threads the two fused programs wall-time
within noise of each other, XLA CPU serializes collectives against
compute), ``serial_est_us`` sums the step's traced grad-sync op estimates
(all comm priced on the critical path), and ``plan_est_us`` is the
*exposed* sync budget under the DDP exposure model (see
:func:`_price_step`): the barrier program is fully exposed, the
overlapped path exposes only its final bucket, so the overlapped row's
``plan_est_us`` sits strictly below the barrier row's.  Under the tuned
CommProfile of a ``--profile`` run both estimate columns are
measured-sourced.  On vma-tracking jax the hook path is inert, so the two
variants collapse to the same step -- the rows still gate wall-time
regressions but the overlap-vs-barrier gap is only meaningful on the
pre-vma leg.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks._timing import bench, emit

ARCH = "qwen3-1.7b"
STEP_NAME = "train_step"      # row names: train_step_barrier/_overlap

# Upscale the smoke config until the pod-crossing gradient sync is a real
# fraction of the step (~25MB of replicated gradients): at pure smoke scale
# the sync is <1% of wall time and the overlap win drowns in step noise.
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


def _price_step(tr, cube):
    """(ops, exposed_plan_us, serial_est_us, est_source) for one traced
    step.  ``serial`` sums every grad-sync op's estimate (all comm priced
    on the critical path).  ``exposed`` prices what the sync adds to the
    step under the DDP exposure model: the barrier path's single program
    is entirely exposed (it cannot start before the last gradient exists),
    while the overlapped path exposes only its *final* bucket -- the one
    whose cotangents are backward's last outputs -- because every earlier
    bucket fires with backward compute still ahead to hide under.  Each
    program is priced by :func:`planner.plan_program`, so under an
    installed tuned CommProfile both columns are measured-sourced."""
    from repro.core import planner
    by_prog: dict[str, list] = {}
    for e in tr.events:
        if e.program_id and e.program_id.startswith("grad-sync"):
            by_prog.setdefault(e.program_id, []).append(e)
    serial_s = sum(e.seconds for evs in by_prog.values() for e in evs)
    plans = {}
    for pid, evs in by_prog.items():
        plans[pid] = planner.plan_program(cube, [
            planner.ProgramOpSpec(op_id=i, primitive=e.primitive,
                                  dims=e.dims, payload_bytes=e.payload_bytes)
            for i, e in enumerate(evs)])
    sources = {p.est_source for p in plans.values()}
    source = sources.pop() if len(sources) == 1 else ("mixed" if sources
                                                      else "analytic")
    # buckets are named grad-sync-b{k}; the highest k (the embedding
    # bucket) is the one backward cannot hide.  The barrier path has one
    # unsuffixed program, which is then also the "last" -- fully exposed.
    exposed_s = 0.0
    if plans:
        last = max(plans, key=lambda pid: int(pid.rsplit("-b", 1)[1])
                   if "-b" in pid else -1)
        exposed_s = plans[last].seconds
    return len(tr.events), exposed_s * 1e6, serial_s * 1e6, source


def _assert_bit_identical(p_a, p_b):
    import jax
    flat_a, tdef = jax.tree.flatten(jax.device_get(p_a))
    flat_b = tdef.flatten_up_to(jax.device_get(p_b))
    for a, b in zip(flat_a, flat_b):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            "overlapped grad sync diverged from the barrier path")


def train_step_bench():
    """Emits train_step_{barrier,overlap} program rows; asserts the two
    sync paths produce bit-identical updated params first."""
    from repro.core.comm import CommTrace
    from repro.runtime.trainer import TrainConfig, make_train_step

    cfg, topo = _setup_train()
    batch = _make_batch(cfg, B=8, S=64)
    variants = {
        "barrier": TrainConfig(overlap_grad_sync=False),
        "overlap": TrainConfig(overlap_grad_sync=True),
    }
    steps = {tag: make_train_step(cfg, topo, tc)
             for tag, tc in variants.items()}

    # bit-identity gate: one step of each variant from identical state --
    # this first call is also the one jax traces, so it is the call the
    # CommTrace must wrap to see the step's comm events
    stepped, traces = {}, {}
    for tag, tc in variants.items():
        params, opt_state = _fresh_state(cfg, topo, tc)
        with CommTrace() as tr:
            p1, _, _ = steps[tag](params, opt_state, batch)
        stepped[tag], traces[tag] = p1, tr
    _assert_bit_identical(stepped["barrier"], stepped["overlap"])

    rows = {}
    for tag, tc in variants.items():
        params, opt_state = _fresh_state(cfg, topo, tc)
        tr = traces[tag]
        call = _step_timer(steps[tag], params, opt_state, batch)
        us = bench(call, warmup=2, reps=7)
        n_ops, exposed_us, serial_us, source = _price_step(tr, topo.cube)
        rows[tag] = {"name": f"{STEP_NAME}_{tag}", "ops": n_ops,
                     "measured_us": round(us, 2),
                     "plan_est_us": round(exposed_us, 3),
                     "serial_est_us": round(serial_us, 3),
                     "est_source": source}
        emit(f"train_step/{ARCH}/{tag}", us,
             f"events={n_ops};sync_exposed_us={exposed_us:.1f}"
             f";sync_serial_us={serial_us:.1f};est_source={source}")
    hidden = (rows["barrier"]["plan_est_us"]
              - rows["overlap"]["plan_est_us"])
    emit(f"train_step/{ARCH}/comm_hidden_us", hidden,
         "barrier_exposed_minus_overlap_exposed")

    overhead = telemetry_overhead_bench(cfg, topo, steps["barrier"],
                                        variants["barrier"], batch,
                                        disabled_us=rows["barrier"]
                                        ["measured_us"])
    ckpt = ckpt_overlap_bench(cfg, topo, variants["barrier"])
    return [rows["barrier"], rows["overlap"], overhead, ckpt]


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
    """``telemetry_overhead`` row: the barrier step re-timed with metrics
    enabled and a Tracer active, including the per-step bookkeeping
    ``Trainer.run`` does on the enabled path (span + counter + histogram).
    ``measured_us`` is the enabled step; ``plan_est_us``/``serial_est_us``
    carry the disabled baseline (the already-gated ``train_step_barrier``
    cell), so the gate tracks the enabled path and the ratio of the two
    columns is the relative overhead -- "disabled within noise of the
    pre-PR step" is enforced by the unchanged ``train_step_barrier`` row.

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
