"""Quickstart: the PID-Comm communicator API in five minutes.

Builds a 2x2x2 virtual hypercube over 8 (fake CPU) devices, binds
communicators to dim selections (``cube.comm``), runs multi-instance
collectives over cube slices (paper Fig. 5), sweeps the Table II algorithm
stages, lets planner-driven ``algorithm="auto"`` dispatch pick the
§IX-A hierarchical flow on a pod-crossing all-reduce -- with every dispatch
observed by a :class:`CommTrace` -- and records a deferred ``cube.program()``
whose lowering fuses a reduce_scatter+all_gather chain into one all_reduce.
Section 9 runs the continuous-batching serve engine (paged KV cache + one
recorded CommProgram per decode step) through an admit -> prefill ->
decode -> evict request lifecycle.  Section 10 races
the collective-fused kernels (repro.kernels.collective): a measured
profile steers a recorded program's all_gather onto the ring_fused flow,
bit-identically.

    PYTHONPATH=src python examples/quickstart.py

Set ``QUICKSTART_SUMMARY=/path.json`` to dump the CommTrace summaries
(CI uploads them as the API-surface artifact).
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp
import numpy as np
from repro.compat import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import CommTrace, Hypercube, plan
from repro.launch.mesh import make_mesh

# 1. define a virtual hypercube over the physical mesh (paper §IV-B):
#    dims are user-chosen; mapping follows the device hierarchy.
mesh = make_mesh((2, 4), ("data", "model"))
cube = Hypercube.build(mesh, {"x": 2, "y": 2, "z": 2})
print("cube:", cube.describe())

# 2. bind a communicator to a dim selection: the bitmap "010" selects the y
#    dimension -> four independent AllReduce instances run at once.  The
#    handle caches group size / instance count / ICI-DCN split once.
ar_y = cube.comm("010")
print("comm:", ar_y.describe())

x = jnp.arange(8.0 * 6).reshape(2, 2, 2, 6)
out = jax.jit(shard_map(
    lambda v: ar_y.all_reduce(v), mesh=cube.mesh,
    in_specs=P("x", "y", "z", None), out_specs=P("x", None, "z", None),
    check_vma=False))(x)
print("AllReduce along y (4 instances):", np.asarray(out).shape)

# 3. AlltoAll over the (x, z) plane -- 2 instances of group size 4
#    (the DLRM embedding exchange of paper Fig. 11).
aa_xz = cube.comm(("x", "z"))
out = jax.jit(shard_map(
    lambda v: aa_xz.all_to_all(v, split_axis=3, concat_axis=3),
    mesh=cube.mesh, in_specs=P("x", "y", "z", None),
    out_specs=P("x", "y", "z", None), check_vma=False))(
        jnp.ones((2, 2, 2, 8)))
print("AlltoAll over (x,z):", np.asarray(out).shape)

# 4. algorithm stages (paper Fig. 16 ablation): naive -> pr -> im -> cm;
#    "auto" asks the planner, "pidcomm" takes the strongest Table II stage.
aa_z = cube.comm("001")
for alg in ("naive", "pr", "im", "pidcomm", "auto"):
    out = jax.jit(shard_map(
        lambda v: aa_z.all_to_all(v, split_axis=3, concat_axis=3,
                                  algorithm=alg),
        mesh=cube.mesh, in_specs=P("x", "y", "z", None),
        out_specs=P("x", "y", "z", None), check_vma=False))(
            jnp.ones((2, 2, 2, 8)))
    print(f"  all_to_all[{alg:8s}] ok, shape {np.asarray(out).shape}")

# 5. plan-driven dispatch across pods: on a pod-crossing gradient AllReduce
#    the planner picks the hierarchical §IX-A split (ICI reduce-scatter ->
#    DCN all-reduce of the 1/|ICI| shard -> ICI all-gather), and that is
#    what algorithm="auto" executes.  CommTrace records each dispatch with
#    the chosen flow/stage and the estimated ICI/DCN bytes and seconds.
prod = Hypercube.build(make_mesh((2, 2, 2), ("pod", "data", "model")),
                       {"pod": 2, "dp": 2, "tp": 2})
grad_ar = prod.comm(("pod", "dp"))
est = plan(prod, "all_reduce", ("pod", "dp"), 64 * 2**20)
print(f"plan: {est.algorithm} via {est.schedule}; "
      f"ICI {est.ici_bytes/2**20:.0f} MiB, DCN {est.dcn_bytes/2**20:.0f} MiB,"
      f" est {est.seconds*1e3:.2f} ms")

with CommTrace() as trace:
    g = jnp.ones((2, 2, 2, 64), jnp.float32)
    out = jax.jit(shard_map(
        lambda v: grad_ar.all_reduce(v), mesh=prod.mesh,
        in_specs=P("pod", "dp", "tp", None),
        out_specs=P(None, None, "tp", None), check_vma=False))(g)
for ev in trace.events:
    print(f"traced: {ev.primitive}[{ev.bitmap}] -> {ev.flow} "
          f"(stage {ev.stage}, g={ev.group_size}x{ev.num_instances}inst, "
          f"ICI {ev.ici_bytes:.0f}B, DCN {ev.dcn_bytes:.0f}B, "
          f"est {ev.seconds*1e6:.2f}us)")
assert trace.events and trace.events[0].flow == "hierarchical"
print("auto dispatch executed the planner's hierarchical pick")

# 6. deferred programs (record -> optimize -> execute): composed patterns
#    are recorded as a CommProgram, and lower() optimizes the whole chain --
#    here the reduce_scatter + all_gather pair (the two halves of a gradient
#    sync written out by hand) fuses into ONE all_reduce, which on the
#    pod-crossing group executes the hierarchical split.  CommTrace.summary()
#    shows the provenance: one event, fused from two recorded ops.
with grad_ar.program(name="quickstart-fuse") as prog:
    a = prog.input(jax.ShapeDtypeStruct((1, 1, 1, 64), jnp.float32))
    shard = grad_ar.reduce_scatter(a, axis=3)
    full = grad_ar.all_gather(shard, axis=3)
    prog.output(full)
lowered = prog.lower()
print(lowered.describe())
assert len(lowered.ops) == 1 and lowered.ops[0].fused_from == (0, 1)

with CommTrace() as ptrace:
    out2 = jax.jit(shard_map(
        lambda v: lowered.execute(v), mesh=prod.mesh,
        in_specs=P("pod", "dp", "tp", None),
        out_specs=P("pod", "dp", "tp", None), check_vma=False))(g)
np.testing.assert_array_equal(np.asarray(out2)[0, 0], np.asarray(out)[0, 0])
summary = ptrace.summary()
print("program trace summary:", summary)
assert summary["fused_events"] == 1 and summary["events"] == 1
assert summary["programs"] == ["quickstart-fuse"]
print("record->optimize->execute: rs+ag fused into one hierarchical "
      "all_reduce, bit-identical to the eager result")

# 7. autotuning (measure -> fit -> plan): a Tuner microbenchmarks the
#    registered flows on the live substrate, fits per-(flow, stage, domain)
#    alpha-beta models, and persists them as a fingerprint-keyed
#    CommProfile.  Installing the profile makes algorithm="auto" dispatch
#    on *measured* data -- every CommEvent (and CommTrace.summary()) then
#    carries est_source="measured" instead of the analytic constants.
import tempfile  # noqa: E402

from repro.core import install_profile  # noqa: E402
from repro.tuning import Tuner  # noqa: E402

tuner = Tuner(cache_dir=tempfile.mkdtemp(prefix="repro-tuning-"))
prof = tuner.tune(cube, sizes=(16 * 1024, 64 * 1024),
                  primitives=("all_reduce", "all_to_all"),
                  reps=2, warmup=1)
print("tuned:", prof.describe())
prof = tuner.load(cube)        # reload: fingerprint-checked round-trip

with install_profile(prof), CommTrace() as ttrace:
    out = jax.jit(shard_map(
        lambda v: ar_y.all_reduce(v), mesh=cube.mesh,
        in_specs=P("x", "y", "z", None), out_specs=P("x", None, "z", None),
        check_vma=False))(x)
tuned_summary = ttrace.summary()
print("tuned trace summary:", tuned_summary)
assert ttrace.events[0].est_source == "measured"
assert tuned_summary["est_sources"] == {"measured": 1}
print("auto dispatch priced from the measured CommProfile "
      f"(flow {ttrace.events[0].flow}, "
      f"est {ttrace.events[0].seconds * 1e6:.1f}us measured)")

# 8. overlap-aware program scheduling (measure -> fit -> plan, program
#    level): the tune() above also ran the *overlap sweep* -- pairs of
#    collectives dispatched back-to-back vs alone -- fitting per-domain-pair
#    serialization factors into the profile.  With the profile installed,
#    plan_program prices a multi-op program's interleaving order and its
#    seconds-vs-serial budget from those measurements: the printed plan
#    carries est_source=measured, closing the loop the per-op models left
#    open.  Structurally identical recordings reuse one cached lowered
#    schedule (the serve engine's per-step program rides this cache).
from repro.core.program import LOWER_STATS  # noqa: E402

print("overlap factors:",
      {k: round(m.factor, 3) for k, m in prof.overlap.items()})

def record_pair():
    prog = cube.program(name="quickstart-overlap")
    with prog:
        a = prog.input(jax.ShapeDtypeStruct((1, 1, 1, 64), jnp.float32))
        b = prog.input(jax.ShapeDtypeStruct((1, 1, 1, 64), jnp.float32))
        prog.output(ar_y.all_reduce(a), aa_z.all_gather(b, axis=3))
    return prog

with install_profile(prof):
    lowered_pair = record_pair().lower()
    stats0 = dict(LOWER_STATS)
    record_pair().lower()                   # identical structure: cache hit
print(lowered_pair.describe())
plan = lowered_pair.plan
assert plan.est_source == "measured"
assert plan.seconds <= plan.serial_seconds + 1e-12
assert LOWER_STATS["cache_hits"] > stats0["cache_hits"]
print(f"overlap-aware plan: {plan.seconds*1e6:.1f}us vs serial "
      f"{plan.serial_seconds*1e6:.1f}us (est_source={plan.est_source}); "
      "re-recording reused the cached lowered program")

# 9. production decode serving (repro.serving): a paged/block KV cache --
#     per-shard page pools, a per-request page table, cross-cube page
#     motion as rooted scatter/gather -- under a continuous-batching
#     engine.  One request's lifecycle: it ADMITS from the arrival queue
#     into a free batch lane, PREFILLS through the flash-decode cell
#     (chunk-1 chunked prefill: each step teacher-forces the next prompt
#     token into the paged cache), DECODES with on-device sampling until
#     its length budget is spent, and EVICTS, returning its pages to the
#     pools for the next admission.  Every step's host<->PE control
#     traffic is ONE recorded CommProgram (broadcasts + the lagged sampled
#     gather), so after the first step every lowering is a
#     structural-fingerprint cache hit.
from repro.configs import get  # noqa: E402
from repro.models.params import init_params  # noqa: E402
from repro.models.serving import make_serve_plan  # noqa: E402
from repro.models.topology import build_serve_topology  # noqa: E402
from repro.serving import Request, ServeEngine  # noqa: E402

cfg = get("qwen3-1.7b").scaled_for_smoke()
stopo = build_serve_topology(cfg, make_mesh((1, 1), ("data", "model")))
splan = make_serve_plan(cfg, stopo, S_ctx=24, global_batch=2)
engine = ServeEngine(cfg, stopo, splan, init_params(cfg, stopo, seed=0),
                     page_size=4)
reqs = [Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new=4),
        Request(rid=1, prompt=[2, 7, 1], max_new=6, arrival=2)]
sstats0 = dict(LOWER_STATS)
with CommTrace() as strace:
    serve_metrics = engine.run(reqs)
serve_summary = strace.summary()
print("serving trace summary:", serve_summary)
for r in serve_metrics["finished"]:
    print(f"  request {r.rid}: admitted step {r.admitted_step}, prefill "
          f"{r.plen} toks, decoded {r.out_tokens}, evicted after step "
          f"{r.finished_step}")
assert "serve-step" in serve_summary["programs"]
assert serve_metrics["programs_recorded"] == serve_metrics["steps"]
assert (LOWER_STATS["cache_hits"] - sstats0["cache_hits"]
        >= serve_metrics["steps"] - 1)
print(f"served {len(serve_metrics['finished'])} requests in "
      f"{serve_metrics['steps']} steps at "
      f"{serve_metrics['tokens_per_s']:.0f} tok/s; the per-step program "
      "lowered once and hit the fingerprint cache every step after")

# 10. collective-fused kernels (repro.kernels.collective): ring-rotation
#     flows that weave the collective *through* compute -- ring attention,
#     gather prologues, reduce-scatter epilogues -- registered in the same
#     algorithm registry as the Table II stages, so they trace, price, and
#     race under algorithm="auto".  A measured CommProfile that prices the
#     fused ring cheaper flips both the eager call site and a recorded
#     program's joint plan onto ring_fused; the movement itself is
#     bit-identical (it is the same blocks, interleaved with compute).
from repro.tuning import (CommProfile, LinkModel,  # noqa: E402
                          topology_fingerprint)

fast = LinkModel(alpha=0.0, beta=1e-12, n=8, r2=1.0)
slow = LinkModel(alpha=1.0, beta=1e-6, n=8, r2=1.0)
fused_prof = CommProfile(topology_fingerprint(cube), models={
    "ring_fused/cm/ici": fast, "rs_epilogue/cm/ici": fast,
    "naive/naive/ici": slow, "direct/im/ici": slow, "direct/cm/ici": slow})

ag_z = cube.comm("001")
with ag_z.program(name="quickstart-fused") as fprog:
    a = fprog.input(jax.ShapeDtypeStruct((1, 1, 1, 16), jnp.float32))
    fprog.output(ag_z.all_gather(a, axis=3))

with install_profile(fused_prof):
    flow_lowered = fprog.lower()
    fest = next(iter(flow_lowered.plan.estimates.values()))
    assert fest.algorithm == "ring_fused", fest
    assert fest.est_source == "measured"
    with CommTrace() as ftrace:
        fx = jnp.ones((2, 2, 2, 16), jnp.float32)
        fout = jax.jit(shard_map(
            lambda v: flow_lowered.execute(v), mesh=cube.mesh,
            in_specs=P("x", "y", "z", None),
            out_specs=P("x", "y", None, None), check_vma=False))(fx)
fused_summary = ftrace.summary()
print("fused-kernel trace summary:", fused_summary)
assert [ev.flow for ev in ftrace.events] == ["ring_fused"]
np.testing.assert_array_equal(          # same blocks, same bytes, same bits
    np.asarray(fout),
    np.asarray(jax.jit(shard_map(
        lambda v: ag_z.all_gather(v, axis=3, algorithm="pidcomm"),
        mesh=cube.mesh, in_specs=P("x", "y", "z", None),
        out_specs=P("x", "y", None, None), check_vma=False))(fx)))
print("measured profile steered the recorded program onto the fused ring "
      f"flow (est {fest.seconds * 1e6:.2f}us measured), bit-identical "
      "to the Table II gather")

# 11. unified telemetry (repro.telemetry): one Tracer captures a span
#     timeline across a train step and the serving engine.  While the
#     tracer is active it sits on the comm trace stack, so every live
#     CommEvent becomes an instant inside whatever span is open --
#     carrying flow/stage/est_source/program_id/fused_from provenance --
#     and lower-cache hits annotate the timeline as instant marks.  The
#     metrics registry counts what the narrative above only printed, and
#     a drift monitor catches a synthetically mis-scaled profile: the
#     fused ring's real wall time sits far outside the band around the
#     profile's (absurdly fast) measured estimate, so exactly one
#     structured ProfileStalenessWarning names the stale
#     (flow, stage, domain) and carries the retune recipe.
import json  # noqa: E402
import time  # noqa: E402
import warnings  # noqa: E402

from repro import telemetry  # noqa: E402
from repro.compat import replicated_psum  # noqa: E402


def toy_grads(p, rows):
    # each device's partial loss meets the replicated leaf "w"
    def loss(q):
        return replicated_psum(jnp.sum(rows @ q["w"]), prod.dim_names)
    _, grads = jax.value_and_grad(loss)(p)
    return grads


engine.reset_metrics()                   # warmup boundary: fresh registry
steps_before11 = engine.step_idx         # run() reports cumulative steps
telemetry.enable_metrics()
with telemetry.Tracer() as tracer:
    with tracer.span("train.step", cat="wall"):
        # a toy gradient the way the train step takes it: value_and_grad
        # under shard_map(check_vma=True), whose autodiff sums the
        # replicated leaf's gradient over the cube -- no sync call
        jax.block_until_ready(jax.jit(shard_map(
            toy_grads, mesh=prod.mesh,
            in_specs=({"w": P()}, P(("pod", "dp", "tp"), None)),
            out_specs={"w": P()}, check_vma=True))(
                {"w": jnp.ones((16,))}, jnp.ones((8, 16))))
    req11 = Request(rid=9, prompt=[6, 2, 8, 3], max_new=3,
                    arrival=engine.step_idx)
    serve11 = engine.run([req11])        # serve.step spans + children
telemetry.disable_metrics()

chrome = json.loads(tracer.chrome_trace_json())   # Perfetto-loadable
evs = chrome["traceEvents"]
serve_spans = [e for e in evs if e.get("name") == "serve.step"]
prog_children = [e for e in evs if e.get("cat") == "comm"
                 and e["args"].get("program_id") == "serve-step"]
assert serve_spans, "each engine decode step opens a serve.step span"
assert prog_children, "the step program's ops land as comm instants"
assert all("est_source" in e["args"] and "fused_from" in e["args"]
           for e in prog_children)
assert any(e.get("name") == "program.lower_cache_hit" for e in evs), \
    "warm-cache lowerings annotate the timeline"
snap = telemetry.REGISTRY.snapshot()
steps11 = serve11["steps"] - steps_before11
assert telemetry.REGISTRY.value("comm.dispatches") > 0
assert telemetry.REGISTRY.value("program.lower_cache_hits") >= steps11
assert engine.metrics.value("serve.steps") == steps11
assert serve11["p50_token_s"] == engine.metrics.quantile(
    "serve.token_seconds", 0.50)
print(f"telemetry: {len(serve_spans)} serve.step spans, "
      f"{len(prog_children)} per-op comm instants with provenance, "
      f"{sum(e.get('name') == 'program.lower_cache_hit' for e in evs)} "
      "lower-cache-hit marks; engine registry is the measurement path")

mon = telemetry.DriftMonitor(min_samples=1)     # judge on first residual
t11 = time.perf_counter()
with install_profile(fused_prof):
    jax.block_until_ready(jax.jit(shard_map(
        lambda v: flow_lowered.execute(v), mesh=cube.mesh,
        in_specs=P("x", "y", "z", None),
        out_specs=P("x", "y", None, None), check_vma=False))(fx))
wall11 = time.perf_counter() - t11
with warnings.catch_warnings(record=True) as wlist:
    warnings.simplefilter("always")
    for ev in ftrace.events:     # measured-sourced, priced ~0 by fused_prof
        mon.observe_event(ev, measured_s=wall11)
stale = [w.message for w in wlist
         if isinstance(w.message, telemetry.ProfileStalenessWarning)]
assert len(stale) == 1, "exactly one structured warning per stale key"
sw = stale[0]
assert (sw.flow, sw.stage, sw.domain) == ("ring_fused", "cm", "ici")
assert "Tuner" in sw.recipe or "tune" in sw.recipe.lower()
print(f"drift monitor flagged ({sw.flow}, {sw.stage}, {sw.domain}): "
      f"median meas_over_est={sw.median:.3g} outside "
      f"[{sw.band[0]:g}, {sw.band[1]:g}] -- {sw.recipe}")

# 12. elastic checkpointing (repro.checkpoint): save from the 2x2x2 cube
#     -- one recorded rooted-gather program per section; the second save's
#     structural fingerprint matches the first, so it hits the lower cache
#     -- then restore the same checkpoint onto a 1-D ring of the same 8
#     devices through a rooted-scatter program planned for THAT cube.
#     Same global bits, different placement: the forward pass on the ring
#     is bit-identical.  Every checkpoint collective carries program_id
#     provenance into the trace.
import shutil  # noqa: E402
import tempfile  # noqa: E402

from repro.checkpoint import CheckpointManager, TrainState
from repro.core import program as program_mod  # noqa: E402

wspec = {"w": P("x", ("y", "z")), "b": P(("x", "y"), None)}
host_w = {"w": jnp.arange(64.0, dtype=jnp.float32).reshape(8, 8),
          "b": jnp.arange(32.0, dtype=jnp.float32).reshape(8, 4)}
placed_w = {k: jax.device_put(v, cube.sharding(wspec[k]))
            for k, v in host_w.items()}
ckpt_dir = tempfile.mkdtemp(prefix="quickstart-ckpt-")
saver = CheckpointManager(ckpt_dir, topo=cube, async_save=False,
                          specs={"params": wspec, "opt": None})
hits_before = program_mod.LOWER_STATS["cache_hits"]
saver.save(1, TrainState(params=placed_w))
saver.save(2, TrainState(params=placed_w))
ckpt_cache_hits = program_mod.LOWER_STATS["cache_hits"] - hits_before
assert ckpt_cache_hits >= 1, "second save must reuse the gather lowering"

ring = Hypercube.build(mesh, {"r": 8})          # elastic: different cube
rspec = {"w": P("r", None), "b": P("r", None)}
loader = CheckpointManager(ckpt_dir, topo=ring,
                           specs={"params": rspec, "opt": None})
with CommTrace() as ckpt_trace:
    restored = loader.restore_params(2)
ckpt_summary = ckpt_trace.summary()
assert "ckpt-restore-params" in ckpt_summary["programs"]
assert restored["w"].sharding.spec == P("r", None)

fwd12 = jax.jit(lambda t: t["w"] @ t["b"])
np.testing.assert_array_equal(np.asarray(fwd12(restored)),
                              np.asarray(fwd12(host_w)))
shutil.rmtree(ckpt_dir)
print("elastic restore: saved on {x,y,z}=2x2x2, restored onto {r}=8 via "
      f"a planned scatter program ({ckpt_cache_hits} save lower-cache "
      "hits); ring forward bit-identical to the host reference")

import os  # noqa: E402
if os.environ.get("QUICKSTART_SUMMARY"):
    out_dir = os.path.dirname(os.environ["QUICKSTART_SUMMARY"]) or "."
    with open(os.path.join(out_dir, "quickstart_chrome_trace.json"),
              "w") as f:
        f.write(tracer.chrome_trace_json())
    with open(os.path.join(out_dir, "quickstart_metrics.json"), "w") as f:
        json.dump({"global": snap, "engine": engine.metrics.snapshot(),
                   "drift": mon.summary()}, f, indent=1)
    with open(os.environ["QUICKSTART_SUMMARY"], "w") as f:
        json.dump({"eager": trace.summary(), "program": summary,
                   "tuned": tuned_summary,
                   "overlap_plan": {
                       "seconds": plan.seconds,
                       "serial_seconds": plan.serial_seconds,
                       "est_source": plan.est_source,
                       "order": list(plan.order)},
                   "fused_kernels": {
                       "summary": fused_summary,
                       "flow": ftrace.events[0].flow,
                       "est_source": ftrace.events[0].est_source},
                   "serving": {
                       "summary": serve_summary,
                       "steps": serve_metrics["steps"],
                       "tokens_per_s": serve_metrics["tokens_per_s"],
                       "programs_recorded":
                           serve_metrics["programs_recorded"]},
                   "checkpoint": {
                       "summary": ckpt_summary,
                       "save_lower_cache_hits": ckpt_cache_hits,
                       "restore_programs": ckpt_summary["programs"]},
                   "telemetry": {
                       "serve_step_spans": len(serve_spans),
                       "comm_child_spans": len(prog_children),
                       "lower_cache_hit_marks": sum(
                           e.get("name") == "program.lower_cache_hit"
                           for e in evs),
                       "metrics": {k: snap[k] for k in sorted(snap)},
                       "stale": mon.summary()["stale"]}},
                  f, indent=1)
    print("wrote", os.environ["QUICKSTART_SUMMARY"],
          "quickstart_chrome_trace.json quickstart_metrics.json")
