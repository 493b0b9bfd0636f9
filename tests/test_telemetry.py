"""Unified telemetry (repro.telemetry): exporter determinism, metric
registry semantics, drift monitoring, and the instrumentation threaded
through program lowering, the trainer and the serving engine.

* Chrome-trace and Prometheus/JSON-lines exports are byte-deterministic
  (monotonic fake clock injected) for a fixed recorded program and a fixed
  serve trace, and round-trip through their own parsers;
* every registered metric name appears in the docs table (meta-test);
* the disabled path writes nothing (default-off contract);
* the drift monitor warns exactly once per stale (flow, stage, domain)
  with the retune recipe and stays quiet in-band; dryrun's byte-underrun
  check shares its band;
* the serving engine's registry is the single measurement path run()
  reports from; trainer telemetry fills step/phase histograms;
* spans reach the profiler only while it records: a real CPU profile of
  engine steps, a train step and a garbage collection holds the catalogue's
  spans, nested; each collective's device ops carry the planner's key.
"""
import gc
import json
import time
import warnings
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.core.comm import CommEvent
from repro.telemetry import drift as drift_mod
from repro.telemetry import spans
from repro.telemetry.metrics import DECLARED
from repro.testing import substrate


class FakeClock:
    """Deterministic monotonic clock: +100us per reading."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1e-4
        return self.t


def _per_shard_aval(cube, payload_shape):
    shape = (1,) * len(cube.dim_sizes) + tuple(payload_shape)
    return jax.ShapeDtypeStruct(shape, jax.numpy.float32)


def _fixed_program(cube):
    """rs+ag pair: lowers to one fused all_reduce with provenance."""
    comm = cube.comm("1")
    with cube.program(name="fixed") as prog:
        a = prog.input(_per_shard_aval(cube, (2, 16)))
        b = comm.reduce_scatter(a, axis=2)
        c = comm.all_gather(b, axis=2)
        prog.output(c)
    return prog


# ------------------------------------------------------ span determinism
def test_chrome_trace_deterministic_for_fixed_program(cube_ring8):
    prog = _fixed_program(cube_ring8)
    prog._lowered_default()            # pre-lower: runs compare hit-free
    x = substrate.integer_payload(cube_ring8, (2, 16), seed=5)
    outs, tracers = [], []
    for _ in range(2):
        with telemetry.Tracer(clock=FakeClock()) as tr:
            with tr.span("step", cat="wall"):
                substrate.run_per_shard(cube_ring8,
                                        lambda v: prog.execute(v), x)
        outs.append(tr.chrome_trace_json())
        tracers.append(tr)
    assert outs[0] == outs[1], "fake-clock export must be byte-identical"

    data = json.loads(outs[0])
    assert "traceEvents" in data       # Perfetto/chrome trace_event format
    comm_evs = [e for e in data["traceEvents"] if e["cat"] == "comm"]
    assert comm_evs, "program execution must ingest CommEvents"
    for e in comm_evs:
        assert {"ph", "ts", "pid", "tid"} <= set(e)
        assert "est_source" in e["args"] and "fused_from" in e["args"]
    # rs+ag fused into one all_reduce: provenance names both recorded ops
    assert any(e["args"]["fused_from"] == [0, 1] for e in comm_evs)
    assert any(e["args"].get("program_id") == "fixed" for e in comm_evs)
    # plain-text timeline carries the same spans for CI logs
    text = tracers[0].timeline()
    assert "step [wall]" in text and "@ comm.all_reduce" in text


def test_chrome_trace_roundtrip(cube_ring8):
    prog = _fixed_program(cube_ring8)
    prog._lowered_default()
    x = substrate.integer_payload(cube_ring8, (2, 16), seed=5)
    with telemetry.Tracer(clock=FakeClock()) as tr:
        substrate.run_per_shard(cube_ring8, lambda v: prog.execute(v), x)
    blob = tr.chrome_trace_json()
    assert json.dumps(json.loads(blob), sort_keys=True, indent=1) == blob


# --------------------------------------------------- metrics determinism
def _lower_fixed_program_twice():
    """A fresh cube + program: lower misses then hits, metrics scoped."""
    cube = substrate.build_cube("ring8")
    with telemetry.scoped_metrics() as reg:
        prog = _fixed_program(cube)
        prog.lower()
        _fixed_program(cube).lower()   # structural twin: cache hit
    return reg


def test_metrics_exports_deterministic_and_roundtrip():
    a = _lower_fixed_program_twice()
    b = _lower_fixed_program_twice()
    assert a.to_prometheus() == b.to_prometheus()
    assert a.to_jsonl() == b.to_jsonl()
    assert a.snapshot() == b.snapshot()
    # the scoped registry saw the lowering instrumentation
    assert a.value("program.lowered") == 1
    assert a.value("program.lower_cache_hits") == 1
    assert a.value("program.fused_ops") == 1
    assert a.value("planner.plan_program_calls") == 1
    # JSON-lines round-trip: parse and re-serialize byte-identically
    lines = a.to_jsonl().splitlines()
    rt = "\n".join(json.dumps(json.loads(ln), sort_keys=True)
                   for ln in lines) + "\n"
    assert rt == a.to_jsonl()
    # Prometheus text: every declared-name line is prefixed and typed
    prom = a.to_prometheus()
    assert "# TYPE repro_program_lowered counter" in prom
    assert "repro_program_lowered 1" in prom


def test_metrics_disabled_path_writes_nothing():
    assert not telemetry.metrics_enabled()
    telemetry.inc("train.steps")
    telemetry.observe("train.step_seconds", 0.5)
    telemetry.set_gauge("serve.tokens_per_s", 1.0)
    assert telemetry.REGISTRY.snapshot() == {}
    cube = substrate.build_cube("ring8")
    _fixed_program(cube).lower()       # instrumented sites stay silent
    assert telemetry.REGISTRY.snapshot() == {}


def test_declared_kind_is_enforced():
    reg = telemetry.MetricsRegistry()
    with pytest.raises(TypeError, match="declared as counter"):
        reg.gauge("train.steps")
    reg.counter("train.steps").inc()
    with pytest.raises(TypeError, match="is a counter"):
        reg.histogram("train.steps")


def test_histogram_quantile_matches_sorted_index_formula():
    reg = telemetry.MetricsRegistry()
    h = reg.histogram("serve.token_seconds")
    vals = [0.003, 0.001, 0.009, 0.002, 0.004]
    for v in vals:
        h.observe(v)
    lat = np.sort(np.asarray(vals))
    n = len(vals)
    for q in (0.5, 0.9, 0.99, 1.0):
        want = float(lat[min(n - 1, int(np.ceil(q * n)) - 1)])
        assert h.quantile(q) == want


# ------------------------------------------------------------- meta-test
def test_every_declared_metric_is_documented():
    doc = (Path(__file__).parent.parent / "docs" /
           "TELEMETRY.md").read_text()
    missing = [name for name in DECLARED if f"`{name}`" not in doc]
    assert not missing, f"docs/TELEMETRY.md missing metrics: {missing}"


# ----------------------------------------------------------------- drift
def _event(**kw):
    base = dict(primitive="all_reduce", bitmap="1", dims=("a",),
                algorithm="auto", flow="ring_fused", stage="cm",
                group_size=8, num_instances=1, payload_bytes=1024.0,
                ici_bytes=1024.0, dcn_bytes=0.0, seconds=1e-4,
                est_source="measured")
    base.update(kw)
    return CommEvent(**base)


def test_drift_monitor_warns_exactly_once_per_key():
    mon = telemetry.DriftMonitor(min_samples=2, require_measured=False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for _ in range(6):                       # meas 100x over estimate
            mon.observe("ring_fused", "cm", "ici", 1e-2, 1e-4)
    ws = [x for x in w
          if issubclass(x.category, telemetry.ProfileStalenessWarning)]
    assert len(ws) == 1, "one structured warning per stale key"
    msg = str(ws[0].message)
    assert "ring_fused" in msg and "cm" in msg and "ici" in msg
    assert "Tuner" in msg or "regenerate" in msg     # retune recipe
    warning = ws[0].message
    assert (warning.flow, warning.stage, warning.domain) == \
        ("ring_fused", "cm", "ici")
    assert mon.stale() == [("ring_fused", "cm", "ici")]
    assert mon.summary()["stale"] == ["ring_fused/cm/ici"]


def test_drift_monitor_quiet_in_band():
    mon = telemetry.DriftMonitor(min_samples=2)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for r in (0.8, 1.0, 1.2, 1.5, 0.6):
            mon.observe("ring_fused", "cm", "ici", r * 1e-4, 1e-4)
    assert not [x for x in w if issubclass(
        x.category, telemetry.ProfileStalenessWarning)]
    assert mon.stale() == []


def test_drift_monitor_skips_analytic_estimates_by_default():
    mon = telemetry.DriftMonitor(min_samples=1)
    mon.observe_event(_event(est_source="analytic"), measured_s=1.0)
    assert mon.residuals == {}
    mon.observe_event(_event(est_source="measured"), measured_s=1.2e-4)
    assert list(mon.residuals) == [("ring_fused", "cm", "ici")]


def test_dryrun_underrun_check_shares_drift_band():
    lo, hi = drift_mod.DEFAULT_BAND
    assert drift_mod.underrun(lo - 1e-9) and not drift_mod.underrun(lo)
    assert drift_mod.outside_band(hi + 1e-9)
    assert not drift_mod.outside_band(1.0)


# -------------------------------------------------------- serving engine
def _setup_engine(B, *, tp=1, **eng_kw):
    import dataclasses as _dc
    from repro.configs import get
    from repro.launch.mesh import make_mesh
    from repro.models.params import init_params
    from repro.models.serving import make_serve_plan
    from repro.models.topology import build_serve_topology
    from repro.serving import ServeEngine
    substrate.ensure_virtual_devices(8)
    cfg = get("qwen3-1.7b").scaled_for_smoke()
    if tp > 1:
        cfg = _dc.replace(cfg, tp=tp)
    mesh = make_mesh((1, tp), ("data", "model"))
    topo = build_serve_topology(cfg, mesh)
    plan = make_serve_plan(cfg, topo, S_ctx=32, global_batch=B)
    params = init_params(cfg, topo, seed=1)
    return cfg, ServeEngine(cfg, topo, plan, params, **eng_kw)


def _serve_trace(cfg, n, seed=3):
    from repro.serving import poisson_trace
    return poisson_trace(n, rate=1.0, plen_range=(3, 6),
                         max_new_range=(2, 4), vocab=cfg.vocab_size,
                         seed=seed)


def test_engine_registry_is_the_single_measurement_path():
    cfg, eng = _setup_engine(2)
    m = eng.run(_serve_trace(cfg, 3))
    reg = eng.metrics
    assert reg.value("serve.steps") == m["steps"]
    assert reg.value("serve.generated_tokens") == m["generated_tokens"]
    assert m["p50_token_s"] == reg.quantile("serve.token_seconds", 0.50)
    assert m["p99_token_s"] == reg.quantile("serve.token_seconds", 0.99)
    assert m["tokens_per_s"] == reg.value("serve.tokens_per_s")
    assert reg.value("serve.admitted") == 3
    assert reg.value("serve.evicted") == len(m["finished"]) == 3
    assert reg.value("serve.preempted") == m["preemptions"] == 0
    assert 0.0 <= reg.value("serve.page_occupancy") <= 1.0
    # per-step program: one miss then hits -> ratio approaches 1
    assert reg.value("serve.lower_cache_hit_ratio") == pytest.approx(
        (m["steps"] - 1) / m["steps"])
    assert "repro_serve_steps" in reg.to_prometheus()
    eng.reset_metrics()
    assert reg.snapshot() == {} and eng.programs_recorded == 0


def test_engine_serve_trace_chrome_deterministic():
    blobs = []
    for _ in range(2):
        cfg, eng = _setup_engine(2)      # fresh cube: fresh lower cache
        with telemetry.Tracer(clock=FakeClock()) as tr:
            eng.run(_serve_trace(cfg, 2))
        blobs.append(tr.chrome_trace_json())
    assert blobs[0] == blobs[1]
    evs = json.loads(blobs[0])["traceEvents"]
    steps = [e for e in evs if e["name"] == "serve.step"]
    assert steps, "each engine step must open a serve.step span"
    comm = [e for e in evs if e["cat"] == "comm"]
    assert comm and all("est_source" in e["args"] for e in comm)
    assert any(e["args"].get("program_id") == "serve-step" for e in comm)
    # lower-cache hits annotate the timeline from step 2 on
    hits = [e for e in evs if e["name"] == "program.lower_cache_hit"]
    assert hits and all(e["ph"] == "i" for e in hits)


def test_queue_seconds_counts_each_request_once():
    # tight pools under lazy admission preempt: a re-admitted request has
    # waited once already and is not counted again
    cfg, eng = _setup_engine(3, tp=2, pages_per_shard=4, admission="lazy")
    from repro.serving import poisson_trace
    reqs = poisson_trace(6, rate=1.0, plen_range=(3, 8),
                         max_new_range=(3, 6), vocab=cfg.vocab_size, seed=3)
    m = eng.run(reqs)
    reg = eng.metrics
    assert m["preemptions"] > 0, "pools sized to force preemption"
    assert reg.value("serve.admitted") == 6 + m["preemptions"]
    h = reg.histogram("serve.queue_seconds")
    assert h.count == len(m["finished"]) == 6
    assert min(h.samples) >= 0.0


def test_queue_seconds_runs_from_submit_to_admission():
    cfg, eng = _setup_engine(1)
    from repro.serving import Request
    first = Request(rid=0, prompt=[1, 2], max_new=2)
    second = Request(rid=1, prompt=[3, 4], max_new=2)
    eng.submit(first)
    eng.submit(second)          # one lane: waits for the first to finish
    t_submit = time.perf_counter()
    while eng.queue or eng.active_h.any():
        eng.step()
        if second.admitted_step < 0:
            t_before = time.perf_counter()
    waits = sorted(eng.metrics.histogram("serve.queue_seconds").samples)
    assert len(waits) == 2
    assert waits[1] >= t_before - t_submit > waits[0]


# ---------------------------------------------------------------- trainer
def _setup_train(**tc_kw):
    from repro.configs import get
    from repro.launch.mesh import make_mesh
    from repro.models.topology import build_topology
    from repro.optim import adamw
    from repro.models.params import init_params
    from repro.runtime.trainer import TrainConfig
    cfg = get("qwen3-1.7b").scaled_for_smoke()
    mesh = make_mesh((1, 1), ("data", "model"))
    topo = build_topology(cfg, mesh)
    tc = TrainConfig(warmup=2, lr=1e-3, **tc_kw)
    params = init_params(cfg, topo, seed=0)
    opt = adamw.init_state(params, tc.adamw)
    return cfg, topo, tc, params, opt


def _batches(cfg, n):
    import jax.numpy as jnp
    from repro.data.pipeline import DataConfig, TokenStream
    dc = DataConfig(seq_len=32, global_batch=2, vocab_size=cfg.vocab_size)
    stream = TokenStream(cfg, dc)
    for s in range(n):
        yield {k: jnp.asarray(v)
               for k, v in stream.global_batch_at(s).items()}


def test_trainer_step_metrics_and_span():
    from repro.runtime.trainer import Trainer
    cfg, topo, tc, params, opt = _setup_train()
    tr = Trainer(cfg, topo, tc)
    telemetry.enable_metrics()
    try:
        with telemetry.Tracer(clock=FakeClock()) as tracer:
            _, _, hist = tr.run(params, opt, _batches(cfg, 2),
                                log_every=0, log=lambda *_: None)
    finally:
        telemetry.disable_metrics()
    assert telemetry.REGISTRY.value("train.steps") == 2
    assert telemetry.REGISTRY.get("train.step_seconds").count == 2
    evs = json.loads(tracer.chrome_trace_json())["traceEvents"]
    assert sum(e["name"] == "train.step" for e in evs) == 2
    assert np.isfinite(hist[-1]["loss"])


def test_trainer_telemetry_split_phases():
    from repro.runtime.trainer import Trainer
    cfg, topo, tc, params, opt = _setup_train(telemetry_split=True)
    tr = Trainer(cfg, topo, tc)
    telemetry.enable_metrics()
    try:
        _, _, hist = tr.run(params, opt, _batches(cfg, 2),
                            log_every=0, log=lambda *_: None)
    finally:
        telemetry.disable_metrics()
    reg = telemetry.REGISTRY
    for name in ("train.fwd_seconds", "train.fwd_bwd_seconds",
                 "train.opt_seconds"):
        assert reg.get(name).count == 2, name
    # phase metrics still produce a full history row
    assert np.isfinite(hist[-1]["loss"])
    assert np.isfinite(hist[-1]["grad_norm"])


# ------------------------------------------------------ profiler sink
class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enter/exit."""
    log: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, args

    def __enter__(self):
        self.log.append(("enter", self.name, self.args))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def _program_entries(log):
    # a collection may start at any allocation; its host.gc span is not
    # this test's
    return [e for e in log if e[1] != "host.gc"]


def test_maybe_span_calls_profiler_sink_only_while_recording(monkeypatch):
    recording = [False]
    monkeypatch.setattr(spans, "_recording", lambda: recording[0])
    monkeypatch.setattr(spans, "_annotation", _FakeAnnotation)
    monkeypatch.setattr(_FakeAnnotation, "log", [])
    with spans.maybe_span("serve.step", step=3) as h:
        assert h is None
    assert _program_entries(_FakeAnnotation.log) == []
    # the off path hands out one shared no-op
    assert spans.maybe_span("a", step=1) is spans.maybe_span("b")

    recording[0] = True
    with spans.maybe_span("serve.step", step=3) as h:
        with spans.maybe_span("serve.wait"):
            pass
    assert h is None                   # no tracer: nothing to hand back
    assert _program_entries(_FakeAnnotation.log) == [
        ("enter", "serve.step", {"step": 3}), ("enter", "serve.wait", {}),
        ("exit", "serve.wait"), ("exit", "serve.step")]

    # both sinks at once: the tracer keeps its handle and category
    _FakeAnnotation.log.clear()
    with telemetry.Tracer(clock=FakeClock()) as tr:
        with spans.maybe_span("program.lower", cat="trace", ops=2) as h:
            pass
    assert h.name == "program.lower" and h.cat == "trace"
    assert [sp.name for sp in tr.finished()] == ["program.lower"]
    assert _program_entries(_FakeAnnotation.log) == [
        ("enter", "program.lower", {"ops": 2}), ("exit", "program.lower")]

    recording[0] = False
    _FakeAnnotation.log.clear()
    with telemetry.Tracer(clock=FakeClock()) as tr:
        with spans.maybe_span("train.step", step=0):
            pass
    assert _program_entries(_FakeAnnotation.log) == []
    assert [sp.name for sp in tr.finished()] == ["train.step"]


def _profiled(tmp_path, fn):
    """Run ``fn`` under a real jax.profiler trace; returns the host
    planes' events as (name, start ns, end ns, stats)."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    pb = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in ProfileData.from_file(str(pb)).planes
            if p.name.startswith("/host") for ln in p.lines
            for e in ln.events]


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


SERVE_PHASES = ("serve.schedule", "serve.program", "serve.decode",
                "serve.wait", "serve.mirror")


def test_engine_spans_on_the_profiler_trace(tmp_path):
    cfg, eng = _setup_engine(2)
    eng.run(_serve_trace(cfg, 2))        # compiles outside the profile
    from repro.serving import Request
    eng.submit(Request(rid=7, prompt=[1, 2, 3], max_new=4,
                       arrival=eng.step_idx))
    first = eng.step_idx
    evs = _profiled(tmp_path, lambda: (eng.step(), eng.step()))
    steps = sorted(e for e in evs if e[0] == "serve.step")
    assert [e[3]["step"] for e in steps] == [first, first + 1]
    for step in steps:
        kids = sorted((e for e in evs if e[0] in SERVE_PHASES
                       and _inside(e, step)), key=lambda e: e[1])
        assert [k[0] for k in kids] == list(SERVE_PHASES)
        # one after another, none overlapping
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    assert sum(e[0] in SERVE_PHASES for e in evs) == 2 * len(SERVE_PHASES)


def test_trainer_spans_on_the_profiler_trace(tmp_path):
    from repro.runtime.trainer import Trainer
    cfg, topo, tc, params, opt = _setup_train()
    tr = Trainer(cfg, topo, tc)
    batches = list(_batches(cfg, 2))
    params, opt, _ = tr.run(params, opt, batches[:1], log_every=0,
                            log=lambda *_: None)       # compile
    evs = _profiled(tmp_path, lambda: tr.run(
        params, opt, batches[1:], start_step=1, log_every=0,
        log=lambda *_: None))
    (step,) = [e for e in evs if e[0] == "train.step"]
    assert step[3] == {"step": 1}
    kids = sorted((e for e in evs if e[0].startswith("train.")
                   and e is not step), key=lambda e: e[1])
    assert [k[0] for k in kids] == ["train.dispatch", "train.wait",
                                    "train.fetch"]
    assert all(_inside(k, step) for k in kids)


def test_gc_is_a_span_on_the_profiler_trace(tmp_path):
    evs = _profiled(tmp_path, lambda: gc.collect())
    gcs = [e for e in evs if e[0] == "host.gc"]
    assert gcs and any(e[3].get("generation") == 2 for e in gcs)
    assert all(e[2] > e[1] for e in gcs)


def test_dispatch_scope_carries_the_planner_key(cube_2x4):
    from repro.compat import shard_map
    from repro.core.comm import CommTrace, scope_estimate, scope_name
    x = substrate.integer_payload(cube_2x4, (4, 16), seed=1)
    comm = cube_2x4.comm("01")
    spec = substrate.global_spec(cube_2x4, 2)
    f = jax.jit(shard_map(lambda v: comm.all_reduce(v), mesh=cube_2x4.mesh,
                          in_specs=spec, out_specs=spec, check_vma=False))
    with CommTrace() as ct:
        hlo = f.lower(x).compile().as_text()
    (ev,) = ct.events
    key = scope_name("all_reduce", "01", ev.flow, ev.payload_bytes)
    assert key == f"comm.all_reduce.01.{ev.flow}.{4 * 16 * 4}"
    (line,) = [ln for ln in hlo.splitlines() if "all-reduce(" in ln]
    assert f'op_name="jit(<lambda>)/shard_map/{key}/' in line
    assert f'comm_scope="{key}"' in line
    assert scope_estimate(cube_2x4, key).seconds == ev.seconds


def test_tracer_keeps_comm_events_as_instants(cube_ring8):
    prog = _fixed_program(cube_ring8)
    prog._lowered_default()
    x = substrate.integer_payload(cube_ring8, (2, 16), seed=5)
    with telemetry.Tracer(clock=FakeClock()) as tr:
        with tr.span("step", cat="wall"):
            substrate.run_per_shard(cube_ring8, lambda v: prog.execute(v), x)
    evs = json.loads(tr.chrome_trace_json())["traceEvents"]
    comm = [e for e in evs if e["cat"] == "comm"]
    assert comm and all(e["ph"] == "i" and "dur" not in e for e in comm)
    assert all(e["name"] == f"comm.{e['args']['primitive']}" for e in comm)
    assert all(e["args"]["est_seconds"] == ev.seconds
               for e, ev in zip(comm, tr.comm_events))
