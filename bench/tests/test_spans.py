"""Idle time named by program spans, and device time per collective
scope, on hand-made traces whose numbers are known and on the recorded
trace of two engine steps."""
import math

import pytest

from bench import harness, spans, trace
from bench.tests.test_trace import recorded

OPS, MODS = trace.OPS_LINE, trace.MODULES_LINE


def planes(ops, host, modules=()):
    return [("/device:TPU:0", {OPS: list(ops), MODS: list(modules)}),
            ("/host:CPU", {"python3": [(trace.WINDOW, 0, 100)] + list(host)})]


def test_program_span_inside_harness_span_names_the_gap():
    ops = [("%fusion.1 = T op()", 0, 30), ("%fusion.2 = T op()", 60, 100)]
    host = [("bench.engine_step", 0, 100), ("serve.step", 5, 95),
            ("serve.program", 28, 45), ("serve.wait", 45, 70)]
    r = spans.reduce_planes(planes(ops, host))
    # the gap 30..60 is cut at 45: 30..45 under serve.program, 45..60
    # under serve.wait; serve.step and the harness span cover both
    assert r["idle_by_span"] == {"serve.program": pytest.approx(15e-9),
                                 "serve.wait": pytest.approx(15e-9)}
    assert r["idle_under"]["serve.step"] == pytest.approx(30e-9)
    assert r["idle_under"]["bench.engine_step"] == pytest.approx(30e-9)
    assert r["count"] == {"bench.engine_step": 1, "serve.step": 1,
                          "serve.program": 1, "serve.wait": 1}


def test_gap_no_program_span_covers_keeps_its_harness_name():
    ops = [("%fusion.1 = T op()", 0, 20), ("%fusion.2 = T op()", 80, 100)]
    host = [("bench.engine_step", 0, 100), ("serve.step", 10, 50),
            ("bench.wait_arrival", 60, 75), ("$python frame", 20, 80),
            ("PjitFunction(step)", 20, 80)]
    r = spans.reduce_planes(planes(ops, host))
    assert r["idle_by_span"] == {
        "serve.step": pytest.approx(30e-9),            # 20..50
        "bench.engine_step": pytest.approx(15e-9),     # 50..60, 75..80
        "bench.wait_arrival": pytest.approx(15e-9)}    # 60..75
    assert r["count"].get("PjitFunction(step)") is None   # not a span


def test_idle_outside_every_span_is_named_so():
    r = spans.reduce_planes(planes([("%copy.1 = T op()", 0, 40)], []))
    assert r["idle_by_span"] == {spans.NO_SPAN: pytest.approx(60e-9)}


def test_recorded_trace_splits_the_same_idle_time():
    """The recorded trace has harness spans only: the same idle time,
    under the same names, as bench/trace.py finds."""
    want = trace.reduce_planes(recorded())
    got = spans.reduce_planes(recorded())
    assert got["window_s"] == pytest.approx(want["window_s"])
    gaps = dict(want["breakdown"]["idle_gaps"])
    assert got["idle_by_span"].keys() == gaps.keys()
    for k, v in gaps.items():
        assert got["idle_by_span"][k] == pytest.approx(v)
    assert sum(got["idle_by_span"].values()) == pytest.approx(
        want["window_s"] - want["busy_s"])
    assert got["scopes"] == {}


A_R = "comm.all_reduce.10.im.4194304"
A_G = "comm.all_gather.11.cm.1048576"


def scoped_planes():
    """Two runs of an all-reduce program (12 ns each under its scope: an
    async pair 20..30 and a copy 30..32), one of an all-gather (25 ns)."""
    ops = [("%all-reduce-start.1 = T op()", 20, 21, A_R),
           ("%all-reduce-done.1 = T op()", 28, 30, A_R),
           ("%copy.1 = T op()", 30, 32, A_R),
           ("%fusion.1 = T op()", 32, 35, None),
           ("%all-reduce-start.1 = T op()", 50, 51, A_R),
           ("%all-reduce-done.1 = T op()", 58, 60, A_R),
           ("%copy.1 = T op()", 60, 62, A_R),
           ("%all-gather.3 = T op()", 70, 95, A_G)]
    mods = [("jit_ar(1)", 19, 36), ("jit_ar(1)", 49, 63),
            ("jit_ag(2)", 69, 96)]
    return planes(ops, [], mods)


def test_device_time_per_scope_and_its_runs():
    r = spans.reduce_planes(scoped_planes())
    assert r["scopes"] == {
        A_G: {"seconds": pytest.approx(25e-9), "calls": 1},
        A_R: {"seconds": pytest.approx(24e-9), "calls": 2}}


def test_scope_found_in_an_op_stat():
    stats = {"hlo_op": "psum.3",
             "tf_op": f"jit(f)/shard_map/{A_R}/psum"}
    assert spans.scope_of("%psum.3 = f32[8]{0} all-reduce(...)",
                          stats) == A_R
    assert spans.scope_of("%fusion.1 = T op()", {"hlo_op": "x"}) is None


def cube_config():
    return harness.load_cell("cube2x2.coll-bw").config


def test_plan_ratios_against_the_planner():
    from repro.compat import make_mesh
    from repro.core.comm import scope_estimate
    from repro.core.hypercube import Hypercube
    import jax
    r = spans.reduce_planes(scoped_planes())
    q = spans.plan_ratios(r, cube_config())
    cube = Hypercube.build(make_mesh((2, 2), ("x", "y"),
                                     devices=jax.devices()[:4]),
                           {"x": 2, "y": 2})
    assert q.keys() == {A_R, A_G}
    assert q[A_R] == pytest.approx(
        12e-9 / scope_estimate(cube, A_R).seconds)
    assert q[A_G] == pytest.approx(
        25e-9 / scope_estimate(cube, A_G).seconds)


def test_readers_on_hand_made_traces(monkeypatch):
    ops = [("%fusion.1 = T op()", 0, 30), ("%fusion.2 = T op()", 60, 100)]
    host = [("serve.step", 0, 50), ("serve.program", 20, 40),
            ("serve.step", 50, 100), ("serve.wait", 55, 65)]
    red = spans.reduce_planes(planes(ops, host))
    monkeypatch.setattr(spans, "of_run", lambda rec, scopes=False: red)
    rec = {"trace": {"window_s": 1e-7}}
    # idle 30..60: 20 ns under the first step, 10 under the second
    assert harness.read_metric("engine_idle_ms.serve", rec) == \
        pytest.approx(1e3 * 30e-9 / 2)
    assert harness.read_metric("exchange_idle_ms.serve", rec) == \
        pytest.approx(1e3 * 10e-9 / 2)

    red = spans.reduce_planes(scoped_planes())
    want = [abs(math.log2(v))
            for v in spans.plan_ratios(red, cube_config()).values()]
    rec = {"trace": {"window_s": 1e-7}, "config": cube_config()}
    assert harness.read_metric("plan_error.coll", rec) == \
        pytest.approx(sum(want) / 2)


def test_readers_find_nothing_in_a_program_without_spans(monkeypatch):
    """A trace of a program that opens no spans and names no scopes (the
    harness's spans only) gives no reading, and no error."""
    red = spans.reduce_planes(recorded())
    monkeypatch.setattr(spans, "of_run", lambda rec, scopes=False: red)
    rec = {"trace": {"window_s": 1.0}, "config": cube_config()}
    for name in ("engine_idle_ms.serve", "exchange_idle_ms.serve",
                 "plan_error.coll"):
        assert harness.read_metric(name, rec) is None
    monkeypatch.undo()
    assert spans.of_run({"trace": {}}) is None       # an untraced run


def test_a_traced_run_is_read_from_the_harness_trace_directory(
        tmp_path, monkeypatch):
    """``of_run`` finds the profile the harness left in its directory; a
    real profile of program spans comes back as planes whose host lines
    hold them."""
    import jax
    import repro.compat  # noqa: F401  (the profiler sink)
    from repro.telemetry.spans import maybe_span
    jax.profiler.start_trace(str(tmp_path))
    with maybe_span("serve.step", step=1):
        with maybe_span("serve.wait"):
            pass
    jax.profiler.stop_trace()
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    got = {}
    monkeypatch.setattr(spans, "reduce_planes", lambda planes: got.setdefault(
        "names", {x[0] for _, lines in planes for evs in lines.values()
                  for x in evs}))
    monkeypatch.setattr(spans, "_CACHE", {})
    assert {"serve.step", "serve.wait"} <= spans.of_run({"trace": {"x": 1}})
