"""The trace reduction, on a recorded trace of two engine steps on one
v5e chip and on hand-made traces whose numbers are known."""
import json

import pytest

from bench import trace
from bench.harness import BENCH


def recorded():
    d = json.load(open(BENCH / "tests" / "data" / "serve_trace.json"))
    return [(n, {k: [tuple(e) for e in v] for k, v in ls.items()})
            for n, ls in d["planes"]]


def test_recorded_trace():
    r = trace.reduce_planes(recorded())
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.153809316)
    assert r["busy_s"] == pytest.approx(0.132512302)
    assert r["module_s"] == {"jit_step_shard": pytest.approx(0.132521659)}
    assert r["collective_s"] == 0.0
    ops = dict(r["breakdown"]["device_ops"])
    assert "while" not in ops                   # containers not counted
    assert sum(r["op_s"].values()) <= r["window_s"]
    # every idle nanosecond is attributed to some host span
    idle = sum(v for _, v in r["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])


def planes(ops, asyncs=(), spans=()):
    host = [("bench.window", 0, 100)] + list(spans)
    return [("/device:TPU:0", {trace.OPS_LINE: list(ops),
                               trace.ASYNC_LINE: list(asyncs)}),
            ("/host:CPU", {"python3": host})]


def test_async_collective_counts_from_start_to_done():
    ops = [("%all-reduce-start.1 = T op()", 10, 12),
           ("%fusion.3 = T op(), kind=kOutput", 12, 40),
           ("%all-reduce-done.1 = T op()", 50, 55)]
    r = trace.reduce_planes(planes(ops))
    assert r["collective_s"] == pytest.approx(45e-9)      # 10 .. 55
    assert r["busy_s"] == pytest.approx(30e-9 + 5e-9)
    assert r["op_s"]["fusion:kOutput"] == pytest.approx(28e-9)


def test_async_line_events_span_their_collective():
    asyncs = [("%all-gather-start.2 = T op()", 20, 70),
              ("%copy-start.1 = T op()", 0, 90)]
    r = trace.reduce_planes(planes([("%copy.1 = T op()", 0, 5)], asyncs))
    assert r["collective_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(5e-9)    # async copies are not ops


def test_idle_gaps_named_by_innermost_span():
    ops = [("%fusion.1 = T op(), kind=kLoop", 0, 30),
           ("%fusion.2 = T op(), kind=kLoop", 60, 100)]
    spans = [("bench.engine_step", 0, 100), ("bench.wait_arrival", 35, 55)]
    r = trace.reduce_planes(planes(ops, spans=spans))
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps == {"bench.wait_arrival": pytest.approx(20e-9),
                    "bench.engine_step": pytest.approx(10e-9)}


def test_window_clips_everything():
    ops = [("%fusion.1 = T op(), kind=kLoop", -50, 20)]
    r = trace.reduce_planes(planes(ops))
    assert r["busy_s"] == pytest.approx(20e-9)
    assert r["window_s"] == pytest.approx(100e-9)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_planes([("/device:TPU:0", {trace.OPS_LINE: []})])


@pytest.mark.parametrize("text,want", [
    ("%psum.3 = f32[4,8]{1,0:T(8,128)} all-reduce(f32[4,8]{1,0} %x), "
     "channel_id=1", ("all-reduce", None)),
    ("%all_to_all.2 = f32[2,2]{1,0} all-to-all(f32[2,2]{1,0} %y)",
     ("all-to-all", None)),
    ("%ag.1 = (f32[8]{0}, f32[16]{0}) all-gather-start(f32[8]{0} %z)",
     ("all-gather", "-start")),
    ("%reduce_scatter.7 = T op()", ("reduce_scatter", None)),
    ("%fusion.1 = bf16[8]{0:T(128)} fusion(bf16[8] %psum.2), kind=kLoop",
     None),
    ("%copy-start.1 = (f32[2]{0}, u32[]{:S(2)}) copy-start(f32[2] %a)",
     None),
])
def test_collectives_found_by_opcode_or_name(text, want):
    assert trace.is_collective(text) == want


def test_collective_time_sums_all_kinds():
    ops = [("%psum.1 = f32[8]{0} all-reduce(f32[8] %a)", 0, 10),
           ("%all_to_all.1 = f32[8]{0} all-to-all(f32[8] %b)", 20, 30),
           ("%copy.1 = f32[8]{0} copy(f32[8] %c)", 30, 40)]
    r = trace.reduce_planes(planes(ops))
    assert r["collective_s"] == pytest.approx(20e-9)
    assert r["busy_s"] == pytest.approx(30e-9)
