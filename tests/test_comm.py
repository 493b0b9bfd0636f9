"""Tests for the communicator-centric API redesign (repro.core.comm):

* registry completeness against paper Table II (derived, not hand-kept);
* plan-driven dispatch -- ``algorithm="auto"`` executes ``planner.plan()``'s
  pick for every primitive, with the pod-crossing all-reduce lowering to the
  hierarchical §IX-A schedule (HLO assertion);
* CommTrace event accounting;
* the deprecated ``Collectives`` shim is bit-identical to a bound
  ``Communicator`` on conformance cells;
* the §V-C compressed registry algorithm end-to-end (value + custom_vjp
  boundary + trainer gradient-sync flag).
"""
import numpy as np
import pytest

from repro.core import planner
from repro.core.collectives import APPLICABILITY, Collectives
from repro.core.comm import (
    CommTrace, Communicator, applicability, get_algorithm,
    register_algorithm, registered_algorithms, resolve_stage)
from repro.testing import oracles, substrate

# Paper Table II, spelled out -- the registry must reproduce it exactly.
TABLE_II = {
    "all_to_all": ("naive", "pr", "im", "cm"),
    "reduce_scatter": ("naive", "pr", "im"),
    "all_reduce": ("naive", "pr", "im"),
    "all_gather": ("naive", "pr", "im", "cm"),
    "scatter": ("naive", "im"),
    "gather": ("naive", "im"),
    "reduce": ("naive", "pr", "im"),
    "broadcast": ("naive",),
}


# ------------------------------------------------------------- the registry
def test_registry_reproduces_table_ii():
    assert applicability() == TABLE_II
    # the legacy constant is the derived table, not a divergent copy
    assert APPLICABILITY == TABLE_II


def test_first_class_algorithms_registered():
    extras = {"hierarchical", "compressed", "ring", "tree"}
    assert extras <= set(registered_algorithms("all_reduce"))
    # extras must not widen the Table II applicability ladder
    for name in extras:
        assert not get_algorithm("all_reduce", name).table_ii
    # every Table II cell resolves to a registered body
    for prim, stages in TABLE_II.items():
        for st in stages:
            assert get_algorithm(prim, st).stage == st


def test_register_rejects_duplicates_and_unknowns():
    with pytest.raises(ValueError, match="already registered"):
        register_algorithm("all_reduce", "im")(lambda comm, x, *, op: x)
    with pytest.raises(ValueError, match="unknown primitive"):
        register_algorithm("warp_gate", "im")(lambda comm, x: x)
    with pytest.raises(ValueError, match="needs an explicit stage"):
        register_algorithm("all_reduce", "fancy")(lambda comm, x, *, op: x)
    with pytest.raises(ValueError, match="no algorithm"):
        get_algorithm("all_reduce", "warp")


def test_communicator_binding(cube_2x2x2):
    c = cube_2x2x2.comm("110")
    assert c.dims == ("a", "b")
    assert c.bitmap == "110"
    assert c.group_size == 4 and c.num_instances == 2
    assert c.fast_dims == ("a", "b") and c.slow_dims == ()
    with pytest.raises(ValueError, match="unknown algorithm"):
        c.all_reduce(np.ones(4, np.float32), algorithm="warp")


def test_pod_communicator_caches_fast_slow_split(cube_pod):
    c = cube_pod.comm(("pod", "dp"))
    assert c.crosses_dcn
    assert c.fast_dims == ("dp",) and c.slow_dims == ("pod",)


# ------------------------------------------------------ plan-driven dispatch
def _expected_flow(cube, primitive, dims, payload_bytes, op="add"):
    """The registry flow 'auto' must execute, per the planner contract."""
    est = planner.plan(cube, primitive, dims, payload_bytes)
    if est.algorithm == "naive":
        return "naive"
    if est.algorithm == "hierarchical" and primitive == "all_reduce" \
            and op == "add":
        return "hierarchical"
    return resolve_stage(primitive, "pidcomm")


@pytest.mark.parametrize("primitive", ["all_reduce", "reduce_scatter",
                                       "all_gather", "all_to_all"])
def test_auto_dispatches_planner_choice(cube_pod, primitive):
    """Every PE<->PE primitive with algorithm="auto" executes the planner's
    pick on a pod-crossing group, and the result matches the oracle."""
    comm = cube_pod.comm(("pod", "dp"))
    g = comm.group_size
    x = substrate.integer_payload(cube_pod, (2, 4 * g), seed=g)
    fns = {
        "all_reduce": lambda v: comm.all_reduce(v),
        "reduce_scatter": lambda v: comm.reduce_scatter(v, axis=4),
        "all_gather": lambda v: comm.all_gather(v, axis=3),
        "all_to_all": lambda v: comm.all_to_all(v, split_axis=4,
                                                concat_axis=4),
    }
    wants = {
        "all_reduce": lambda: oracles.all_reduce(x, 3, (0, 1)),
        "reduce_scatter": lambda: oracles.reduce_scatter(x, 3, (0, 1),
                                                         axis=1),
        "all_gather": lambda: oracles.all_gather(x, 3, (0, 1), axis=0),
        "all_to_all": lambda: oracles.all_to_all(x, 3, (0, 1), split_axis=1,
                                                 concat_axis=1),
    }
    with CommTrace() as tr:
        got = substrate.run_per_shard(cube_pod, fns[primitive], x)
    np.testing.assert_array_equal(got, wants[primitive]())
    ev = [e for e in tr.events if e.primitive == primitive]
    assert len(ev) == 1
    payload = x[0, 0, 0].size * x.dtype.itemsize
    assert ev[0].flow == _expected_flow(cube_pod, primitive, ("pod", "dp"),
                                        payload)
    assert ev[0].algorithm == "auto"


def test_auto_rooted_primitives_dispatch_and_trace(cube_2x2x2):
    comm = cube_2x2x2.comm("111")
    host = np.arange(16 * 3, dtype=np.float32).reshape(16, 3)
    with CommTrace() as tr:
        dev = comm.scatter(host, axis=0)
        rep = comm.broadcast(host)
        back = comm.gather(dev)
        red = comm.reduce(dev, op="add", axis=0)
    np.testing.assert_array_equal(back, host)
    np.testing.assert_array_equal(np.asarray(red), host.sum(0))
    got = substrate.local_blocks(cube_2x2x2, dev)
    np.testing.assert_array_equal(
        got, oracles.scatter(host, cube_2x2x2.dim_sizes, (0, 1, 2), axis=0))
    assert [e.primitive for e in tr.events] == [
        "scatter", "broadcast", "gather", "reduce"]
    assert all(e.algorithm == "auto" for e in tr.events)


def test_auto_nonadditive_pod_all_reduce_event_matches_executed_flow(
        cube_pod):
    """op="max" cannot take the hierarchical split: auto must execute the
    direct flow AND the recorded event must carry the direct estimate (the
    op-blind planner pick's hierarchical numbers would understate DCN
    bytes by |ICI|x)."""
    comm = cube_pod.comm(("pod", "dp"))
    x = substrate.integer_payload(cube_pod, (64,), seed=6)
    with CommTrace() as tr:
        got = substrate.run_per_shard(
            cube_pod, lambda v: comm.all_reduce(v, op="max"), x)
    np.testing.assert_array_equal(got, oracles.all_reduce(x, 3, (0, 1),
                                                          op="max"))
    ev = tr.events[0]
    assert ev.flow == "im"
    direct = planner.estimate(cube_pod, "all_reduce", ("pod", "dp"), 64 * 4,
                              algorithm="direct")
    assert ev.dcn_bytes == direct.dcn_bytes
    assert ev.seconds == direct.seconds


def test_auto_pod_crossing_all_reduce_is_hierarchical_hlo(cube_pod):
    """Acceptance: the planner picks hierarchical for the pod-crossing
    all-reduce and 'auto' lowers the §IX-A reduce-scatter/all-reduce/
    all-gather schedule."""
    est = planner.plan(cube_pod, "all_reduce", ("pod", "dp"), 4 * 4096)
    assert est.algorithm == "hierarchical"
    comm = cube_pod.comm(("pod", "dp"))
    x = substrate.integer_payload(cube_pod, (4096,), seed=3)
    hlo = substrate.lowered_text(cube_pod, lambda v: comm.all_reduce(v), x)
    assert "reduce-scatter" in hlo or "reduce_scatter" in hlo
    assert "all-gather" in hlo or "all_gather" in hlo
    # intra-pod group: auto lowers the direct psum, not the split
    intra = cube_pod.comm(("dp",))
    with CommTrace() as tr:
        got = substrate.run_per_shard(cube_pod,
                                      lambda v: intra.all_reduce(v), x)
    np.testing.assert_array_equal(got, oracles.all_reduce(x, 3, (1,)))
    assert tr.events[0].flow == "im"


# ----------------------------------------------------------- trace accounting
def test_commtrace_event_accounting(cube_pod):
    comm = cube_pod.comm(("pod", "dp"))
    x = substrate.integer_payload(cube_pod, (64,), seed=5)
    payload = 64 * 4
    with CommTrace() as outer:
        with CommTrace() as inner:
            substrate.run_per_shard(cube_pod, lambda v: comm.all_reduce(v), x)
        substrate.run_per_shard(
            cube_pod, lambda v: comm.all_gather(v, axis=3), x)
    # nested traces both observe the dispatch inside their window
    assert len(inner.events) == 1 and len(outer.events) == 2
    ar, ag = outer.events
    assert (ar.primitive, ar.flow, ar.stage) == ("all_reduce",
                                                 "hierarchical", "im")
    assert ar.bitmap == "110" and ar.dims == ("pod", "dp")
    assert ar.group_size == 4 and ar.num_instances == 2
    assert ar.payload_bytes == payload
    assert ar.dcn_bytes > 0 and ar.ici_bytes > 0 and ar.seconds > 0
    # the hierarchical DCN hop carries the 1/|ICI| shard, cheaper than the
    # flat collective's
    flat = planner.estimate(cube_pod, "all_reduce", ("pod", "dp"), payload,
                            algorithm="direct")
    assert ar.dcn_bytes < flat.dcn_bytes
    assert ag.primitive == "all_gather" and ag.payload_bytes == payload
    s = outer.summary()
    assert s["events"] == 2
    assert s["by_flow"]["all_reduce/hierarchical"]["count"] == 1
    assert s["ici_bytes"] == pytest.approx(ar.ici_bytes + ag.ici_bytes)
    # no active trace -> no recording, dispatch unaffected
    substrate.run_per_shard(cube_pod, lambda v: comm.all_reduce(v), x)
    assert len(outer.events) == 2


# ------------------------------------------------------- shim differential
SHIM_CELLS = [
    ("cube_ring8", "1", "all_reduce", "pidcomm"),
    ("cube_2x2x2", "011", "all_to_all", "im"),
    ("cube_2x4", "01", "reduce_scatter", "pr"),
]


@pytest.mark.parametrize("cube_name,bitmap,primitive,stage", SHIM_CELLS)
def test_shim_equals_communicator(cube_name, bitmap, primitive, stage,
                                  request):
    """Collectives (deprecated shim) and Communicator produce bit-identical
    results on conformance cells -- same registry bodies underneath."""
    cube = request.getfixturevalue(cube_name)
    names = cube.dims_from_bitmap(bitmap)
    idx = tuple(cube.dim_names.index(d) for d in names)
    with pytest.warns(DeprecationWarning, match="cube.comm"):
        col = Collectives(cube)
    comm = cube.comm(bitmap)
    nd = len(cube.dim_sizes)
    g = cube.group_size(names)
    x = substrate.integer_payload(cube, (2, 4 * g), seed=g)
    if primitive == "all_reduce":
        via_col = substrate.run_per_shard(
            cube, lambda v: col.all_reduce(v, names, algorithm=stage), x)
        via_comm = substrate.run_per_shard(
            cube, lambda v: comm.all_reduce(v, algorithm=stage), x)
        want = oracles.all_reduce(x, nd, idx)
    elif primitive == "all_to_all":
        via_col = substrate.run_per_shard(
            cube, lambda v: col.all_to_all(v, names, split_axis=nd + 1,
                                           concat_axis=nd + 1,
                                           algorithm=stage), x)
        via_comm = substrate.run_per_shard(
            cube, lambda v: comm.all_to_all(v, split_axis=nd + 1,
                                            concat_axis=nd + 1,
                                            algorithm=stage), x)
        want = oracles.all_to_all(x, nd, idx, split_axis=1, concat_axis=1)
    else:
        via_col = substrate.run_per_shard(
            cube, lambda v: col.reduce_scatter(v, names, axis=nd + 1,
                                               algorithm=stage), x)
        via_comm = substrate.run_per_shard(
            cube, lambda v: comm.reduce_scatter(v, axis=nd + 1,
                                                algorithm=stage), x)
        want = oracles.reduce_scatter(x, nd, idx, axis=1)
    np.testing.assert_array_equal(via_col, via_comm)  # bit-identical
    np.testing.assert_array_equal(via_comm, want)


# ------------------------------------------------------ compressed algorithm
def test_compressed_all_reduce_value_and_planner(cube_pod):
    comm = cube_pod.comm(("pod", "dp"))
    x = substrate.integer_payload(cube_pod, (512,), seed=11)
    with CommTrace() as tr:
        got = substrate.run_per_shard(
            cube_pod, lambda v: comm.all_reduce(v, algorithm="compressed"), x)
    want = oracles.all_reduce(x, 3, (0, 1))
    # int8 DCN hop: lossy but blockwise-absmax tight on small-int payloads
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=0.5)
    ev = tr.events[0]
    assert (ev.flow, ev.stage) == ("compressed", "cm")
    hier = planner.estimate(cube_pod, "all_reduce", ("pod", "dp"), 512 * 4,
                            algorithm="pidcomm")
    assert ev.dcn_bytes < hier.dcn_bytes  # 8-bit wire vs fp32 wire
    # opt-in planner candidate
    p = planner.plan(cube_pod, "all_reduce", ("pod", "dp"), 512 * 4,
                     allow_compressed=True)
    assert p.algorithm == "compressed" and p.stage == "cm"
    p0 = planner.plan(cube_pod, "all_reduce", ("pod", "dp"), 512 * 4)
    assert p0.algorithm == "hierarchical"


def test_compressed_all_reduce_custom_vjp_boundary(cube_pod):
    """Gradients flow through the compressed collective (straight-through
    quantizer): d/dx sum(compressed_AR(x)) stays finite and matches the
    uncompressed all-reduce cotangent within quantization tolerance."""
    import jax
    import jax.numpy as jnp
    comm = cube_pod.comm(("pod", "dp"))
    x = substrate.integer_payload(cube_pod, (512,), seed=13)

    def per_shard(v):
        def f(u):
            return jnp.sum(comm.all_reduce(u, algorithm="compressed"))
        return jax.grad(f)(v)

    got = substrate.run_per_shard(cube_pod, per_shard, x)
    # uncompressed convention: grad of sum(psum(x)) per shard is g * ones
    want = np.ones_like(x) * cube_pod.comm(("pod", "dp")).group_size
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=0.5)


def test_compressed_requires_dcn_and_add(cube_ring8):
    with pytest.raises(ValueError, match="DCN-crossing"):
        substrate.run_per_shard(
            cube_ring8,
            lambda v: cube_ring8.comm("d").all_reduce(
                v, algorithm="compressed"),
            np.ones((8, 4), np.float32))
