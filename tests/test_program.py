"""Deferred CommProgram semantics (repro.core.program):

* recording defers dispatch (no events, symbolic values, op accounting);
* one-op programs are bit-identical to eager dispatch (the conformance
  contract holds through both paths);
* peephole fusion: a recorded rs+ag pair executes as one all_reduce --
  bit-identical, provenance-tagged (``fused_from``), verified in the HLO,
  and strictly cheaper in event count and estimated DCN bytes/seconds;
* the all_reduce -> rs+ag split rewrite (forced mode);
* same-group coalescing: independent same-group all-reduces dispatch
  as bucketed all-reduces, one per group and size budget;
* joint planning (planner.plan_program): dependency-safe interleaved order
  and the shared ICI/DCN budget;
* execute_async per-op futures with dependency-ordered dispatch.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import planner
from repro.core.comm import CommTrace
from repro.core.program import CommProgram, ProgramValue
from repro.testing import oracles, substrate


def _per_shard_aval(cube, payload_shape, dtype=jnp.float32):
    shape = (1,) * len(cube.dim_sizes) + tuple(payload_shape)
    return jax.ShapeDtypeStruct(shape, dtype)


# ------------------------------------------------------------- recording
def test_recording_defers_dispatch(cube_ring8):
    comm = cube_ring8.comm("1")
    with CommTrace() as tr:
        with comm.program(name="rec") as prog:
            a = prog.input(_per_shard_aval(cube_ring8, (2, 16)))
            b = comm.reduce_scatter(a, axis=2)
            c = comm.all_gather(b, axis=2)
            prog.output(c)
    assert tr.events == []                       # nothing dispatched
    assert isinstance(b, ProgramValue) and isinstance(c, ProgramValue)
    assert b.shape == (1, 2, 2) and c.shape == (1, 2, 16)
    assert len(prog._ops) == 2
    assert "reduce_scatter" in prog.describe()


def test_program_validation(cube_ring8, cube_2x2x2):
    comm = cube_ring8.comm("1")
    prog = cube_ring8.program()
    with prog:
        a = prog.input(_per_shard_aval(cube_ring8, (8,)))
        with pytest.raises(ValueError, match="different cube"):
            cube_2x2x2.comm("010").all_reduce(a)
        with pytest.raises(RuntimeError, match="still recording"):
            prog.lower()
        comm.all_reduce(a)
    with pytest.raises(ValueError, match="takes 1 inputs"):
        prog.execute()
    with pytest.raises(RuntimeError, match="already recorded"):
        with prog:
            pass


# ------------------------------------------------- one-op program parity
ONE_OP_CELLS = [
    ("cube_ring8", "1", "all_to_all", "naive"),
    ("cube_ring8", "1", "all_to_all", "pidcomm"),
    ("cube_2x4", "01", "reduce_scatter", "pr"),
    ("cube_2x4", "01", "all_gather", "pidcomm"),
    ("cube_2x2x2", "011", "all_reduce", "naive"),
    ("cube_2x2x2", "110", "all_reduce", "pidcomm"),
    ("cube_pod", "110", "all_reduce", "auto"),
]


@pytest.mark.parametrize("cube_name,bitmap,primitive,alg", ONE_OP_CELLS)
def test_one_op_program_bit_identical_to_eager(cube_name, bitmap, primitive,
                                               alg, request):
    """Eager single-op calls remain supported as one-op programs: the
    program path executes the identical registry body, bit-identically."""
    cube = request.getfixturevalue(cube_name)
    names = cube.dims_from_bitmap(bitmap)
    idx = tuple(cube.dim_names.index(d) for d in names)
    comm = cube.comm(names)
    nd = len(cube.dim_sizes)
    g = cube.group_size(names)
    x = substrate.integer_payload(cube, (2, 4 * g), seed=g)
    kwargs = {
        "all_to_all": dict(split_axis=nd + 1, concat_axis=nd + 1),
        "reduce_scatter": dict(axis=nd + 1),
        "all_gather": dict(axis=nd),
        "all_reduce": {},
    }[primitive]
    oracle = {
        "all_to_all": lambda: oracles.all_to_all(x, nd, idx, split_axis=1,
                                                 concat_axis=1),
        "reduce_scatter": lambda: oracles.reduce_scatter(x, nd, idx, axis=1),
        "all_gather": lambda: oracles.all_gather(x, nd, idx, axis=0),
        "all_reduce": lambda: oracles.all_reduce(x, nd, idx),
    }[primitive]()

    eager = substrate.run_per_shard(
        cube, lambda v: getattr(comm, primitive)(v, algorithm=alg,
                                                 **kwargs), x)
    with cube.program() as prog:
        a = prog.input(_per_shard_aval(cube, (2, 4 * g)))
        prog.output(getattr(comm, primitive)(a, algorithm=alg, **kwargs))
    via_prog = substrate.run_per_shard(cube, lambda v: prog.execute(v), x)
    np.testing.assert_array_equal(via_prog, eager)   # bit-identical
    np.testing.assert_array_equal(via_prog, oracle)


# ----------------------------------------------------------- rs+ag fusion
def _record_rs_ag(cube, comm, payload):
    prog = cube.program(name="rsag")
    with prog:
        a = prog.input(_per_shard_aval(cube, payload))
        axis = len(cube.dim_sizes) + 1
        b = comm.reduce_scatter(a, axis=axis)
        c = comm.all_gather(b, axis=axis)
        prog.output(c)
    return prog


def test_fused_rs_ag_equals_eager_all_reduce(cube_pod):
    """Acceptance: a recorded rs+ag pair executes as one all_reduce, with
    fused_from provenance on the CommTrace event, bit-identical to the
    eager all_reduce on the 8-device substrate."""
    comm = cube_pod.comm(("pod", "dp"))
    g = comm.group_size
    x = substrate.integer_payload(cube_pod, (2, 4 * g), seed=7)
    prog = _record_rs_ag(cube_pod, comm, (2, 4 * g))
    low = prog.lower()
    assert len(low.ops) == 1
    fused = low.ops[0]
    assert fused.primitive == "all_reduce"
    assert fused.fused_from == (0, 1) and not fused.coalesced

    with CommTrace() as tr:
        got = substrate.run_per_shard(cube_pod, lambda v: low.execute(v), x)
    eager = substrate.run_per_shard(cube_pod, lambda v: comm.all_reduce(v), x)
    np.testing.assert_array_equal(got, eager)        # bit-identical
    np.testing.assert_array_equal(got, oracles.all_reduce(x, 3, (0, 1)))
    [ev] = tr.events
    assert ev.primitive == "all_reduce" and ev.flow == "hierarchical"
    assert ev.program_id == prog.program_id
    assert ev.fused_from == (0, 1)


def test_fusion_strictly_reduces_events_and_bytes(cube_pod):
    """CommTrace accounting: fusion cuts the event count 2 -> 1 and the
    estimated DCN bytes and seconds strictly drop (the fused pod-crossing
    all_reduce takes the hierarchical split; the eager pair pays full-rate
    DCN on both hops)."""
    comm = cube_pod.comm(("pod", "dp"))
    g = comm.group_size
    x = substrate.integer_payload(cube_pod, (2, 4 * g), seed=3)
    axis = 4
    with CommTrace() as eager_tr:
        substrate.run_per_shard(
            cube_pod,
            lambda v: comm.all_gather(comm.reduce_scatter(v, axis=axis),
                                      axis=axis), x)
    prog = _record_rs_ag(cube_pod, comm, (2, 4 * g))
    low = prog.lower()
    with CommTrace() as fused_tr:
        substrate.run_per_shard(cube_pod, lambda v: low.execute(v), x)
    assert len(eager_tr.events) == 2 and len(fused_tr.events) == 1
    e_ici, e_dcn = eager_tr.total_bytes()
    f_ici, f_dcn = fused_tr.total_bytes()
    assert f_dcn < e_dcn
    assert sum(e.seconds for e in fused_tr.events) < \
        sum(e.seconds for e in eager_tr.events)
    s = fused_tr.summary()
    assert s["fused_events"] == 1 and s["fused_from_ops"] == 2
    assert s["programs"] == [prog.program_id]


def test_fused_program_hlo_is_one_all_reduce(cube_ring8):
    """Acceptance HLO check: the fused program lowers to the all-reduce op
    alone -- no reduce-scatter / all-gather survives -- while the eager pair
    lowers to both."""
    comm = cube_ring8.comm("1")
    x = substrate.integer_payload(cube_ring8, (2, 16), seed=5)
    eager_hlo = substrate.lowered_text(
        cube_ring8,
        lambda v: comm.all_gather(comm.reduce_scatter(v, axis=2), axis=2), x)
    assert "reduce_scatter" in eager_hlo or "reduce-scatter" in eager_hlo
    assert "all_gather" in eager_hlo or "all-gather" in eager_hlo

    low = _record_rs_ag(cube_ring8, comm, (2, 16)).lower()
    hlo = substrate.lowered_text(cube_ring8, lambda v: low.execute(v), x)
    assert "all_reduce" in hlo or "all-reduce" in hlo
    assert "reduce_scatter" not in hlo and "reduce-scatter" not in hlo
    assert "all_gather" not in hlo and "all-gather" not in hlo


def test_no_fusion_when_shard_is_consumed(cube_ring8):
    """The rs result escaping as a program output blocks the rewrite."""
    comm = cube_ring8.comm("1")
    prog = cube_ring8.program()
    with prog:
        a = prog.input(_per_shard_aval(cube_ring8, (2, 16)))
        b = comm.reduce_scatter(a, axis=2)
        c = comm.all_gather(b, axis=2)
        prog.output(b, c)                      # the shard itself is needed
    low = prog.lower()
    assert [o.primitive for o in low.ops] == ["reduce_scatter", "all_gather"]
    x = substrate.integer_payload(cube_ring8, (2, 16), seed=2)
    from repro.compat import shard_map
    spec = substrate.global_spec(cube_ring8, 2)
    shard, full = jax.jit(shard_map(
        lambda v: low.execute(v), mesh=cube_ring8.mesh, in_specs=spec,
        out_specs=(spec, spec), check_vma=False))(x)
    np.testing.assert_array_equal(
        np.asarray(shard), oracles.reduce_scatter(x, 1, (0,), axis=1))
    np.testing.assert_array_equal(np.asarray(full),
                                  oracles.all_reduce(x, 1, (0,)))


def test_split_all_reduce_rewrite(cube_ring8):
    """The reverse peephole: under forced mode an all_reduce becomes the
    rs+ag pair (provenance on both halves), bit-identical."""
    comm = cube_ring8.comm("1")
    prog = cube_ring8.program()
    with prog:
        a = prog.input(_per_shard_aval(cube_ring8, (16, 3)))
        prog.output(comm.all_reduce(a))
    low = prog.lower(split_all_reduce=True)
    prims = [o.primitive for o in low.ops]
    assert prims == ["reduce_scatter", "all_gather"]
    assert all(o.fused_from == (0,) for o in low.ops)
    x = substrate.integer_payload(cube_ring8, (16, 3), seed=9)
    got = substrate.run_per_shard(cube_ring8, lambda v: low.execute(v), x)
    np.testing.assert_array_equal(got, oracles.all_reduce(x, 1, (0,)))
    # default "cost" mode keeps the fused collective (the split ties, never
    # strictly wins, on the flat byte model)
    assert [o.primitive for o in prog.lower().ops] == ["all_reduce"]


# ------------------------------------------------------------- coalescing
def test_multiple_coalesce_buckets_all_survive(cube_pod):
    """Regression: several distinct-group buckets in one program must each
    emit their own coalesced op (a mixed-dims program, one group per
    replication pattern)."""
    prog = cube_pod.program()
    groups = [("pod",), ("pod", "tp"), ("pod", "dp", "tp")]
    vals = []
    with prog:
        for gi, dims in enumerate(groups):
            comm = cube_pod.comm(dims)
            for k in range(2):
                v = prog.input(_per_shard_aval(cube_pod, (4 + gi + k,)))
                vals.append(comm.all_reduce(v))
        prog.output(*vals)
    low = prog.lower()
    assert len(low.ops) == 3 and all(o.coalesced for o in low.ops)
    assert sorted(o.comm.dims for o in low.ops) == sorted(groups)
    xs = [substrate.integer_payload(cube_pod, (4 + gi + k,),
                                    seed=10 * gi + k)
          for gi in range(3) for k in range(2)]
    from repro.compat import shard_map
    sp = tuple(substrate.global_spec(cube_pod, 1) for _ in xs)
    got = jax.jit(shard_map(lambda *vs: low.execute(*vs),
                            mesh=cube_pod.mesh, in_specs=sp, out_specs=sp,
                            check_vma=False))(*xs)
    for (gi, k), x, r in zip([(g, k) for g in range(3) for k in range(2)],
                             xs, got):
        idx = tuple(cube_pod.dim_names.index(d) for d in groups[gi])
        np.testing.assert_array_equal(np.asarray(r),
                                      oracles.all_reduce(x, 3, idx))


def test_coalescing_respects_size_and_group(cube_pod):
    """A leaf above the coalescing threshold and a leaf on a different
    group each keep their own dispatch."""
    big = 1 << 19                                    # 2 MiB of f32 > 1 MiB
    prog = cube_pod.program()
    c_all = cube_pod.comm(("pod", "dp"))
    c_tp = cube_pod.comm(("tp",))
    with prog:
        i1 = prog.input(_per_shard_aval(cube_pod, (8,)))
        i2 = prog.input(_per_shard_aval(cube_pod, (12,)))
        i3 = prog.input(_per_shard_aval(cube_pod, (big,)))
        i4 = prog.input(_per_shard_aval(cube_pod, (8,)))
        prog.output(c_all.all_reduce(i1), c_all.all_reduce(i2),
                    c_all.all_reduce(i3), c_tp.all_reduce(i4))
    low = prog.lower()
    coalesced = [o for o in low.ops if o.coalesced]
    assert len(coalesced) == 1 and len(coalesced[0].fused_from) == 2
    assert len(low.ops) == 3                         # bucket + big + tp


def test_provenance_chains_to_recorded_ops(cube_pod):
    """fused_from always names *recorded* op ids: when coalescing absorbs
    an op that fusion created, the provenance chains through to the
    original rs/ag pair, not the synthetic intermediate id."""
    comm = cube_pod.comm(("pod", "dp"))
    prog = cube_pod.program()
    with prog:
        a = prog.input(_per_shard_aval(cube_pod, (2, 8)))
        fused = comm.all_gather(comm.reduce_scatter(a, axis=4), axis=4)
        b = prog.input(_per_shard_aval(cube_pod, (2, 8)))
        plain = comm.all_reduce(b)
        prog.output(fused, plain)
    low = prog.lower()
    [op] = low.ops
    assert op.coalesced
    assert sorted(op.fused_from) == [0, 1, 2]        # rs, ag, plain ar
    assert all(i < len(prog._ops) for i in op.fused_from)


# ---------------------------------------------------------- joint planning
def test_plan_program_escalation_parity(cube_pod):
    """A stage-requested additive all_reduce on a both-domain group is
    priced as the hierarchical flow the dispatcher actually executes (not
    the flat direct collective), while max-reductions and intra-pod groups
    keep the direct byte model."""
    mb = float(1 << 20)
    plan = planner.plan_program(cube_pod, [
        planner.ProgramOpSpec(0, "all_reduce", ("pod", "dp"), mb,
                              algorithm="im"),
        planner.ProgramOpSpec(1, "all_reduce", ("pod", "dp"), mb,
                              algorithm="im", op="max"),
        planner.ProgramOpSpec(2, "all_reduce", ("dp",), mb,
                              algorithm="im"),
        planner.ProgramOpSpec(3, "all_reduce", ("pod", "dp"), mb,
                              algorithm="ring"),
    ])
    direct = planner.estimate(cube_pod, "all_reduce", ("pod", "dp"), mb,
                              algorithm="direct")
    assert plan.estimates[0].algorithm == "hierarchical"
    assert plan.estimates[0].dcn_bytes < direct.dcn_bytes
    assert plan.estimates[1].algorithm == "direct"   # max cannot split
    assert plan.estimates[2].algorithm == "direct"   # intra-pod
    assert plan.estimates[3].algorithm == "direct"   # ring never escalates


def test_plan_program_order_and_budget(cube_pod):
    """plan_program levels ops by dependency, interleaves independent
    DCN/ICI-dominant ops, and prices each wave at the larger of the two
    domain budgets (never more than the serial sum)."""
    mb = float(1 << 20)
    ops = [
        planner.ProgramOpSpec(0, "all_reduce", ("pod", "dp"), mb),
        planner.ProgramOpSpec(1, "all_gather", ("tp",), mb),
        planner.ProgramOpSpec(2, "all_reduce", ("pod", "dp"), mb,
                              algorithm="compressed"),
        planner.ProgramOpSpec(3, "reduce_scatter", ("tp",), mb, deps=(1,)),
    ]
    plan = planner.plan_program(cube_pod, ops)
    assert set(plan.order) == {0, 1, 2, 3}
    assert plan.order.index(1) < plan.order.index(3)     # dependency-safe
    assert plan.levels[0] and plan.levels[1] == (3,)
    # wave 0 interleaves: a DCN-dominant op leads, an ICI one follows
    doms = [plan.estimates[i].dominant() for i in plan.levels[0][:2]]
    assert doms == ["dcn", "ici"]
    assert plan.seconds <= plan.serial_seconds + 1e-12
    assert plan.ici_bytes > 0 and plan.dcn_bytes > 0
    assert plan.estimates[2].algorithm == "compressed"
    with pytest.raises(ValueError, match="cyclic"):
        planner.plan_program(cube_pod, [
            planner.ProgramOpSpec(0, "all_reduce", ("tp",), mb, deps=(1,)),
            planner.ProgramOpSpec(1, "all_reduce", ("tp",), mb, deps=(0,)),
        ])


# ------------------------------------------------------------------ async
def test_execute_async_futures(cube_ring8):
    """Per-op futures dispatch in dependency order and memoize."""
    comm = cube_ring8.comm("1")
    prog = cube_ring8.program()
    with prog:
        a = prog.input(_per_shard_aval(cube_ring8, (2, 16)))
        b = comm.reduce_scatter(a, axis=2)
        c = comm.all_gather(b, axis=2)
        prog.output(c)
    low = prog.lower(fuse=False)                     # keep both ops live
    assert len(low.ops) == 2
    x = substrate.integer_payload(cube_ring8, (2, 16), seed=8)

    def per_shard(v):
        ex = low.execute_async(v)
        assert not any(f.done() for f in ex.futures)
        out = ex.futures[1].result()                 # forces the rs dep too
        assert all(f.done() for f in ex.futures)
        return out

    got = substrate.run_per_shard(cube_ring8, per_shard, x)
    np.testing.assert_array_equal(got, oracles.all_reduce(x, 1, (0,)))


# -------------------------------------------------- all_to_all chain merge
def test_merge_a2a_chain_bit_identical(cube_2x2x2):
    """§VII DLRM peephole: consecutive all_to_all ops over disjoint dims
    lower to ONE chained IR op (jointly planned over the union of dims)
    whose execution is bit-identical to the unfused program -- the merged
    form must chain, because a single joint multi-dim all_to_all orders
    blocks differently."""
    ca = cube_2x2x2.comm("100")
    cc = cube_2x2x2.comm("001")
    nd = len(cube_2x2x2.dim_sizes)
    rng = np.random.RandomState(3)
    x = rng.randn(2, 2, 2, 16).astype(np.float32)

    prog = cube_2x2x2.program(name="aa-chain")
    with prog:
        v = prog.input(_per_shard_aval(cube_2x2x2, (16,)))
        w = ca.all_to_all(v, split_axis=nd, concat_axis=nd)
        prog.output(cc.all_to_all(w, split_axis=nd, concat_axis=nd))

    merged = prog.lower()
    plain = prog.lower(merge_a2a=False)
    assert len(plain.ops) == 2
    assert len(merged.ops) == 1
    mop = merged.ops[0]
    assert mop.fused_from == (0, 1) and len(mop.chain) == 2
    assert mop.comm.dims == ("a", "c")               # planned over the union
    est = merged.plan.estimates[mop.op_id]
    assert est.primitive == "all_to_all"

    with CommTrace() as tr:
        got = substrate.run_per_shard(cube_2x2x2,
                                      lambda v: merged.execute(v), x)
    want = substrate.run_per_shard(cube_2x2x2,
                                   lambda v: plain.execute(v), x)
    np.testing.assert_array_equal(got, want)         # bit-identical
    # ... and both equal the composed oracles
    o = oracles.all_to_all(x, 3, (0,), split_axis=0, concat_axis=0)
    o = oracles.all_to_all(o, 3, (2,), split_axis=0, concat_axis=0)
    np.testing.assert_array_equal(got, o)
    # execution chains both stages under the merged op's provenance
    assert [e.primitive for e in tr.events] == ["all_to_all", "all_to_all"]
    assert all(e.fused_from == (0, 1) for e in tr.events)
    assert all(e.program_id == "aa-chain" for e in tr.events)


def test_merge_a2a_requires_disjoint_dims(cube_2x2x2):
    """Overlapping dim selections must NOT merge (the rewrite is only
    defined for disjoint groups), and an intermediate that is a program
    output is kept."""
    cab = cube_2x2x2.comm("110")
    cbc = cube_2x2x2.comm("011")
    nd = len(cube_2x2x2.dim_sizes)
    prog = cube_2x2x2.program(name="aa-overlap")
    with prog:
        v = prog.input(_per_shard_aval(cube_2x2x2, (16,)))
        w = cab.all_to_all(v, split_axis=nd, concat_axis=nd)
        prog.output(cbc.all_to_all(w, split_axis=nd, concat_axis=nd))
    assert len(prog.lower().ops) == 2                # shared dim "b": no merge

    ca = cube_2x2x2.comm("100")
    cc = cube_2x2x2.comm("001")
    prog2 = cube_2x2x2.program(name="aa-mid-out")
    with prog2:
        v = prog2.input(_per_shard_aval(cube_2x2x2, (16,)))
        w = ca.all_to_all(v, split_axis=nd, concat_axis=nd)
        out = cc.all_to_all(w, split_axis=nd, concat_axis=nd)
        prog2.output(w, out)                         # intermediate escapes
    assert len(prog2.lower().ops) == 2
