"""Program futures, staging, inter-wave pricing and two trainer/bench
guards:

* futures resolved through rewrite provenance
  (:meth:`ProgramExecution.future_for` through the rs+ag peephole and
  through coalescing), and foreign or unknown handles rejected;
* ``ProgramExecution.stage`` pre-building a coalesced bucket's payload;
* inter-wave overlap pricing in :func:`planner.plan_program`;
* the ``Trainer.run`` step-timing fix (block before reading the clock);
* the bench gate's absolute floor and its programs section
  (:func:`benchmarks.run.check_against`).
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.testing import substrate


# ------------------------------------------- futures through rewrites
def _per_shard_aval(cube, payload_shape, dtype=jnp.float32):
    shape = (1,) * len(cube.dim_sizes) + tuple(payload_shape)
    return jax.ShapeDtypeStruct(shape, dtype)


def test_future_for_resolves_through_rs_ag_fusion(cube_ring8):
    """A caller holding the recorded reduce_scatter (or all_gather) of a
    fused rs+ag pair still gets a resolvable future: it maps through
    fused_from provenance to the fused all_reduce's result."""
    from repro.testing import oracles
    comm = cube_ring8.comm("1")
    prog = cube_ring8.program()
    with prog:
        a = prog.input(_per_shard_aval(cube_ring8, (2, 16)))
        rs = comm.reduce_scatter(a, axis=2)
        ag = comm.all_gather(rs, axis=2)
        prog.output(ag)
    low = prog.lower()                       # rs+ag -> one all_reduce
    assert len(low.ops) == 1 and low.ops[0].primitive == "all_reduce"
    x = substrate.integer_payload(cube_ring8, (2, 16), seed=8)

    def per_shard(v):
        ex = low.execute_async(v)
        f_rs = ex.future_for(rs)             # recorded op eaten by fusion
        f_ag = ex.future_for(ag)
        f_id = ex.future_for(1)              # same, by recorded op id
        assert f_rs.op is low.ops[0] and f_ag.op is low.ops[0]
        out = f_rs.result()
        assert f_ag.done() and f_id.done()
        return out

    got = substrate.run_per_shard(cube_ring8, per_shard, x)
    np.testing.assert_array_equal(got, oracles.all_reduce(x, 1, (0,)))


def test_future_for_coalesced_member_returns_own_value(cube_ring8):
    """future_for on one leaf of a coalesced bucket returns exactly that
    leaf's synced value (out_vids subsetting), not the whole bucket."""
    comm = cube_ring8.comm("1")
    prog = cube_ring8.program()
    with prog:
        a = prog.input(_per_shard_aval(cube_ring8, (2, 4)))
        b = prog.input(_per_shard_aval(cube_ring8, (2, 4)))
        ra = comm.all_reduce(a)
        rb = comm.all_reduce(b)
        prog.output(ra, rb)
    low = prog.lower()
    assert len(low.ops) == 1 and low.ops[0].coalesced
    x = substrate.integer_payload(cube_ring8, (2, 4), seed=1)
    y = substrate.integer_payload(cube_ring8, (2, 4), seed=2)

    def per_shard(va, vb):
        ex = low.execute_async(va, vb)
        out_b = ex.future_for(rb).result()   # just rb's leaf
        assert out_b.shape == vb.shape
        return out_b

    from repro.compat import shard_map as smap
    sp = substrate.global_spec(cube_ring8, 2)
    got = jax.jit(smap(per_shard, mesh=cube_ring8.mesh, in_specs=(sp, sp),
                       out_specs=sp, check_vma=False))(x, y)
    from repro.testing import oracles
    np.testing.assert_array_equal(np.asarray(got),
                                  oracles.all_reduce(y, 1, (0,)))


def test_future_for_rejects_foreign_and_unknown_handles(cube_ring8):
    comm = cube_ring8.comm("1")
    prog = cube_ring8.program()
    with prog:
        a = prog.input(_per_shard_aval(cube_ring8, (2, 4)))
        ra = comm.all_reduce(a)
        prog.output(ra)
    other = cube_ring8.program()
    with other:
        oa = other.input(_per_shard_aval(cube_ring8, (2, 4)))
        ob = comm.all_reduce(oa)
        other.output(ob)
    low = prog.lower()
    x = substrate.integer_payload(cube_ring8, (2, 4), seed=3)

    def per_shard(v):
        ex = low.execute_async(v)
        with pytest.raises(ValueError, match="belongs to"):
            ex.future_for(ob)
        with pytest.raises(KeyError, match="no recorded op"):
            ex.future_for(7)
        return ex.outputs()

    substrate.run_per_shard(cube_ring8, per_shard, x)


def test_stage_prebuilds_coalesced_payload(cube_ring8):
    """stage() concatenates a coalesced bucket's payload ahead of the wire
    op; force() consumes the staged payload and the result is unchanged."""
    comm = cube_ring8.comm("1")
    prog = cube_ring8.program()
    with prog:
        a = prog.input(_per_shard_aval(cube_ring8, (2, 4)))
        b = prog.input(_per_shard_aval(cube_ring8, (2, 4)))
        prog.output(comm.all_reduce(a), comm.all_reduce(b))
    low = prog.lower()
    assert low.ops[0].coalesced
    x = substrate.integer_payload(cube_ring8, (2, 4), seed=4)
    y = substrate.integer_payload(cube_ring8, (2, 4), seed=5)

    def per_shard(va, vb):
        ex = low.execute_async(va, vb).stage()
        assert set(ex._staged) == {low.ops[0].op_id}
        outs = ex.outputs()
        assert not ex._staged                # consumed, not re-concatenated
        return outs[0]

    from repro.compat import shard_map as smap
    sp = substrate.global_spec(cube_ring8, 2)
    got = jax.jit(smap(per_shard, mesh=cube_ring8.mesh, in_specs=(sp, sp),
                       out_specs=sp, check_vma=False))(x, y)
    from repro.testing import oracles
    np.testing.assert_array_equal(np.asarray(got),
                                  oracles.all_reduce(x, 1, (0,)))


# ------------------------------------------------- inter-wave planning
def _pod_fake():
    return substrate.fake_cube((2, 2, 2), ("pod", "data", "model"),
                               {"pod": 2, "dp": 2, "tp": 2})


def _profile(cube, factor):
    from repro.tuning import (
        CommProfile, LinkModel, OverlapModel, overlap_key,
        topology_fingerprint)
    lm = LinkModel(alpha=1e-4, beta=1e-9, n=8, r2=1.0)
    models = {f"{alg}/{stage}/{dom}": lm
              for alg, stage in (("naive", "naive"), ("direct", "im"),
                                 ("direct", "cm"), ("hierarchical", "im"))
              for dom in ("ici", "dcn")}
    overlap = {overlap_key(a, b): OverlapModel(factor=factor, n=4)
               for a in ("ici", "dcn") for b in ("ici", "dcn")}
    return CommProfile(topology_fingerprint(cube), models=models,
                       overlap=overlap)


def _two_wave_ops(head_deps=(0,)):
    from repro.core import planner
    mb = float(1 << 20)
    return [
        planner.ProgramOpSpec(0, "all_reduce", ("pod", "dp"), mb),
        planner.ProgramOpSpec(1, "all_gather", ("tp",), mb),
        planner.ProgramOpSpec(2, "all_gather", ("tp",), mb,
                              deps=head_deps),
    ]


def test_inter_wave_boundary_discount_under_measured_factors():
    """With measured serialization factors, the wave-boundary pair earns
    an overlap credit when the next wave's head does not depend on the
    previous wave's tail -- the budget drops strictly below the
    no-discount (factor=1.0) budget, provenance stays measured."""
    from repro.core import planner
    cube = _pod_fake()
    ops = _two_wave_ops(head_deps=(0,))      # head dep != chosen tail
    p_discount = planner.plan_program(cube, ops,
                                      profile=_profile(cube, 0.25))
    p_serial = planner.plan_program(cube, ops,
                                    profile=_profile(cube, 1.0))
    assert p_discount.est_source == "measured"
    assert p_serial.est_source == "measured"
    assert p_discount.seconds < p_serial.seconds
    assert p_discount.serial_seconds == p_serial.serial_seconds
    # discounting never reorders waves or drops ops
    assert p_discount.levels == p_serial.levels


def test_inter_wave_no_credit_when_head_depends_on_tail():
    """A wave-2 op that depends on every wave-1 op cannot overlap the
    boundary: the program's budget is exactly wave-1's (intra-discounted)
    budget plus the standalone wave-2 budget."""
    from repro.core import planner
    cube = _pod_fake()
    prof = _profile(cube, 0.25)
    free = planner.plan_program(cube, _two_wave_ops(head_deps=(0,)),
                                profile=prof)
    chained = planner.plan_program(cube, _two_wave_ops(head_deps=(0, 1)),
                                   profile=prof)
    assert chained.seconds > free.seconds    # the boundary credit is lost
    wave0 = planner.plan_program(cube, _two_wave_ops()[:2], profile=prof)
    solo = planner.plan_program(
        cube, [planner.ProgramOpSpec(2, "all_gather", ("tp",),
                                     float(1 << 20))], profile=prof)
    assert chained.seconds == wave0.seconds + solo.seconds
    assert chained.est_source == "measured"


def test_inter_wave_unmeasured_boundary_is_mixed():
    """An overlappable wave boundary whose ordered domain pair the profile
    never measured counts as an unmeasured pair: the plan demotes to
    "mixed" even though every op and intra-wave pair is measured -- and
    covering the boundary pair restores full provenance."""
    from repro.core import planner
    from repro.tuning import OverlapModel, overlap_key
    cube = _pod_fake()
    mb = float(1 << 20)
    prof = _profile(cube, 0.25)
    prof.overlap.clear()
    prof.overlap[overlap_key("ici", "ici")] = OverlapModel(0.25, 4)
    prof.overlap[overlap_key("dcn", "dcn")] = OverlapModel(0.25, 4)
    ops = [  # wave0: two ici ops; wave1: two dcn ops, ici->dcn boundary
        planner.ProgramOpSpec(0, "all_gather", ("tp",), mb),
        planner.ProgramOpSpec(1, "all_gather", ("tp",), mb),
        planner.ProgramOpSpec(2, "all_reduce", ("pod", "dp"), mb,
                              deps=(0,)),
        planner.ProgramOpSpec(3, "all_reduce", ("pod", "dp"), mb,
                              deps=(0,)),
    ]
    p = planner.plan_program(cube, ops, profile=prof)
    assert p.est_source == "mixed"
    prof.overlap[overlap_key("ici", "dcn")] = OverlapModel(0.25, 4)
    p_full = planner.plan_program(cube, ops, profile=prof)
    assert p_full.est_source == "measured"
    assert p_full.seconds < p.seconds        # the boundary now discounts


def test_multi_wave_analytic_budget_unchanged():
    """Without a profile the multi-wave budget is exactly the sum of the
    standalone per-wave analytic budgets -- the inter-wave machinery must
    be invisible on the analytic path."""
    from repro.core import planner
    cube = _pod_fake()
    ops = _two_wave_ops(head_deps=(0,))
    p = planner.plan_program(cube, ops)
    assert p.est_source == "analytic"
    wave0 = planner.plan_program(cube, ops[:2])
    wave1 = planner.plan_program(
        cube, [planner.ProgramOpSpec(2, "all_gather", ("tp",),
                                     float(1 << 20))])
    assert p.seconds == wave0.seconds + wave1.seconds


# ------------------------------------------------ trainer step timing
def test_step_deadline_sees_async_dispatched_compute():
    """Regression for the step-timing bug: Trainer.run must block on the
    step's real outputs (params/opt_state) before reading the clock.  A
    step whose metrics are ready immediately but whose param update is an
    async-dispatched expensive computation must still trip the deadline."""
    from repro.runtime.trainer import Trainer, TrainConfig

    n = 800
    x = jnp.ones((n, n), jnp.float32)

    @jax.jit
    def expensive(v):
        for _ in range(20):
            v = jnp.tanh(v @ v) / n
        return v

    jax.block_until_ready(expensive(x))      # compile + warm cache
    t0 = time.monotonic()
    jax.block_until_ready(expensive(x))
    step_cost = time.monotonic() - t0
    # above async-dispatch latency, well below the blocked step cost
    deadline = max(step_cost / 4, 2e-3)

    def slow_step(params, opt_state, batch):
        # metrics are plain floats (ready instantly); the param update is
        # dispatched asynchronously -- without the block-before-clock fix
        # dt would only see the dispatch, not the compute
        return expensive(params), opt_state, {"loss": 0.1,
                                              "grad_norm": 1.0}

    tr = object.__new__(Trainer)
    tr.tc = TrainConfig(step_deadline_s=deadline)
    tr.step_fn = slow_step
    tr.checkpointer = None
    tr.slow_steps = 0
    _, _, hist = tr.run(x, {}, [None], log_every=0, log=lambda *_: None)
    assert tr.slow_steps == 1
    assert hist[0]["straggler"] == 1.0


# ------------------------------------------------------ bench-gate floor
def _bench_doc(rows=(), programs=()):
    return {"schema": [], "program_schema": [],
            "rows": list(rows), "programs": list(programs)}


def _row(us, primitive="all_reduce", flow="direct", nbytes=1024):
    return {"primitive": primitive, "flow": flow, "stage": "im",
            "nbytes": nbytes, "measured_us": us, "est_us": 1.0,
            "est_source": "analytic"}


def test_check_against_zero_seed_row_uses_absolute_floor(tmp_path):
    """A seed row with measured_us == 0 must not make the gate
    hair-trigger: fresh values inside tolerance * floor pass, genuinely
    regressed values still fail."""
    from benchmarks.run import check_against
    seed = tmp_path / "seed.json"
    fresh_ok = tmp_path / "ok.json"
    fresh_bad = tmp_path / "bad.json"
    seed.write_text(json.dumps(_bench_doc(rows=[_row(0.0)])))
    fresh_ok.write_text(json.dumps(_bench_doc(rows=[_row(9.0)])))
    fresh_bad.write_text(json.dumps(_bench_doc(rows=[_row(80.0)])))
    assert check_against(str(seed), str(fresh_ok),
                         tolerance=2.0, floor_us=5.0) == []
    failures = check_against(str(seed), str(fresh_bad),
                             tolerance=2.0, floor_us=5.0)
    assert len(failures) == 1 and "80.0us" in failures[0]


def test_check_against_gates_programs_section(tmp_path):
    """The programs section (train_step rows included) is gated by name
    with the same tolerance and floor."""
    from benchmarks.run import check_against

    def prow(us):
        return {"name": "train_step", "ops": 3, "measured_us": us,
                "plan_est_us": 1.0, "serial_est_us": 2.0,
                "est_source": "measured"}

    seed = tmp_path / "seed.json"
    fresh_ok = tmp_path / "ok.json"
    fresh_bad = tmp_path / "bad.json"
    seed.write_text(json.dumps(_bench_doc(programs=[prow(100.0)])))
    fresh_ok.write_text(json.dumps(_bench_doc(programs=[prow(150.0)])))
    fresh_bad.write_text(json.dumps(_bench_doc(programs=[prow(250.0)])))
    assert check_against(str(seed), str(fresh_ok), tolerance=2.0) == []
    failures = check_against(str(seed), str(fresh_bad), tolerance=2.0)
    assert len(failures) == 1 and "train_step" in failures[0]
    # seeds without a programs key (older trajectory docs) still gate rows
    old = tmp_path / "old.json"
    old.write_text(json.dumps({"rows": [_row(10.0)]}))
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(_bench_doc(rows=[_row(11.0)])))
    assert check_against(str(old), str(fresh), tolerance=2.0) == []
