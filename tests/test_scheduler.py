"""Overlap-aware program scheduler + cross-program reuse (PR 5 tentpole).

Overlap profile (schema v2):
  * OverlapSample factor math and the median fit;
  * JSON round-trip determinism with the overlap section populated;
  * v1 -> v2 migration (pre-overlap profiles load with an empty overlap
    section) and future-schema rejection with a retune recipe;
  * the fingerprint-mismatch error names both jax versions (the CI matrix
    leg that measured vs the one loading).

Overlap-aware planning (``planner.plan_program``):
  * a fully-measured profile (op models + overlap factors < 1) prices
    ``seconds`` strictly under ``serial_seconds`` with
    ``est_source="measured"`` on the plan itself;
  * overlap factors alone (no op models) mark the plan ``"mixed"``;
  * no profile keeps the analytic model bit-for-bit (order and budget);
  * a synthetic *inverting* overlap profile flips the chosen interleaving
    of a two-op program, with bit-identical execution either way;
  * ``execute_async`` dispatches in exactly the plan's interleaving order.

Cross-program reuse (``repro.core.program`` lower cache):
  * structurally identical programs lower once (observable via
    ``LOWER_STATS``) and execute bit-identically through the cached
    schedule;
  * structure, lowering knobs and installed profile all key the cache.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import planner
from repro.core import program as program_mod
from repro.core.comm import CommTrace
from repro.testing import oracles, substrate
from repro.testing.substrate import fake_cube
from repro.tuning import (
    CommProfile, LinkModel, OverlapModel, OverlapSample,
    ProfileMismatchError, Tuner, fit_overlap, overlap_key,
    topology_fingerprint)
from repro.tuning import microbench
from repro.tuning import profile as profile_mod


def _per_shard_aval(cube, payload_shape, dtype=jnp.float32):
    shape = (1,) * len(cube.dim_sizes) + tuple(payload_shape)
    return jax.ShapeDtypeStruct(shape, dtype)


def _ov(dom_a, dom_b, sa, sb, pair):
    return OverlapSample(dom_a=dom_a, dom_b=dom_b,
                         primitive_a="all_reduce", primitive_b="all_reduce",
                         bitmap_a="1", bitmap_b="1", nbytes=1 << 20,
                         seconds_a=sa, seconds_b=sb, seconds_pair=pair)


@pytest.fixture(autouse=True)
def _fresh_lower_cache():
    program_mod.clear_lower_cache()
    yield
    program_mod.clear_lower_cache()


# ------------------------------------------------------- overlap profile
def test_overlap_sample_factor_math():
    # pair == max + 0.5*min -> half the smaller op serializes
    assert _ov("ici", "dcn", 1e-3, 2e-3, 2.5e-3).factor() == \
        pytest.approx(0.5)
    # perfect overlap and fully-serial clip to the [0, 1] ends
    assert _ov("ici", "ici", 1e-3, 1e-3, 0.5e-3).factor() == 0.0
    assert _ov("ici", "ici", 1e-3, 1e-3, 5e-3).factor() == 1.0
    models = fit_overlap([_ov("ici", "dcn", 1e-3, 2e-3, 2.5e-3),
                          _ov("ici", "dcn", 1e-3, 2e-3, 2.7e-3),
                          _ov("dcn", "ici", 1e-3, 1e-3, 2e-3)])
    assert set(models) == {"ici->dcn", "dcn->ici"}
    assert models["ici->dcn"].factor == pytest.approx(0.6)  # median of .5/.7
    assert models["ici->dcn"].n == 2
    assert models["dcn->ici"].factor == 1.0
    assert overlap_key("ici", "dcn") == "ici->dcn"


def test_overlap_roundtrip_deterministic(tmp_path):
    ring = fake_cube((8,), ("d",), {"d": 8})
    prof = CommProfile(topology_fingerprint(ring),
                       overlap_samples=[_ov("ici", "ici", 1e-3, 1e-3, 1.5e-3)])
    assert prof.has_overlap
    assert prof.overlap_factor("ici", "ici") == pytest.approx(0.5)
    assert prof.overlap_factor("ici", "dcn") is None
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    prof.save(p1)
    CommProfile.load(p1).save(p2)
    assert p1.read_bytes() == p2.read_bytes()
    re = CommProfile.load(p1, cube=ring)
    assert re.overlap == prof.overlap
    assert re.token() == prof.token()


def test_v1_profile_migrates_with_empty_overlap(tmp_path):
    """Schema bump with migration: a pre-overlap (v1) profile file loads as
    a valid v2 profile whose overlap section is empty -- per-op fits carry
    over, plan_program keeps the analytic interleaving until a retune."""
    ring = fake_cube((8,), ("d",), {"d": 8})
    prof = CommProfile(topology_fingerprint(ring),
                       overlap_samples=[_ov("ici", "ici", 1e-3, 1e-3, 2e-3)])
    path = prof.save(tmp_path / "prof.json")
    data = json.loads(open(path).read())
    del data["overlap"]
    del data["overlap_samples"]
    data["schema_version"] = 1
    with open(path, "w") as f:
        json.dump(data, f)
    old = CommProfile.load(path, cube=ring)
    assert old.overlap == {} and not old.has_overlap
    assert old.fingerprint == prof.fingerprint
    # ... while a future schema is still rejected with the retune recipe
    data["schema_version"] = profile_mod.SCHEMA_VERSION + 1
    with open(path, "w") as f:
        json.dump(data, f)
    with pytest.raises(ProfileMismatchError, match="tune"):
        CommProfile.load(path)


def test_fingerprint_mismatch_names_jax_version():
    """The CI matrix satellite: a profile measured on one jax leg loaded on
    another must say so in the error, not just dump two dicts."""
    ring = fake_cube((8,), ("d",), {"d": 8})
    fp = dict(topology_fingerprint(ring), jax="9.9.9")
    prof = CommProfile(fp)
    with pytest.raises(ProfileMismatchError, match=r"jax 9\.9\.9"):
        prof.check_fingerprint(ring)
    import jax as jax_mod
    with pytest.raises(ProfileMismatchError,
                       match=jax_mod.__version__.replace(".", r"\.")):
        prof.check_fingerprint(ring)


def test_profile_merge_unions_overlap():
    ring = fake_cube((8,), ("d",), {"d": 8})
    fp = topology_fingerprint(ring)
    a = CommProfile(fp, overlap_samples=[_ov("ici", "ici", 1e-3, 1e-3, 2e-3)])
    b = CommProfile(fp, overlap_samples=[
        _ov("ici", "ici", 1e-3, 1e-3, 2e-3),        # exact dup: dropped
        _ov("ici", "dcn", 1e-3, 1e-3, 1e-3)])
    merged = a.merge(b)
    assert len(merged.overlap_samples) == 2
    assert set(merged.overlap) == {"ici->ici", "ici->dcn"}
    assert a.token() != merged.token()


# --------------------------------------------- overlap-aware plan_program
def _pod_link_models():
    lm = LinkModel(alpha=1e-4, beta=1e-9, n=8, r2=1.0)
    return {f"{alg}/{stage}/{dom}": lm
            for alg, stage in (("naive", "naive"), ("direct", "im"),
                               ("direct", "cm"), ("hierarchical", "im"))
            for dom in ("ici", "dcn")}


def _two_op_specs():
    mb = float(1 << 20)
    return [planner.ProgramOpSpec(0, "all_reduce", ("pod", "dp"), mb),
            planner.ProgramOpSpec(1, "all_gather", ("tp",), mb)]


def test_plan_program_measured_overlap_budget():
    """Acceptance: with a profile covering op models AND overlap factors,
    the joint plan's seconds are measured-sourced and strictly under the
    serial bound (factor < 1 leaves real overlap on the table)."""
    pod = fake_cube((2, 2, 2), ("pod", "data", "model"),
                    {"pod": 2, "dp": 2, "tp": 2})
    fp = topology_fingerprint(pod)
    full = CommProfile(fp, models=_pod_link_models(), overlap={
        overlap_key(a, b): OverlapModel(factor=0.25, n=4)
        for a in ("ici", "dcn") for b in ("ici", "dcn")})
    plan = planner.plan_program(pod, _two_op_specs(), profile=full)
    assert plan.est_source == "measured"
    assert all(e.est_source == "measured" for e in plan.estimates.values())
    assert plan.seconds < plan.serial_seconds
    # overlap factors without op models still beat the serial bound but
    # carry "mixed" provenance (ops priced analytic, interleaving measured)
    ov_only = CommProfile(fp, overlap={
        overlap_key(a, b): OverlapModel(factor=0.25, n=4)
        for a in ("ici", "dcn") for b in ("ici", "dcn")})
    plan2 = planner.plan_program(pod, _two_op_specs(), profile=ov_only)
    assert plan2.est_source == "mixed"
    # no profile: analytic provenance, analytic budget formula
    plan3 = planner.plan_program(pod, _two_op_specs())
    assert plan3.est_source == "analytic"


def test_plan_program_without_overlap_is_unchanged():
    """A profile with op models but no overlap section must not perturb the
    analytic interleaving model: same order, same seconds formula, and the
    measured-ops-under-analytic-interleaving gap is visible as "mixed"."""
    pod = fake_cube((2, 2, 2), ("pod", "data", "model"),
                    {"pod": 2, "dp": 2, "tp": 2})
    prof = CommProfile(topology_fingerprint(pod), models=_pod_link_models())
    assert not prof.has_overlap
    p_prof = planner.plan_program(pod, _two_op_specs(), profile=prof)
    p_none = planner.plan_program(pod, _two_op_specs())
    assert p_prof.order == p_none.order
    assert p_prof.levels == p_none.levels
    assert p_prof.est_source == "mixed"


def test_wave_order_never_hides_an_op_twice():
    """Adjacent-pair pricing caps each op's hidden time at its own length:
    a short op flanked by two long same-link neighbours must not be
    subtracted once per neighbour (the two long ops still serialize)."""
    from repro.core.planner import CommEstimate, _wave_order_seconds
    est = {
        0: CommEstimate("all_reduce", "direct", (), 0.0, 1e6, 100e-6),
        1: CommEstimate("all_gather", "direct", (), 1e6, 0.0, 10e-6),
        2: CommEstimate("all_reduce", "direct", (), 0.0, 1e6, 100e-6),
    }
    secs, measured, total_pairs = _wave_order_seconds(
        (0, 1, 2), est, lambda a, b: 0.0)       # perfect overlap everywhere
    assert measured == 2 and total_pairs == 2
    # the 10us op hides once, not twice: 210 - 10 = 200, never 190
    assert secs == pytest.approx(200e-6)


def test_partial_overlap_coverage_is_mixed_not_measured():
    """Plan-level provenance: measured op models + an overlap section that
    does not cover the chosen order's domain pairs must report "mixed" --
    the interleaving budget fell back to the analytic assumption."""
    pod = fake_cube((2, 2, 2), ("pod", "data", "model"),
                    {"pod": 2, "dp": 2, "tp": 2})
    prof = CommProfile(topology_fingerprint(pod), models=_pod_link_models(),
                       overlap={overlap_key("ici", "ici"):
                                OverlapModel(factor=0.25, n=4)})
    # the two-op wave is one dcn + one ici op: its adjacent pair is
    # cross-domain either way, which this profile never measured
    plan = planner.plan_program(pod, _two_op_specs(), profile=prof)
    assert all(e.est_source == "measured" for e in plan.estimates.values())
    assert plan.est_source == "mixed"


def _inverting_overlap_profile(cube):
    """Overlap factors that contradict the analytic assumption: leading
    with the DCN op serializes completely, leading with the ICI op overlaps
    perfectly -- so the cheapest interleaving reverses."""
    return CommProfile(topology_fingerprint(cube), overlap={
        overlap_key("dcn", "ici"): OverlapModel(factor=1.0, n=4),
        overlap_key("ici", "dcn"): OverlapModel(factor=0.0, n=4),
    })


def test_inverting_overlap_flips_interleaving(cube_pod):
    """Tentpole satellite: the same recorded two-op program lowers to the
    DCN-led order analytically and to the ICI-led order under the
    inverting overlap profile, with bit-identical outputs through both
    schedules."""
    ar = cube_pod.comm(("pod",))           # DCN-dominant all_reduce
    ag = cube_pod.comm(("tp",))            # ICI-dominant all_gather

    def record():
        prog = cube_pod.program(name="flip")
        with prog:
            a = prog.input(_per_shard_aval(cube_pod, (2, 8)))
            b = prog.input(_per_shard_aval(cube_pod, (2, 8)))
            prog.output(ar.all_reduce(a), ag.all_gather(b, axis=4))
        return prog

    analytic = record().lower()
    doms = [analytic.plan.estimates[o.op_id].dominant()
            for o in analytic.ops]
    assert doms == ["dcn", "ici"]          # analytic interleave leads DCN
    assert analytic.plan.est_source == "analytic"

    prof = _inverting_overlap_profile(cube_pod)
    with planner.install_profile(prof):
        flipped = record().lower()
    doms = [flipped.plan.estimates[o.op_id].dominant()
            for o in flipped.ops]
    assert doms == ["ici", "dcn"]          # the measured factors flipped it
    assert flipped.plan.est_source == "mixed"
    assert flipped.plan.order != analytic.plan.order

    xa = substrate.integer_payload(cube_pod, (2, 8), seed=11)
    xb = substrate.integer_payload(cube_pod, (2, 8), seed=12)
    from repro.compat import shard_map
    sp = substrate.global_spec(cube_pod, 2)
    out_sp = (sp, sp)

    def run(low):
        fn = jax.jit(shard_map(lambda u, v: low.execute(u, v),
                               mesh=cube_pod.mesh, in_specs=(sp, sp),
                               out_specs=out_sp, check_vma=False))
        return [np.asarray(r) for r in fn(xa, xb)]

    got_a = run(analytic)
    got_f = run(flipped)
    for ga, gf in zip(got_a, got_f):
        np.testing.assert_array_equal(ga, gf)        # bit-identical
    np.testing.assert_array_equal(got_a[0],
                                  oracles.all_reduce(xa, 3, (0,)))
    np.testing.assert_array_equal(got_a[1],
                                  oracles.all_gather(xb, 3, (2,), axis=1))


def test_execute_async_matches_plan_order(cube_pod):
    """The dispatch order of ``execute_async`` (forced via ``outputs()``)
    is exactly ``plan_program``'s interleaving order -- for the analytic
    order and for the overlap-flipped one."""
    ar = cube_pod.comm(("pod",))
    ag = cube_pod.comm(("tp",))

    def record():
        prog = cube_pod.program(name="async-order")
        with prog:
            a = prog.input(_per_shard_aval(cube_pod, (2, 8)))
            b = prog.input(_per_shard_aval(cube_pod, (2, 8)))
            prog.output(ar.all_reduce(a), ag.all_gather(b, axis=4))
        return prog

    xa = substrate.integer_payload(cube_pod, (2, 8), seed=21)
    xb = substrate.integer_payload(cube_pod, (2, 8), seed=22)
    from repro.compat import shard_map
    sp = substrate.global_spec(cube_pod, 2)

    def dispatched(low):
        """primitives in actual dispatch order, per plan-ordered ops."""
        with CommTrace() as tr:
            fn = jax.jit(shard_map(
                lambda u, v: low.execute_async(u, v).outputs(),
                mesh=cube_pod.mesh, in_specs=(sp, sp), out_specs=(sp, sp),
                check_vma=False))
            fn(xa, xb)
        return [e.primitive for e in tr.events]

    analytic = record().lower()
    want = [next(o.primitive for o in analytic.ops if o.op_id == oid)
            for oid in analytic.plan.order]
    assert dispatched(analytic) == want == ["all_reduce", "all_gather"]

    with planner.install_profile(_inverting_overlap_profile(cube_pod)):
        flipped = record().lower()
    want = [next(o.primitive for o in flipped.ops if o.op_id == oid)
            for oid in flipped.plan.order]
    assert dispatched(flipped) == want == ["all_gather", "all_reduce"]


# --------------------------------------------------- cross-program reuse
def _twin_program(cube, n=16):
    comm = cube.comm("1")
    prog = cube.program(name="twin")
    with prog:
        a = prog.input(_per_shard_aval(cube, (2, n)))
        b = prog.input(_per_shard_aval(cube, (2, n)))
        prog.output(comm.all_reduce(a), comm.all_gather(b, axis=2))
    return prog

def test_lower_cache_reuses_identical_structure(cube_ring8):
    s0 = dict(program_mod.LOWER_STATS)
    l1 = _twin_program(cube_ring8).lower()
    l2 = _twin_program(cube_ring8).lower()
    d = {k: program_mod.LOWER_STATS[k] - s0[k]
         for k in program_mod.LOWER_STATS}
    assert d == {"lowered": 1, "cache_hits": 1}
    # the cached schedule is rebound, not shared: each lowered program
    # executes with its own constants/inputs
    assert l2.ops is l1.ops and l2.plan is l1.plan
    assert l2.program is not l1.program

    xa = substrate.integer_payload(cube_ring8, (2, 16), seed=31)
    xb = substrate.integer_payload(cube_ring8, (2, 16), seed=32)
    from repro.compat import shard_map
    sp = substrate.global_spec(cube_ring8, 2)

    def run(low):
        fn = jax.jit(shard_map(lambda u, v: low.execute(u, v),
                               mesh=cube_ring8.mesh, in_specs=(sp, sp),
                               out_specs=(sp, sp), check_vma=False))
        return [np.asarray(r) for r in fn(xa, xb)]

    for g, w in zip(run(l1), run(l2)):
        np.testing.assert_array_equal(g, w)          # bit-identical
    np.testing.assert_array_equal(run(l2)[0],
                                  oracles.all_reduce(xa, 1, (0,)))


def test_lower_cache_keys_structure_knobs_and_profile(cube_ring8):
    s0 = dict(program_mod.LOWER_STATS)
    _twin_program(cube_ring8).lower()
    _twin_program(cube_ring8, n=32).lower()          # different avals
    _twin_program(cube_ring8).lower(fuse=False)      # different knobs
    with planner.install_profile(CommProfile(
            topology_fingerprint(cube_ring8),
            overlap={overlap_key("ici", "ici"): OverlapModel(0.5, 4)})):
        _twin_program(cube_ring8).lower()            # different profile
    _twin_program(cube_ring8).lower(reuse=False)     # opt-out
    d = {k: program_mod.LOWER_STATS[k] - s0[k]
         for k in program_mod.LOWER_STATS}
    assert d == {"lowered": 5, "cache_hits": 0}
    # same structure+knobs+profile again: hit
    _twin_program(cube_ring8).lower()
    assert program_mod.LOWER_STATS["cache_hits"] - s0["cache_hits"] == 1


# ------------------------------------------------- bench-regression gate
def test_bench_check_against(tmp_path):
    """The CI gate (benchmarks.run --check-against): best measured_us per
    (primitive, flow, nbytes) compared at a noise tolerance; regressions
    fail, improvements and within-tolerance drift pass, dropped coverage
    warns without failing."""
    run_mod = pytest.importorskip("benchmarks.run")

    def write(name, rows):
        path = tmp_path / name
        with open(path, "w") as f:
            json.dump({"rows": rows, "programs": []}, f)
        return str(path)

    def row(prim, flow, nbytes, us):
        return {"primitive": prim, "flow": flow, "nbytes": nbytes,
                "measured_us": us, "stage": "im", "est_us": 1.0,
                "est_source": "analytic"}

    seed = write("seed.json", [row("all_reduce", "im", 1024, 100.0),
                               row("all_reduce", "im", 1024, 90.0),  # dup key
                               row("all_gather", "im", 2048, 50.0)])
    ok = write("ok.json", [row("all_reduce", "im", 1024, 170.0),
                           row("all_gather", "im", 2048, 10.0)])
    assert run_mod.check_against(seed, ok, 2.0) == []
    bad = write("bad.json", [row("all_reduce", "im", 1024, 500.0),
                             row("all_gather", "im", 2048, 50.0)])
    failures = run_mod.check_against(seed, bad, 2.0)
    assert len(failures) == 1 and "all_reduce/im/1024" in failures[0]
    # the tolerance is against the *best* seed row for the key (90, not 100)
    edge = write("edge.json", [row("all_reduce", "im", 1024, 185.0),
                               row("all_gather", "im", 2048, 50.0)])
    assert len(run_mod.check_against(seed, edge, 2.0)) == 1
    # dropped coverage warns (stderr) but does not fail the gate
    sparse = write("sparse.json", [row("all_reduce", "im", 1024, 100.0)])
    assert run_mod.check_against(seed, sparse, 2.0) == []


# ----------------------------------------------------------- live tuning
def test_live_overlap_sweep_and_measured_program_plan(tmp_path, cube_ring8):
    """End to end on the live substrate: tune (with the overlap sweep) ->
    reload -> a multi-op program's joint plan prices its budget from the
    measured models, and the overlap section actually drove the wave
    pricing (seconds <= serial with measured provenance)."""
    samples = microbench.overlap_sweep(cube_ring8, sizes=(16 * 1024,),
                                       reps=2, warmup=1)
    assert [s.dom_a for s in samples] == ["ici"]     # single-domain cube
    assert all(0.0 <= s.factor() <= 1.0 for s in samples)

    tuner = Tuner(cache_dir=tmp_path)
    prof = tuner.tune(cube_ring8, sizes=(8192,),
                      primitives=("all_reduce", "all_gather"),
                      reps=2, warmup=1, overlap_sizes=(8192,))
    assert prof.has_overlap
    reloaded = tuner.load(cube_ring8)                # fingerprint-checked
    assert reloaded.overlap == prof.overlap

    with planner.install_profile(reloaded):
        low = _twin_program(cube_ring8).lower()
    assert low.plan.est_source == "measured"
    assert low.plan.seconds <= low.plan.serial_seconds + 1e-12
    assert "est_source=measured" in low.describe()

    # per-op-only tunes remain possible (partial sweep, no overlap)
    t2 = Tuner(cache_dir=tmp_path / "no-ov")
    p2 = t2.tune(cube_ring8, sizes=(8192,), primitives=("all_reduce",),
                 reps=2, warmup=1, overlap=False)
    assert not p2.has_overlap
