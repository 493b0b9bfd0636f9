"""Continuous-batching engine: mixed-length arrival traces complete with the
per-step CommProgram served from the structural-fingerprint lower cache,
greedy outputs are batching-invariant, preemption round-trips through the
rooted-collective swap, and the restore-for-serving checkpoint path loads
train-cube params onto the serve topology."""
import dataclasses

import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get
from repro.core.program import LOWER_STATS, clear_lower_cache
from repro.launch.mesh import make_mesh
from repro.models.params import init_params, param_specs
from repro.models.serving import make_serve_plan
from repro.models.topology import build_serve_topology, build_topology
from repro.serving import Request, ServeEngine, poisson_trace


def _setup(B, *, tp=1, S_ctx=32, **eng_kw):
    cfg = get("qwen3-1.7b").scaled_for_smoke()
    if tp > 1:
        cfg = dataclasses.replace(cfg, tp=tp)
    mesh = make_mesh((1, tp), ("data", "model"))
    topo = build_serve_topology(cfg, mesh)
    plan = make_serve_plan(cfg, topo, S_ctx=S_ctx, global_batch=B)
    params = init_params(cfg, topo, seed=1)
    return cfg, ServeEngine(cfg, topo, plan, params, **eng_kw)


def _trace(cfg, n, seed=3, temperature=0.0):
    return poisson_trace(n, rate=1.0, plen_range=(3, 8),
                         max_new_range=(3, 6), vocab=cfg.vocab_size,
                         seed=seed, temperature=temperature)


def test_mixed_trace_completes_with_cached_programs():
    """The tentpole invariant: a mixed-length Poisson trace is served to
    completion with ONE recorded CommProgram per step, and every lowering
    after the first is a structural-fingerprint cache hit."""
    cfg, eng = _setup(3)
    reqs = _trace(cfg, 6)
    clear_lower_cache()
    before = dict(LOWER_STATS)
    m = eng.run(reqs)
    hits = LOWER_STATS["cache_hits"] - before["cache_hits"]
    lowered = LOWER_STATS["lowered"] - before["lowered"]
    assert m["programs_recorded"] == m["steps"]
    assert lowered == 1, "per-step program must lower exactly once"
    assert hits >= m["steps"] - 1
    assert len(m["finished"]) == 6
    for r in m["finished"]:
        assert len(r.out_tokens) == r.max_new, r.rid
        assert all(0 <= t < cfg.vocab_size for t in r.out_tokens)


def test_engine_observes_kv_read_share_every_step():
    """Each step observes ``serve.kv_read_share`` from the host page table
    (``PageTable.read_share``); off the chip the step gathers each layer's
    view, so ``serve.paged_kernel`` reads 0."""
    cfg, eng = _setup(3)
    m = eng.run(_trace(cfg, 5))
    h = eng.metrics.get("serve.kv_read_share")
    assert h.count == m["steps"]
    assert 0 < max(h.samples) <= 1 and min(h.samples) >= 0
    assert eng.metrics.value("serve.paged_kernel") == 0.0


def test_greedy_outputs_are_batching_invariant():
    """Each request decoded alone (B=1) must produce the same greedy tokens
    as the continuously-batched run -- slot assignment, paging and admission
    order cannot leak into the sampled stream."""
    cfg, eng = _setup(3)
    m = eng.run(_trace(cfg, 5))
    batched = {r.rid: list(r.out_tokens) for r in m["finished"]}
    _, solo = _setup(1)      # one engine, one compile; requests in sequence
    for proto in _trace(cfg, 5):
        alone = dataclasses.replace(proto, arrival=solo.step_idx)
        ms = solo.run([alone])
        assert list(ms["finished"][-1].out_tokens) == batched[proto.rid], \
            proto.rid


def test_preemption_swap_preserves_outputs():
    """Tight page pools under lazy admission force preemption; the swap
    round-trip (rooted gather out / scatter back) must not change any
    request's greedy continuation."""
    cfg, eng = _setup(3, tp=2)
    ref = {r.rid: list(r.out_tokens)
           for r in eng.run(_trace(cfg, 6))["finished"]}
    _, tight = _setup(3, tp=2, pages_per_shard=4, admission="lazy")
    m = tight.run(_trace(cfg, 6))
    assert m["preemptions"] > 0, "pools sized to force preemption"
    for r in m["finished"]:
        assert list(r.out_tokens) == ref[r.rid], r.rid


def test_temperature_sampling_and_slot_reuse():
    """Temperature sampling completes; more requests than lanes exercises
    slot reuse (every lane serves several requests)."""
    cfg, eng = _setup(2)
    m = eng.run(_trace(cfg, 6, temperature=0.8))
    assert len(m["finished"]) == 6
    for r in m["finished"]:
        assert len(r.out_tokens) == r.max_new
        assert all(0 <= t < cfg.vocab_size for t in r.out_tokens)


def test_engine_input_validation():
    cfg, eng = _setup(2, S_ctx=16)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(rid=0, prompt=[], max_new=2))
    with pytest.raises(ValueError, match="S_ctx"):
        eng.submit(Request(rid=1, prompt=[1] * 10, max_new=10))


def test_make_serve_plan_rejects_unknown_cache_dtype():
    cfg = get("qwen3-1.7b").scaled_for_smoke()
    mesh = make_mesh((1, 1), ("data", "model"))
    topo = build_serve_topology(cfg, mesh)
    with pytest.raises(ValueError, match="bf16.*int8"):
        make_serve_plan(cfg, topo, S_ctx=8, global_batch=1,
                        cache_dtype="fp8")


def test_restore_for_serving(tmp_path):
    """Params saved on the train cube restore straight onto the serve
    topology (sectioned manifest, no opt-state skeleton, device_put with
    the serve-side specs) and the engine decodes with them."""
    cfg = dataclasses.replace(get("qwen3-1.7b").scaled_for_smoke(), tp=2)
    train_topo = build_topology(cfg, make_mesh((1, 2), ("data", "model")))
    params = init_params(cfg, train_topo, seed=4)
    opt = {"m": np.zeros(3, np.float32), "count": np.int32(0)}
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(7, params, opt)

    stopo = build_serve_topology(cfg, make_mesh((1, 2), ("data", "model")))
    sspecs = param_specs(cfg, stopo)
    restored = mgr.restore_params(7, params, topo=stopo, param_specs=sspecs)
    # values survive the re-shard bit-exactly
    import jax
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    plan = make_serve_plan(cfg, stopo, S_ctx=16, global_batch=2)
    eng = ServeEngine(cfg, stopo, plan, restored)
    m = eng.run([Request(rid=0, prompt=[5, 6, 7], max_new=3)])
    assert len(m["finished"][0].out_tokens) == 3
    # architecture mismatch is a clear error, not leaf-offset garbage
    with pytest.raises(ValueError, match="params leaves"):
        mgr.restore_params(7, {"w": np.zeros(2)})
