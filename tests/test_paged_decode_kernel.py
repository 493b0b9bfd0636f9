"""The paged decode kernel (``repro.kernels.attention.paged_decode``), run
in Pallas interpret mode, against the view path's arithmetic on the same
pools: a dense masked softmax over each lane's gathered view, keeping only
allocated slots below the lane's position. Then the whole paged decode step
taking the kernel, against the contiguous cache's step. Agreement is to
float32 rounding, not bitwise: the kernel sums the keys in other orders."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention import paged_decode
from repro.kernels.attention.paged_decode import (
    page_walk, paged_decode_attention)
from repro.models.layers import NEG_INF, decode_mask
from repro.serving.pages import PagePlan, PageTable
from repro.testing.paging import paged_view

RTOL = 2e-5


def _plan(page_size, n_shards, S_loc, pages_per_shard):
    bps = S_loc // page_size
    return PagePlan(page_size=page_size, pages_per_shard=pages_per_shard,
                    n_shards=n_shards, S_loc=S_loc, blocks_per_shard=bps,
                    n_blocks=bps * n_shards)


def _lanes(pplan, rng):
    """A page table with the edge cases, every lane's blocks allocated in
    an interleaved order from LIFO free lists that have seen frees, and
    each lane's position. Lanes: 0 idle (nothing allocated, position 9);
    1 at the last slot of shard 0; 2 at position 0, so its only key is its
    own; 3 long; 4 with an unallocated block below its position; 5 past
    shard 0 (every shard-0 slot is below it); 6 one key into a block."""
    S = pplan.S_loc * pplan.n_shards
    ps = pplan.page_size
    tbl = PageTable(pplan, 7)
    pos = np.array([9, pplan.S_loc - 1, 0, S - 3, 5 * ps + 2, S - 1,
                    2 * ps + 1], np.int32)
    # churn the free lists so page ids come back out of order
    for t in range(0, S, ps):
        tbl.ensure(6, t)
    tbl.free_slot(6)
    for t in range(S):
        for b in (1, 3, 5, 4, 6, 2):
            if t <= pos[b]:
                assert tbl.ensure(b, t)
    j_hole = 2
    pid = tbl.table[4, j_hole]
    tbl.free[pplan.owner(j_hole)].append(int(pid))
    tbl.table[4, j_hole] = -1
    return tbl.table.copy(), pos


def _reference(q, k_pool, v_pool, layer, table, pos, shard, pplan, window):
    """The view path's arithmetic on one shard's gathered view of one
    layer: (o unnormalised, m, l), float32."""
    bps, ps = pplan.blocks_per_shard, pplan.page_size
    kv = paged_view(np.asarray(k_pool, np.float32), table, shard, ps, bps)
    vv = paged_view(np.asarray(v_pool, np.float32), table, shard, ps, bps)
    kf, vf = jnp.asarray(kv[layer]), jnp.asarray(vv[layer])
    B, H, hd = q.shape
    KV = kf.shape[2]
    G = H // KV
    myt = table[:, shard * bps:(shard + 1) * bps]
    alloc = jnp.asarray(np.repeat(myt >= 0, ps, axis=1))     # (B, S_loc)
    dk = shard * pplan.S_loc + jnp.arange(pplan.S_loc)
    dq = jnp.asarray(pos)[:, None]
    ok = alloc & (dk[None] < dq) & decode_mask(dq, dk[None], window)
    s = jnp.einsum("bkgd,bskd->bkgs", q.reshape(B, KV, G, hd), kf)
    s = jnp.where(ok[:, None, None], s, NEG_INF).reshape(B, H, -1)
    m = s.max(-1)
    p = jnp.where(ok[:, None], jnp.exp(s - m[..., None]), 0.0)
    o = jnp.einsum("bkgs,bskd->bkgd", p.reshape(B, KV, G, -1), vf)
    return o.reshape(B, H, hd), m, p.sum(-1)


def _run(q, k_pool, v_pool, layer, table, pos, shard, pplan, window):
    bps = pplan.blocks_per_shard
    myt = jnp.asarray(table[:, shard * bps:(shard + 1) * bps])
    walk = page_walk(myt, jnp.asarray(pos), shard * pplan.S_loc,
                     pplan.page_size)
    # a python int window stays static, as a model's static window does
    static = {"window": window} if isinstance(window, int) else {}
    fn = jax.jit(functools.partial(paged_decode_attention, interpret=True,
                                   **static))
    return fn(q, k_pool, v_pool, jnp.int32(layer), walk=walk,
              **({} if static else {"window": window}))


def _inputs(H, KV, hd, pplan, dtype, seed):
    rng = np.random.RandomState(seed)
    table, pos = _lanes(pplan, rng)
    shape = (3, pplan.n_shards * pplan.pool_pages, pplan.page_size, KV, hd)
    pools = [jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(dtype)
             for _ in range(2)]
    q = jnp.asarray(rng.randn(len(pos), H, hd).astype(np.float32)
                    ) * hd ** -0.5
    return q, pools, table, pos


def _local(pool, pplan, shard):
    n = pplan.pool_pages
    return pool[:, shard * n:(shard + 1) * n]


def _close(got, want):
    o, m, l = (np.asarray(x) for x in got)
    wo, wm, wl = (np.asarray(x) for x in want)
    np.testing.assert_allclose(m, wm, rtol=RTOL, atol=RTOL)
    np.testing.assert_allclose(l, wl, rtol=RTOL, atol=RTOL)
    np.testing.assert_allclose(o, wo, rtol=RTOL,
                               atol=RTOL * max(1.0, np.abs(wo).max()))


# (page_size, H, KV, hd, window, dtype): GQA groups of 1, 2 and 8, no
# window, a static one and a traced one, bf16 pools as served and float32
CASES = [
    (4, 8, 8, 32, -1, jnp.bfloat16),
    (4, 8, 4, 32, 6, jnp.bfloat16),
    (4, 8, 1, 32, "traced", jnp.bfloat16),
    (8, 8, 8, 32, "traced", jnp.float32),
    (8, 8, 4, 32, -1, jnp.float32),
    (8, 8, 1, 32, 11, jnp.bfloat16),
    (4, 4, 4, 96, -1, jnp.bfloat16),
]


@pytest.mark.parametrize("ps,H,KV,hd,window,dtype", CASES)
def test_kernel_matches_view_arithmetic(ps, H, KV, hd, window, dtype):
    """One shard holding every slot: each lane's partial (o, m, l), the
    idle lane's and the empty one's included (m NEG_INF, l and o zero)."""
    pplan = _plan(ps, 1, 8 * ps, 60)
    q, (kp, vp), table, pos = _inputs(H, KV, hd, pplan, dtype, seed=ps + KV)
    win = jnp.int32(7) if window == "traced" else window
    got = _run(q, kp, vp, 2, table, pos, 0, pplan, win)
    want = _reference(q, kp, vp, 2, table, pos, 0, pplan,
                      7 if window == "traced" else window)
    _close(got, want)
    m, l = np.asarray(got[1]), np.asarray(got[2])
    assert (m[0] == NEG_INF).all() and (l[0] == 0).all()    # idle lane
    assert (m[2] == NEG_INF).all() and (l[2] == 0).all()    # position 0


@pytest.mark.parametrize("ps,window", [(4, -1), (8, "traced")])
def test_two_shards_combine_to_the_whole_cache(ps, window):
    """Two shards' partials, each over its own pool and slot range, LSE
    combined as the decode step combines them, equal the whole cache's
    attention on one shard."""
    H, KV, hd = 8, 2, 32
    two = _plan(ps, 2, 4 * ps, 40)
    q, (kp, vp), table, pos = _inputs(H, KV, hd, two, jnp.bfloat16, seed=3)
    win = jnp.int32(13) if window == "traced" else window
    parts = [_run(q, _local(kp, two, sh), _local(vp, two, sh), 1, table,
                  pos, sh, two, win) for sh in range(2)]
    m_all = jnp.maximum(parts[0][1], parts[1][1])
    w = [jnp.exp(m - m_all) for _, m, _ in parts]
    l = sum(p[2] * wi for p, wi in zip(parts, w))
    o = sum(p[0] * wi[..., None] for p, wi in zip(parts, w))

    # the same blocks on one shard of twice the extent, pages renumbered
    one = _plan(ps, 1, 8 * ps, 2 * two.pool_pages)
    renum = np.where(table >= 0, table + np.repeat(
        np.arange(2) * two.pool_pages, two.blocks_per_shard)[None], -1)
    scratch = jnp.zeros((3, 1) + kp.shape[2:], kp.dtype)
    wo, wm, wl = _reference(q, jnp.concatenate([kp, scratch], 1),
                            jnp.concatenate([vp, scratch], 1), 1, renum, pos,
                            0, one, 13 if window == "traced" else window)
    live = np.asarray(wl > 0)
    np.testing.assert_allclose(np.asarray(m_all)[live], np.asarray(wm)[live],
                               rtol=RTOL)
    norm = lambda o, l: np.asarray(o / jnp.maximum(l, 1e-30)[..., None])
    np.testing.assert_allclose(norm(o, l)[live], norm(wo, wl)[live],
                               rtol=RTOL, atol=RTOL)


def test_fits_follows_the_chip_tiling():
    """The shapes the chip's compiler takes a page DMA of (found on a
    described v5e): head_dim in whole 128-lane rows, two or more bf16 kv
    heads."""
    assert paged_decode.fits(8, 128, jnp.bfloat16)
    assert paged_decode.fits(2, 256, jnp.bfloat16)
    assert not paged_decode.fits(1, 256, jnp.bfloat16)
    assert not paged_decode.fits(32, 96, jnp.bfloat16)
    assert not paged_decode.fits(8, 64, jnp.bfloat16)


# ------------------------------------ the decode step through the kernel
@pytest.fixture
def kernel_path(monkeypatch):
    """Take the kernel path off the chip: the path choice without its
    platform test, and the kernel in interpret mode; float32 compute."""
    from repro.models import blocks, lm, params
    from repro.serving import pages
    for mod in (params, blocks, lm):
        monkeypatch.setattr(mod, "COMPUTE_DTYPE", jnp.float32)
    monkeypatch.setattr(
        pages, "reads_in_place",
        lambda topo, plan, pool: (plan.cache_dtype == "bf16"
                                  and plan.S_cache == plan.S_ctx))
    monkeypatch.setattr(pages, "paged_decode_attention", functools.partial(
        paged_decode_attention, interpret=True))


@pytest.mark.parametrize("arch,tp,layers", [("qwen3-1.7b", 1, 2),
                                            ("qwen3-1.7b", 2, 2),
                                            ("gemma3-1b", 1, 6)])
def test_paged_step_through_the_kernel_matches_contiguous(kernel_path, arch,
                                                          tp, layers):
    """Teacher-forced decode, the paged step on the kernel against the
    contiguous cache's step: qwen3 on one shard and on two (the kernel's
    partials LSE-combined over the kv group), and gemma3 at six layers,
    five with a local window of 8 and one global, so the cache does not
    roll and the windows are traced through the layer scan."""
    from test_paging import _contig_step_fn, _paged_step_fn
    from repro.configs import get
    from repro.launch.mesh import make_mesh
    from repro.models.params import init_params
    from repro.models.serving import Server, init_cache, make_serve_plan
    from repro.models.topology import build_serve_topology
    from repro.serving.pages import (
        PagedServer, init_paged_cache, make_page_plan)
    cfg = dataclasses.replace(get(arch).scaled_for_smoke(), tp=tp,
                              n_layers=layers)
    topo = build_serve_topology(cfg, make_mesh((1, tp), ("data", "model")))
    B, S = 2, 16
    plan = make_serve_plan(cfg, topo, S_ctx=S, global_batch=B)
    pplan = make_page_plan(plan, topo, page_size=4)
    params = init_params(cfg, topo, seed=1)
    server = Server(cfg, topo, plan)
    cache = init_cache(cfg, topo, plan)
    pcache = init_paged_cache(cfg, topo, plan, pplan)
    tbl = PageTable(pplan, B)
    step_c = _contig_step_fn(cfg, topo, plan, server)
    step_p = _paged_step_fn(cfg, topo, plan, pplan, PagedServer(server, pplan))
    rng = np.random.RandomState(7)
    tokens = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    for t in range(S):
        for b in range(B):
            assert tbl.ensure(b, t)
        pos = jnp.full((B,), t, jnp.int32)
        tok = jnp.asarray(tokens[:, t])
        want, cache = step_c(params, cache, tok, pos)
        if t == 0:
            assert "pallas_call" in str(jax.make_jaxpr(step_p)(
                params, pcache, jnp.asarray(tbl.array()), tok, pos))
        got, pcache = step_p(params, pcache, jnp.asarray(tbl.array()), tok,
                             pos)
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-5 * scale)
