"""Paged KV cache correctness: the host page table against the pure-NumPy
oracle, the device layer view and row write against the NumPy paged view,
the rooted-collective swap round-trip, one step's writes against the
contiguous step's, the step's temporary against depth, and -- the headline
guarantee -- paged decode bit-identical (bf16) / close (int8) to the
contiguous-cache ``Server.decode_shard`` across architectures, including a
rolling-window cache and a multi-shard (tp=2) kv group."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.configs import get
from repro.launch.mesh import make_mesh
from repro.models.params import init_params, param_specs
from repro.models.serving import (
    Server, cache_specs, init_cache, make_serve_plan)
from repro.models.topology import build_serve_topology
from repro.serving.pages import (
    PAGED_KEYS, PagedServer, PageTable, extract_slot_pages, gather_view,
    init_paged_cache, inject_slot_pages, local_block_ids, make_page_plan,
    paged_cache_specs, row_targets, write_rows)
from repro.testing.paging import PageTableOracle, paged_view


# --------------------------------------------------- table vs NumPy oracle
def test_page_table_matches_oracle():
    """Random ensure/free/admit interleavings: every observable (tables,
    free lists, return values, admission math) must match the independent
    NumPy implementation step for step."""
    rng = np.random.RandomState(0)
    page, pps, nsh, S_cache, slots = 4, 5, 2, 32, 3
    impl = _table(page, pps, nsh, S_cache, slots)
    orac = PageTableOracle(page, pps, nsh, S_cache, slots)
    for t in range(400):
        r = rng.rand()
        if r < 0.6:
            s = rng.randint(slots)
            p = rng.randint(S_cache)
            assert impl.ensure(s, p) == orac.ensure(s, p), (t, s, p)
        elif r < 0.8:
            s = rng.randint(slots)
            assert impl.free_slot(s) == orac.free_slot(s), (t, s)
        else:
            n = rng.randint(1, S_cache + 4)
            assert impl.blocks_needed(n) == orac.blocks_needed(n)
            assert impl.can_admit(n) == orac.can_admit(n)
        assert np.array_equal(impl.table, orac.table), t
        assert [list(f) for f in impl.free] == orac.free, t


def test_read_share_counts_allocated_blocks_below_each_position():
    """``PageTable.read_share`` against a loop over the table: allocated
    blocks holding a slot below the lane's position, over all blocks."""
    rng = np.random.RandomState(2)
    page, nsh, S_cache, slots = 4, 2, 32, 5
    impl = _table(page, 40, nsh, S_cache, slots)
    for _ in range(60):
        impl.ensure(rng.randint(slots), rng.randint(S_cache))
    impl.free_slot(3)
    for _ in range(20):
        pos = rng.randint(0, S_cache + 8, slots)
        want = sum(impl.table[b, j] >= 0 and j * page < pos[b]
                   for b in range(slots) for j in range(S_cache // page))
        assert impl.read_share(pos) == want / (slots * S_cache // page)


def _table(page, pps, nsh, S_cache, slots):
    from repro.serving.pages import PagePlan
    S_loc = S_cache // nsh
    pplan = PagePlan(page_size=page, pages_per_shard=pps, n_shards=nsh,
                     S_loc=S_loc, blocks_per_shard=S_loc // page,
                     n_blocks=(S_loc // page) * nsh)
    return PageTable(pplan, slots)


# ------------------------------------------- layer view, row write vs NumPy
def test_gather_view_matches_numpy_oracle():
    rng = np.random.RandomState(1)
    page, pps, nsh, S_cache, B = 4, 6, 2, 32, 3
    impl = _table(page, pps, nsh, S_cache, B)
    pplan = impl.pplan
    # allocate a random subset of blocks
    for s in range(B):
        for p in rng.choice(S_cache, size=rng.randint(2, S_cache),
                            replace=False):
            impl.ensure(s, int(p))
    table = jnp.asarray(impl.array())
    for shard in range(nsh):
        pool = rng.randn(2, pplan.pool_pages, page, 5).astype(np.float32)
        pool[:, pplan.pages_per_shard] = 0      # the scratch page, as kept
        safe, valid = local_block_ids(pplan, table, shard)
        want = paged_view(pool, impl.array(), shard, page,
                          pplan.blocks_per_shard)
        for u in range(2):
            got = np.asarray(gather_view(jnp.asarray(pool), u, safe, pplan))
            assert np.array_equal(got, want[u]), (shard, u)
        # write_rows at row_targets is gather_view's right inverse on
        # allocated blocks: a lane's row reads back at its slot, the rest
        # of every view is unchanged, and rows with no page are dropped
        slot = rng.randint(S_cache, size=B)
        loc = slot - shard * pplan.S_loc
        in_rng = (loc >= 0) & (loc < pplan.S_loc)
        idx = np.clip(loc, 0, pplan.S_loc - 1)
        pg, off = row_targets(pplan, safe, valid, jnp.asarray(idx),
                              jnp.asarray(in_rng))
        rows = rng.randn(2, B, 5).astype(np.float32)
        back = write_rows(jnp.asarray(pool), jnp.asarray(rows), pg, off)
        want_back = want.copy()
        for b in range(B):
            if in_rng[b] and impl.table[b, slot[b] // page] >= 0:
                want_back[:, b, idx[b]] = rows[:, b]
        for u in range(2):
            re = np.asarray(gather_view(back, u, safe, pplan))
            assert np.array_equal(re, want_back[u]), (shard, u)
        assert not np.asarray(back)[:, pplan.pages_per_shard].any()


# ------------------------------------- paged decode vs contiguous decode
def _zero_scratch(pcache, pplan):
    """Zero every shard's scratch page, as the zero init leaves it and
    decode keeps it."""
    ids = jnp.asarray([sh * pplan.pool_pages + pplan.pages_per_shard
                       for sh in range(pplan.n_shards)])
    return {pk: {k: leaf.at[:, ids].set(0) if k in PAGED_KEYS else leaf
                 for k, leaf in d.items()}
            for pk, d in pcache.items()}


def _random_pools(cfg, topo, plan, pplan, rng, scale=1.0):
    """Pools of random values, but for the zero scratch pages."""
    return _zero_scratch(jax.tree.map(
        lambda z: jnp.asarray(rng.randn(*z.shape).astype(np.float32) * scale
                              ).astype(z.dtype),
        init_paged_cache(cfg, topo, plan, pplan)), pplan)


def _paged_step_fn(cfg, topo, plan, pplan, paged):
    ba = plan.batch_axes or None
    cspec = paged_cache_specs(cfg, topo, plan, pplan)
    return jax.jit(shard_map(
        paged.decode_shard, mesh=topo.cube.mesh,
        in_specs=(param_specs(cfg, topo), cspec, P(), P(ba), P(ba)),
        out_specs=(P(ba, topo.tp), cspec), check_vma=False))


def _contig_step_fn(cfg, topo, plan, server):
    ba = plan.batch_axes or None
    cspec = cache_specs(cfg, topo, plan)
    return jax.jit(shard_map(
        server.decode_shard, mesh=topo.cube.mesh,
        in_specs=(param_specs(cfg, topo), cspec, P(ba), P(ba)),
        out_specs=(P(ba, topo.tp), cspec), check_vma=False))


def _run_diff(arch, *, tp=1, cache_dtype="bf16", S=16, B=2):
    """Teacher-forced decode, paged vs contiguous, step by step.  Returns
    the worst absolute logits difference (0.0 = bit-identical)."""
    cfg = get(arch).scaled_for_smoke()
    if tp > 1:
        cfg = dataclasses.replace(cfg, tp=tp)
    mesh = make_mesh((1, tp), ("data", "model"))
    topo = build_serve_topology(cfg, mesh)
    plan = make_serve_plan(cfg, topo, S_ctx=S, global_batch=B,
                           cache_dtype=cache_dtype)
    pplan = make_page_plan(plan, topo, page_size=4)
    params = init_params(cfg, topo, seed=1)
    server = Server(cfg, topo, plan)
    paged = PagedServer(server, pplan)

    cache = init_cache(cfg, topo, plan)
    pcache = init_paged_cache(cfg, topo, plan, pplan)
    tbl = PageTable(pplan, B)
    step_c = _contig_step_fn(cfg, topo, plan, server)
    step_p = _paged_step_fn(cfg, topo, plan, pplan, paged)

    rng = np.random.RandomState(7)
    tokens = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    worst = 0.0
    for t in range(S):
        for b in range(B):
            assert tbl.ensure(b, t % plan.S_cache)
        pos = jnp.full((B,), t, jnp.int32)
        tok = jnp.asarray(tokens[:, t])
        ref, cache = step_c(params, cache, tok, pos)
        got, pcache = step_p(params, pcache, jnp.asarray(tbl.array()),
                             tok, pos)
        worst = max(worst, float(np.abs(np.asarray(got)
                                        - np.asarray(ref)).max()))
    return worst


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "gemma3-1b",
                                  "mixtral-8x7b"])
def test_paged_decode_bit_identical_bf16(arch):
    """bf16 caches: the paged path reconstructs the exact contiguous view
    and runs the unchanged flash-decode cell, so logits must be bitwise
    equal -- incl. mixtral's rolling window-8 cache (block reuse on wrap)."""
    assert _run_diff(arch) == 0.0


def test_paged_decode_bit_identical_multishard():
    """tp=2 kv group: per-shard page pools, shard-local block ownership."""
    assert _run_diff("qwen3-1.7b", tp=2) == 0.0


def test_paged_decode_int8_close():
    """int8 KV cache: quantization happens on identical values in both
    layouts, so the paths still agree tightly."""
    assert _run_diff("qwen3-1.7b", cache_dtype="int8") < 1e-5


@pytest.mark.parametrize("tp,cache_dtype", [(1, "bf16"), (1, "int8"),
                                             (2, "bf16"), (2, "int8")])
def test_paged_step_writes_one_row_per_lane(tp, cache_dtype):
    """One paged step changes each pool only at the (page, offset) where an
    active lane's slot lands on its owner shard, with the value the
    contiguous step writes there; every other element, the scratch pages
    and int8 scales included, keeps its bits."""
    cfg = dataclasses.replace(get("qwen3-1.7b").scaled_for_smoke(), tp=tp)
    mesh = make_mesh((1, tp), ("data", "model"))
    topo = build_serve_topology(cfg, mesh)
    B = 3
    plan = make_serve_plan(cfg, topo, S_ctx=16, global_batch=B,
                           cache_dtype=cache_dtype)
    pplan = make_page_plan(plan, topo, page_size=4)
    server = Server(cfg, topo, plan)
    params = init_params(cfg, topo, seed=1)
    tbl = PageTable(pplan, B)
    # lane 0 writes slot 5 (block 1), lane 1 slot 10 (block 2, the second
    # shard's under tp=2), lane 2 is idle: nothing allocated
    pos_h = np.array([5, 10, 3], np.int32)
    for t in range(6):
        assert tbl.ensure(0, t)
    for t in range(11):
        assert tbl.ensure(1, t)
    rng = np.random.RandomState(5)
    pcache = _random_pools(cfg, topo, plan, pplan, rng, scale=3.0)

    # the contiguous cache holding the same views (NumPy oracle)
    def contiguous(leaf):
        leaf = np.asarray(leaf)
        return np.concatenate([
            paged_view(leaf[:, sh * pplan.pool_pages:
                            (sh + 1) * pplan.pool_pages],
                       tbl.array(), sh, pplan.page_size,
                       pplan.blocks_per_shard)
            for sh in range(pplan.n_shards)], axis=2)

    cache = jax.tree.map(lambda z: jnp.asarray(contiguous(z)), pcache)
    tok = jnp.asarray(rng.randint(0, cfg.vocab_size, B).astype(np.int32))
    pos = jnp.asarray(pos_h)
    _, want = _contig_step_fn(cfg, topo, plan, server)(
        params, cache, tok, pos)
    _, got = _paged_step_fn(cfg, topo, plan, pplan,
                            PagedServer(server, pplan))(
        params, pcache, jnp.asarray(tbl.array()), tok, pos)

    written = []                      # (global page, offset, lane, slot)
    for b, slot in enumerate(pos_h):
        j = slot // pplan.page_size
        pid = tbl.table[b, j]
        if pid >= 0:
            gp = pplan.owner(j) * pplan.pool_pages + pid
            written.append((gp, slot % pplan.page_size, b, slot))
    assert len(written) == 2
    for pk, d in pcache.items():
        for k in d:
            if k not in PAGED_KEYS:
                continue
            before = np.asarray(pcache[pk][k])
            after = np.asarray(got[pk][k])
            expect = before.copy()
            for gp, o, b, slot in written:
                expect[:, gp, o] = np.asarray(want[pk][k])[:, b, slot]
            assert np.array_equal(after, expect), (pk, k)


def _step_temp_bytes(n_layers, *, B, S_ctx):
    """The compiled engine step's temporary bytes for the smoke qwen3 at
    ``n_layers`` over an int8 cache, and one layer's k+v view with its
    scales.  (XLA's CPU backend widens a bf16 pool to f32 as a whole, which
    would hide what the step itself holds.)"""
    from repro.serving.engine import make_step
    cfg = dataclasses.replace(get("qwen3-1.7b").scaled_for_smoke(),
                              n_layers=n_layers)
    topo = build_serve_topology(cfg, make_mesh((1, 1), ("data", "model")))
    plan = make_serve_plan(cfg, topo, S_ctx=S_ctx, global_batch=B,
                           cache_dtype="int8")
    pplan = make_page_plan(plan, topo, page_size=4)
    pcache = init_paged_cache(cfg, topo, plan, pplan)
    S = jax.ShapeDtypeStruct
    i32 = lambda *s: S(s, jnp.int32)
    args = (init_params(cfg, topo, seed=1), pcache,
            i32(B, pplan.n_blocks), i32(B), i32(B), S((B,), jnp.bool_),
            i32(B, S_ctx), S((B,), jnp.bool_), i32(B), i32(B),
            i32(B, S_ctx), i32(B), S((B,), jnp.bool_),
            S((B,), jnp.float32), S((2,), jnp.uint32))
    compiled = make_step(cfg, topo, plan, pplan).lower(*args).compile()
    view = sum(B * plan.S_cache * int(np.prod(leaf.shape[3:]))
               * leaf.dtype.itemsize
               for d in pcache.values() for k, leaf in d.items()
               if k in PAGED_KEYS)
    return compiled.memory_analysis().temp_size_in_bytes, view


def test_paged_step_temp_does_not_grow_with_depth():
    """The engine step reads one layer's view at a time and writes rows,
    so two more layers add less than one layer's view of temporary
    (gathering the whole view added four per layer)."""
    t2, view = _step_temp_bytes(2, B=8, S_ctx=512)
    t4, _ = _step_temp_bytes(4, B=8, S_ctx=512)
    assert t4 - t2 < view, (t2, t4, view)


# ------------------------------------------------- swap-out / swap-in
def test_swap_roundtrip_restores_views():
    """extract (rooted gather) -> free -> re-allocate -> inject (rooted
    scatter + broadcast): every shard's reconstructed cache view for the
    swapped slot must come back bit-identical; other slots untouched."""
    cfg = dataclasses.replace(get("qwen3-1.7b").scaled_for_smoke(), tp=2)
    mesh = make_mesh((1, 2), ("data", "model"))
    topo = build_serve_topology(cfg, mesh)
    plan = make_serve_plan(cfg, topo, S_ctx=16, global_batch=2)
    pplan = make_page_plan(plan, topo, page_size=4)
    tbl = PageTable(pplan, 2)
    rng = np.random.RandomState(3)
    pcache = _random_pools(cfg, topo, plan, pplan, rng)
    for b in range(2):
        for t in range(0, 12):          # partial footprint: blocks 0..2
            tbl.ensure(b, t)

    def views(pc, slot):
        out = {}
        table = jnp.asarray(tbl.array())
        for shard in range(pplan.n_shards):
            safe, valid = local_block_ids(pplan, table, shard)
            lo = shard * pplan.pool_pages
            for pk, d in pc.items():
                for k, leaf in d.items():
                    if k in PAGED_KEYS:
                        # gather_view takes the shard-LOCAL pool slice
                        lp = leaf[:, lo:lo + pplan.pool_pages]
                        v = jnp.stack([gather_view(lp, u, safe, pplan)
                                       for u in range(lp.shape[0])])
                        out[(shard, pk, k)] = np.asarray(v[:, slot])
                    else:
                        out[(shard, pk, k)] = np.asarray(leaf[:, slot])
        return out

    before0 = views(pcache, 0)
    row1 = tbl.table[1].copy()
    saved = extract_slot_pages(pcache, tbl.table[0], 0, pplan, topo, plan)
    tbl.free_slot(0)
    # scrub every usable page of the pools so restoration can't luck into
    # stale data
    pcache = _zero_scratch(jax.tree.map(lambda z: jnp.zeros_like(z) - 1,
                                        pcache), pplan)
    for j in np.nonzero(saved["valid"])[0]:
        assert tbl.ensure(0, int(j) * pplan.page_size)
    pcache = inject_slot_pages(pcache, saved, tbl.table[0], 0, pplan,
                               topo, plan)
    after0 = views(pcache, 0)
    for key in before0:
        assert np.array_equal(after0[key], before0[key]), key
    # slot 1's mapping is untouched by slot 0's swap cycle
    assert np.array_equal(tbl.table[1], row1)
