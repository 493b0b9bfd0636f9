"""Paged/block KV cache: fixed-size KV blocks behind a per-request page
table, with cross-cube page exchange expressed as rooted scatter/gather
collectives on the serve topology.

The contiguous decode cache (``repro.models.serving.cache_defs``) allocates
``S_cache`` slots per request up front; a paged cache carves the same slot
space into fixed-size **blocks** (``page_size`` slots) drawn from per-shard
physical page pools, so short requests hold only the pages they touched and
freed pages are immediately reusable by the next admission (slot reuse,
continuous batching).

Layout invariants that make paged decode *bit-identical* to the contiguous
reference:

  * logical block ``j`` of any request covers cache slots
    ``[j*page_size, (j+1)*page_size)`` and is **owned** by the kv shard whose
    contiguous slot range contains it (``owner(j) = j // blocks_per_shard``).
    Allocation never crosses that boundary, so each shard can materialize its
    exact contiguous ``(B, S_loc, ...)`` cache view from purely local pages;
  * each shard's pool carries one extra **scratch** page, which unallocated
    blocks read.  It starts zero and decode never writes it: a row whose
    block is unallocated (an idle batch lane) or owned by another shard is
    dropped.  So the view reads zeros there, matching the zero-init of the
    contiguous cache; stale data in a *reallocated* page sits at key
    positions the flash-decode mask already excludes (causality / ``dk >= 0``
    under rolling), so it never reaches a logit.

``PagedServer.decode_shard`` runs the *unchanged* ``Server.decode_shard``
flash-decode cell with two per-layer hooks: inside the layer scan each
layer reads its keys from the pools; the scan emits only the row each lane
wrote at that layer, and one scatter after the scan writes those rows into
the pools at ``(page, slot % page_size)``.  No whole-cache view is ever
materialised.  Where the pools live on TPU, hold the compute dtype and the
cache is not rolling (:func:`reads_in_place`), a layer's keys are read in
place by the paged decode kernel
(``repro.kernels.attention.paged_decode``), which walks only each lane's
allocated blocks below its position.  Elsewhere (the int8 layout, rolling
caches, every other backend) each layer gathers its ``(B, S_loc)`` view,
and the bf16 differential test asserts bitwise equality against the
contiguous path.

Page exchange across the cube boundary (preemption/swap in the engine, or
any host-mediated migration) is the rooted-collective pair of paper
SIV-B3: ``extract_slot_pages`` gathers a request's blocks PEs -> host
(``comm.gather``), ``inject_slot_pages`` partitions them back host -> PEs
along the block axis in owner order (``comm.scatter``), plus a broadcast
for the per-request recurrent-state rows (SSM/RWKV) that are not paged.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.kernels.attention.paged_decode import (
    PageWalk, fits, page_walk, paged_decode_attention)
from repro.models.blocks import decode_slot
from repro.models.config import ModelConfig
from repro.models.serving import ServePlan, Server, cache_defs
from repro.models.topology import Topology

Array = jax.Array

# cache-tree keys that live in page pools; everything else (SSM states,
# conv tails, token-shift carries, encoder-decoder cross K/V) stays a
# per-slot row exactly as in the contiguous layout
PAGED_KEYS = ("k", "v", "k_s", "v_s")


@dataclasses.dataclass(frozen=True)
class PagePlan:
    """Static geometry of the page pools for one (ServePlan, topology)."""
    page_size: int           # cache slots per block/page
    pages_per_shard: int     # usable physical pages per kv shard
    n_shards: int            # size of the kv group (plan.kv_axes)
    S_loc: int               # contiguous slots per shard (= S_cache / n)
    blocks_per_shard: int    # logical blocks of one request per shard
    n_blocks: int            # logical blocks per request (= S_cache / page)

    @property
    def pool_pages(self) -> int:
        """Physical page-axis extent per shard (usable + 1 scratch, which
        stays zero)."""
        return self.pages_per_shard + 1

    @property
    def n_pages_global(self) -> int:
        return self.n_shards * self.pool_pages

    def owner(self, block: int) -> int:
        """The kv shard whose contiguous slot range covers ``block``."""
        return block // self.blocks_per_shard


def make_page_plan(plan: ServePlan, topo: Topology, *, page_size: int = 4,
                   pages_per_shard: int | None = None) -> PagePlan:
    """Derive the page geometry. ``page_size`` must divide the per-shard
    cache extent so no block straddles a shard boundary; the default pool
    capacity covers every slot of every request (no paging pressure) --
    shrink ``pages_per_shard`` to exercise admission control/preemption."""
    n = topo.size(plan.kv_axes)
    S_loc = plan.S_cache // n
    if S_loc % page_size:
        raise ValueError(
            f"page_size {page_size} does not divide the per-shard cache "
            f"extent {S_loc} (S_cache {plan.S_cache} over {n} shards); "
            "pick a divisor so no block straddles a shard boundary")
    blocks_per_shard = S_loc // page_size
    if pages_per_shard is None:
        pages_per_shard = blocks_per_shard * plan.global_batch
    return PagePlan(page_size=page_size, pages_per_shard=pages_per_shard,
                    n_shards=n, S_loc=S_loc,
                    blocks_per_shard=blocks_per_shard,
                    n_blocks=blocks_per_shard * n)


# ------------------------------------------------------------- pool layout
def paged_cache_defs(cfg: ModelConfig, topo: Topology, plan: ServePlan,
                     pplan: PagePlan):
    """Like :func:`repro.models.serving.cache_defs`, with the attention K/V
    entries (and int8 scales) re-laid as page pools: the per-request
    ``(B, S_cache)`` slot axes become a shared ``(n_pages_global, page_size)``
    pool sharded over the kv axes along the page axis."""
    defs = cache_defs(cfg, topo, plan)
    out = {}
    for pkey, d in defs.items():
        nd = {}
        for k, (shp, spec, dt) in d.items():
            if k in PAGED_KEYS:
                # (n_units, B, S_cache, *tail) -> (n_units, pages, page, *tail)
                tail = shp[3:]
                nd[k] = ((shp[0], pplan.n_pages_global, pplan.page_size)
                         + tail,
                         P(None, plan.kv_axes, None, *([None] * len(tail))),
                         dt)
            else:
                nd[k] = (shp, spec, dt)
        out[pkey] = nd
    return out


def _is_def(x):
    return isinstance(x, tuple) and isinstance(x[0], tuple)


def paged_cache_specs(cfg, topo, plan, pplan):
    return jax.tree.map(lambda d: d[1],
                        paged_cache_defs(cfg, topo, plan, pplan),
                        is_leaf=_is_def)


def init_paged_cache(cfg, topo, plan, pplan):
    """Zero pools (smoke-scale only; a reallocated page is *not* re-zeroed
    at runtime -- the flash-decode mask makes that unnecessary)."""
    return jax.tree.map(lambda d: jnp.zeros(d[0], d[2]),
                        paged_cache_defs(cfg, topo, plan, pplan),
                        is_leaf=_is_def)


# ------------------------------------------------- host-side page table
class PageTable:
    """Per-request page table + per-shard LIFO free lists (host side).

    ``table[slot, j]`` is the *local* page index of logical block ``j`` on
    its owner shard, or -1 while unallocated.  Blocks allocate lazily as a
    request's write position crosses a block boundary (``ensure``) and free
    as a batch on eviction (``free_slot``).
    """

    def __init__(self, pplan: PagePlan, max_slots: int):
        self.pplan = pplan
        self.max_slots = max_slots
        self.table = np.full((max_slots, pplan.n_blocks), -1, np.int32)
        # LIFO free lists: the page freed last is reused first, which keeps
        # the stale-data window (masked anyway) as short as possible
        self.free = [list(range(pplan.pages_per_shard - 1, -1, -1))
                     for _ in range(pplan.n_shards)]

    # -------------------------------------------------------- allocation
    def block_of(self, cache_pos: int) -> int:
        return int(cache_pos) // self.pplan.page_size

    def ensure(self, slot: int, cache_pos: int) -> bool:
        """Allocate the block covering ``cache_pos`` (a slot index within
        ``S_cache``; the caller applies any rolling modulus).  Returns False
        when the owner shard's free list is empty (admission control /
        preemption territory) without partial effects."""
        j = self.block_of(cache_pos)
        if self.table[slot, j] >= 0:
            return True
        sh = self.pplan.owner(j)
        if not self.free[sh]:
            return False
        self.table[slot, j] = self.free[sh].pop()
        return True

    def free_slot(self, slot: int) -> int:
        """Return every page of ``slot`` to its shard free list."""
        n = 0
        for j in range(self.pplan.n_blocks):
            pid = int(self.table[slot, j])
            if pid >= 0:
                self.free[self.pplan.owner(j)].append(pid)
                self.table[slot, j] = -1
                n += 1
        return n

    # ---------------------------------------------------------- capacity
    def free_per_shard(self) -> list[int]:
        return [len(f) for f in self.free]

    def blocks_needed(self, n_positions: int) -> list[int]:
        """Per-shard block count covering cache slots ``0..n_positions-1``
        (capped at the full cache extent)."""
        pp = self.pplan
        nb = min(-(-int(n_positions) // pp.page_size), pp.n_blocks)
        need = [0] * pp.n_shards
        for j in range(nb):
            need[pp.owner(j)] += 1
        return need

    def can_admit(self, n_positions: int) -> bool:
        """True when every shard can cover the request's full eventual
        footprint -- the no-deadlock admission policy."""
        return all(f >= n for f, n in zip(self.free_per_shard(),
                                          self.blocks_needed(n_positions)))

    def read_share(self, pos: np.ndarray) -> float:
        """The share of all lanes' blocks that the paged decode kernel
        reads at cache positions ``pos`` (B,): allocated blocks holding a
        slot below the lane's position."""
        pp = self.pplan
        upto = -(-np.minimum(pos, pp.n_blocks * pp.page_size)
                 // pp.page_size)
        read = (self.table >= 0) & (np.arange(pp.n_blocks) < upto[:, None])
        return float(read.sum()) / read.size

    def array(self) -> np.ndarray:
        """Snapshot for the per-step replicated broadcast."""
        return self.table.copy()


# ------------------------------------------ per-shard layer view, row write
def local_block_ids(pplan: PagePlan, table: Array, shard: Array | int):
    """This shard's slice of the table: (safe local page ids, valid mask),
    both ``(B, blocks_per_shard)``.  Unallocated blocks map to the scratch
    page so the view's gather stays branch-free."""
    myt = lax.dynamic_slice_in_dim(
        table, shard * pplan.blocks_per_shard, pplan.blocks_per_shard,
        axis=1)
    valid = myt >= 0
    safe = jnp.where(valid, myt, pplan.pages_per_shard)
    return safe, valid


def gather_view(pool: Array, layer: Array | int, safe: Array,
                pplan: PagePlan) -> Array:
    """One unit of the local pool ``(n_units, pool_pages, page, *tail)`` ->
    the shard's contiguous cache view of that unit ``(B, S_loc, *tail)``.
    Unallocated blocks read the scratch page, which decode never writes,
    so they read as zeros (identical to the contiguous zero-init)."""
    g = pool[layer, safe.reshape(-1)]
    return g.reshape((safe.shape[0], pplan.S_loc) + pool.shape[3:])


def row_targets(pplan: PagePlan, safe: Array, valid: Array, idx: Array,
                in_rng: Array):
    """Where each lane's new row lands in the local pool: (page id, offset
    within the page), both ``(B,)``, from the lane's clipped local slot
    ``idx`` and whether its slot is on this shard (``in_rng``).  A row whose
    slot another shard owns, or whose block is unallocated (an idle lane),
    gets the page id ``pool_pages``, past the pool, and is dropped."""
    bidx = jnp.arange(safe.shape[0])
    blk = idx // pplan.page_size
    page = jnp.where(in_rng & valid[bidx, blk], safe[bidx, blk],
                     pplan.pool_pages)
    return page, idx % pplan.page_size


def write_rows(pool: Array, rows: Array, page: Array, off: Array) -> Array:
    """Write one row per lane and unit ``(n_units, B, *tail)`` into the
    local pool at ``(page, off)``, in place; rows whose page lies past the
    pool are dropped.  Allocated page ids are unique by construction, so no
    two rows collide.  The unit axis is indexed too, so every indexed axis
    leads and no backend transposes the pool to scatter into it."""
    units = jnp.arange(pool.shape[0])[:, None]
    return pool.at[units, page[None], off[None]].set(rows, mode="drop")


def reads_in_place(topo: Topology, plan: ServePlan, pool) -> bool:
    """Whether paged decode reads the K/V pool leaf ``pool`` in place with
    the paged decode kernel: the pools live on TPU devices, hold the
    compute dtype (not the int8 layout with its scales) in pages the chip
    can DMA whole, and the cache is not rolling, so a slot is a position.
    Every other case gathers each layer's view."""
    return (topo.cube.mesh.devices.flat[0].platform == "tpu"
            and plan.cache_dtype == "bf16" and plan.S_cache == plan.S_ctx
            and fits(pool.shape[3], pool.shape[4], pool.dtype))


class PagedLayer(NamedTuple):
    """One attention layer's K and V left in the page pools, as
    ``attn_decode`` finds them under ``c["pages"]``: this shard's whole pool
    leaves, the unit to read, the step's walk of every lane's blocks and
    the shard's slot extent."""
    k: Array
    v: Array
    layer: Array
    walk: PageWalk
    S_loc: int

    def attend(self, qf: Array, window):
        """This shard's partial ``(o, m, l)`` of the scaled queries ``qf``
        (B, H, hd) over each lane's keys below its position."""
        return paged_decode_attention(qf, self.k, self.v, self.layer,
                                      window, self.walk)


class PagedServer:
    """Paged decode cell: ``Server.decode_shard`` (unchanged flash-decode
    arithmetic) with hooks that read each layer's keys from the page pools
    inside the layer scan, in place with the paged decode kernel where
    :func:`reads_in_place` holds and from a gathered view elsewhere, and
    emit only the row each lane wrote; one scatter after the scan writes
    those rows into the pools.  Per-shard function; wrap in ``shard_map``
    with ``paged_cache_specs`` for the cache and a replicated spec for the
    page table."""

    def __init__(self, server: Server, pplan: PagePlan):
        self.server = server
        self.pplan = pplan

    def decode_shard(self, params, pcache, table, tokens: Array, pos: Array):
        """One paged decode step. ``table``: (B, n_blocks) int32 replicated.
        Returns (logits, new paged cache)."""
        pplan = self.pplan
        cfg, plan = self.server.cfg, self.server.plan
        me = lax.axis_index(plan.kv_axes)
        safe, valid = local_block_ids(pplan, table, me)
        my_lo, idx, in_rng = decode_slot(pos, pplan.S_loc, pplan.n_shards,
                                         plan.kv_axes,
                                         plan.S_cache < plan.S_ctx)
        bidx = jnp.arange(idx.shape[0])

        # the scan's per-unit input for a pooled leaf is the unit's index
        # into the pool: no slice of the pool is ever materialised
        units = jnp.arange(cfg.n_layers // cfg.unit())
        xs = {pkey: {k: units if k in PAGED_KEYS else leaf
                     for k, leaf in d.items()}
              for pkey, d in pcache.items()}
        in_place = {key: reads_in_place(self.server.topo, plan, d["k"])
                    for key, d in pcache.items() if "k" in d}
        if any(in_place.values()):
            walk = page_walk(jnp.where(valid, safe, -1), pos, my_lo,
                             pplan.page_size)

        def layer_cache(key, cin):
            if in_place.get(key):
                c = {k: v for k, v in cin.items() if k not in PAGED_KEYS}
                c["pages"] = PagedLayer(pcache[key]["k"], pcache[key]["v"],
                                        cin["k"], walk, pplan.S_loc)
                return c
            return {k: gather_view(pcache[key][k], v, safe, pplan)
                    if k in PAGED_KEYS else v for k, v in cin.items()}

        def layer_out(key, c):
            if "pages" in c:       # attn_decode left the new rows in k, v
                return {k: v for k, v in c.items() if k != "pages"}
            return {k: v[bidx, idx] if k in PAGED_KEYS else v
                    for k, v in c.items()}

        logits, out = self.server.decode_shard(
            params, xs, tokens, pos, layer_cache=layer_cache,
            layer_out=layer_out)
        page, off = row_targets(pplan, safe, valid, idx, in_rng)
        new = {}
        for pkey, d in pcache.items():
            new[pkey] = {
                k: write_rows(leaf, out[pkey][k], page, off)
                if k in PAGED_KEYS else out[pkey][k]
                for k, leaf in d.items()}
        return logits, new


# --------------------------------------------- cross-cube page exchange
def _global_page_ids(pplan: PagePlan, table_row: np.ndarray):
    """A request's blocks as global pool page ids, unallocated -> the owner
    shard's scratch page.  Returns (ids (n_blocks,), valid (n_blocks,))."""
    ids = np.empty(pplan.n_blocks, np.int32)
    valid = np.zeros(pplan.n_blocks, bool)
    for j in range(pplan.n_blocks):
        sh = pplan.owner(j)
        pid = int(table_row[j])
        valid[j] = pid >= 0
        ids[j] = sh * pplan.pool_pages + (pid if pid >= 0
                                          else pplan.pages_per_shard)
    return ids, valid


def extract_slot_pages(pcache, table_row: np.ndarray, slot: int,
                       pplan: PagePlan, topo: Topology, plan: ServePlan
                       ) -> dict:
    """Swap-out half of the page exchange: gather one request's pages (and
    its per-slot recurrent-state rows) PEs -> host through the rooted
    ``gather`` collective on the kv group.  The caller frees the pages
    afterwards; the returned dict round-trips through
    :func:`inject_slot_pages`."""
    kvc = topo.comm(plan.kv_axes)
    gids, valid = _global_page_ids(pplan, table_row)
    gidx = jnp.asarray(gids)
    pages, rows = {}, {}
    for pkey, d in pcache.items():
        for k, leaf in d.items():
            if k in PAGED_KEYS:
                taken = jnp.take(leaf, gidx, axis=1)
                host = np.array(kvc.gather(taken))
                host[:, ~valid] = 0          # no page holds these blocks
                pages[(pkey, k)] = host
            else:
                rows[(pkey, k)] = np.array(kvc.gather(leaf[:, slot]))
    return {"pages": pages, "rows": rows, "valid": valid}


def inject_slot_pages(pcache, saved: dict, table_row: np.ndarray, slot: int,
                      pplan: PagePlan, topo: Topology, plan: ServePlan):
    """Swap-in half: partition the saved pages back host -> PEs with the
    rooted ``scatter`` along the block axis (blocks sit in owner order, so
    the equal per-shard split lands each page on the shard that owns it),
    broadcast the per-slot state rows, and write both into the pools at the
    freshly allocated ids in ``table_row``."""
    kvc = topo.comm(plan.kv_axes)
    gids, _ = _global_page_ids(pplan, table_row)
    gidx = jnp.asarray(gids)
    bidx = jnp.asarray(int(slot))
    new = {pkey: dict(d) for pkey, d in pcache.items()}
    for (pkey, k), host in saved["pages"].items():
        dev = kvc.scatter(host, axis=1)
        new[pkey][k] = new[pkey][k].at[:, gidx].set(
            dev.astype(new[pkey][k].dtype))
    for (pkey, k), host in saved["rows"].items():
        dev = kvc.broadcast(host)
        new[pkey][k] = new[pkey][k].at[:, bidx].set(
            dev.astype(new[pkey][k].dtype))
    return new


__all__ = [
    "PAGED_KEYS", "PagePlan", "PageTable", "PagedLayer", "PagedServer",
    "extract_slot_pages", "gather_view", "init_paged_cache",
    "inject_slot_pages", "local_block_ids", "make_page_plan",
    "paged_cache_defs", "paged_cache_specs", "reads_in_place", "row_targets",
    "write_rows",
]
