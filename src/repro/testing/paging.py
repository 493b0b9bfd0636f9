"""Pure-NumPy oracle for the paged KV cache (:mod:`repro.serving.pages`).

Mirrors the host-side page-table semantics (shard-local block ownership,
lazy allocation, LIFO free lists, full-footprint admission math) and the
device-side view reconstruction (gather of a shard's local pages into its
contiguous cache extent, zero-filled where unallocated) with nothing but
NumPy, so the differential tests can check the jax implementation --
including the bit-identity of paged decode -- against an independently
written reference.
"""
from __future__ import annotations

import numpy as np


class PageTableOracle:
    """Reference page table: identical observable behaviour to
    ``repro.serving.pages.PageTable`` (same allocation order, same free-list
    discipline), implemented independently and minimally."""

    def __init__(self, page_size: int, pages_per_shard: int, n_shards: int,
                 S_cache: int, max_slots: int):
        if (S_cache // n_shards) % page_size:
            raise ValueError("page_size must divide the per-shard extent")
        self.page_size = page_size
        self.pages_per_shard = pages_per_shard
        self.n_shards = n_shards
        self.S_loc = S_cache // n_shards
        self.blocks_per_shard = self.S_loc // page_size
        self.n_blocks = self.blocks_per_shard * n_shards
        self.table = np.full((max_slots, self.n_blocks), -1, np.int32)
        self.free = [list(range(pages_per_shard - 1, -1, -1))
                     for _ in range(n_shards)]

    def owner(self, block: int) -> int:
        return block // self.blocks_per_shard

    def ensure(self, slot: int, cache_pos: int) -> bool:
        j = int(cache_pos) // self.page_size
        if self.table[slot, j] >= 0:
            return True
        if not self.free[self.owner(j)]:
            return False
        self.table[slot, j] = self.free[self.owner(j)].pop()
        return True

    def free_slot(self, slot: int) -> int:
        n = 0
        for j in range(self.n_blocks):
            if self.table[slot, j] >= 0:
                self.free[self.owner(j)].append(int(self.table[slot, j]))
                self.table[slot, j] = -1
                n += 1
        return n

    def blocks_needed(self, n_positions: int) -> list[int]:
        nb = min(-(-int(n_positions) // self.page_size), self.n_blocks)
        need = [0] * self.n_shards
        for j in range(nb):
            need[self.owner(j)] += 1
        return need

    def can_admit(self, n_positions: int) -> bool:
        return all(len(f) >= n for f, n in zip(self.free,
                                               self.blocks_needed(n_positions)))


def paged_view(pool: np.ndarray, table: np.ndarray, shard: int,
               page_size: int, blocks_per_shard: int) -> np.ndarray:
    """Reference for ``pages.gather_view``: one shard's local pool
    ``(n_units, pool_pages, page_size, *tail)`` plus the **global** table
    ``(B, n_blocks)`` -> that shard's contiguous ``(n_units, B, S_loc, *tail)``
    cache view, zeros where a block is unallocated (``gather_view`` gives
    one unit of it)."""
    n_units = pool.shape[0]
    tail = pool.shape[3:]
    B = table.shape[0]
    S_loc = blocks_per_shard * page_size
    out = np.zeros((n_units, B, S_loc) + tail, pool.dtype)
    myt = table[:, shard * blocks_per_shard:(shard + 1) * blocks_per_shard]
    for b in range(B):
        for jj in range(blocks_per_shard):
            pid = int(myt[b, jj])
            if pid >= 0:
                out[:, b, jj * page_size:(jj + 1) * page_size] = pool[:, pid]
    return out


__all__ = ["PageTableOracle", "paged_view"]
