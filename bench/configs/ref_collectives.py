"""Plain reference of the hypercube collectives: what each PE must hold
after one call, computed in NumPy, PE by PE.

Layout: a global array ``x`` of shape ``(*cube, rows, cols)`` whose entry
``x[c]`` is the block PE ``c`` holds. A call over the dims selected by a
bitmap (one character per cube dim, outermost first, ``1`` = selected)
runs one group per assignment of the other dims; a member's rank in its
group counts its selected coordinates in cube order, outermost first.

  all_reduce       every member holds the sum of the group's blocks
  reduce_scatter   member r holds column chunk r of that sum
  all_gather       every member holds the group's blocks, stacked by rank
                   along rows
  all_to_all       member j's column block i is member i's column block j

``mode="bf16"`` sums in bfloat16 (the control: one precision below the
float32 the configuration states), which breaks its exactness guarantee.
"""
from __future__ import annotations

import itertools

import numpy as np


def members(cube, bitmap: str, pe) -> list:
    """The PEs of ``pe``'s group, in rank order."""
    sel = [i for i, b in enumerate(bitmap) if b == "1"]
    out = []
    for coords in itertools.product(*(range(cube[i]) for i in sel)):
        m = list(pe)
        for i, v in zip(sel, coords):
            m[i] = v
        out.append(tuple(m))
    return out


def call(primitive: str, x: np.ndarray, bitmap: str, mode: str = "f32"):
    cube = x.shape[:len(bitmap)]
    out = {}
    for pe in itertools.product(*(range(n) for n in cube)):
        grp = members(cube, bitmap, pe)
        blocks = [x[m] for m in grp]
        g, r = len(grp), grp.index(pe)
        if primitive in ("all_reduce", "reduce_scatter"):
            acc = np.zeros_like(blocks[0])
            for b in blocks:
                if mode == "bf16":
                    acc = _bf16(_bf16(acc) + _bf16(b))
                else:
                    acc = acc + b
            out[pe] = (acc if primitive == "all_reduce"
                       else np.split(acc, g, axis=1)[r])
        elif primitive == "all_gather":
            out[pe] = np.concatenate(blocks, axis=0)
        elif primitive == "all_to_all":
            out[pe] = np.concatenate(
                [np.split(b, g, axis=1)[r] for b in blocks], axis=1)
        else:
            raise ValueError(primitive)
    first = next(iter(out.values()))
    res = np.empty(cube + first.shape, first.dtype)
    for pe, v in out.items():
        res[pe] = v
    return res


def _bf16(a: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even)."""
    u = a.astype(np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)
