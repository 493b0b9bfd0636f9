"""The train step's gradient reductions come from autodiff.

``make_train_step`` differentiates the loss under
``shard_map(check_vma=True)`` and calls no gradient sync of its own: the
varying-axes tracker sums each leaf's gradient over every cube axis the
leaf's spec does not shard. One case per replication pattern on the
pod-crossing 2x2x2 cube, checked bit-exactly against the NumPy oracle on
integer-valued payloads.
"""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.testing import oracles, substrate

LEAF = 8      # global leaf length: divisible by every product of cube dims


@pytest.mark.parametrize("sharded", [(), ("tp",), ("dp", "tp"),
                                     ("pod", "dp", "tp")],
                         ids=["replicated", "tp", "dp-tp", "pod-dp-tp"])
def test_autodiff_sums_grads_over_unsharded_axes(cube_pod, sharded):
    cube = cube_pod
    spec = P(sharded) if sharded else P()
    local = LEAF // int(np.prod([cube.size(d) for d in sharded]))
    w = np.arange(LEAF, dtype=np.float32)
    c = substrate.integer_payload(cube, (local,), seed=2)

    def loss(params, c_shard):
        # c varies over every cube axis; each shard's partial loss meets a
        # leaf that is invariant over the axes its spec does not shard
        part = (params["w"] * c_shard[0, 0, 0]).sum()
        return compat.replicated_psum(part, cube.dim_names)

    def grad_shard(params, c_shard):
        g = jax.grad(loss)(params, c_shard)["w"]
        return g[None, None, None]

    cspec = substrate.global_spec(cube, 1)
    got = jax.jit(compat.shard_map(
        grad_shard, mesh=cube.mesh, in_specs=({"w": spec}, cspec),
        out_specs=cspec, check_vma=True))({"w": w}, c)

    unsharded = tuple(i for i, d in enumerate(cube.dim_names)
                      if d not in sharded)
    want = oracles.all_reduce(c, 3, unsharded) if unsharded else c
    np.testing.assert_array_equal(np.asarray(got), want)
