"""Collective-fused kernel tests (repro.kernels.collective).

Contracts, per the package docstring:
  * ``ring_attention`` -- matches the gather-then-attend oracle within the
    *documented* tolerance (``RING_ATTN_TOL``): online-softmax merging of
    the per-hop partials reorders the exp/sum, so bit-identity is
    impossible by construction and the budget is asserted explicitly;
  * ``all_gather_matmul`` (ag_prologue) -- bit-identical to
    compute-after-gather: row-wise maps commute with concatenation;
  * ``matmul_reduce_scatter`` (rs_epilogue) -- bit-identical to
    matmul-then-reduce_scatter on integer-valued fp32 (exact sums);
  * the model call sites (``attn_block`` / ``dense_ffn`` via
    ``ModelConfig.fused_comm``) -- a full forward agrees with the unfused
    pipeline within the propagated ring-attention tolerance.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.kernels.collective import (
    RING_ATTN_TOL, all_gather_matmul, matmul_reduce_scatter, ring_attention)
from repro.models.layers import reference_attention
from repro.testing import substrate


def _run_ring8(cube, fn, *arrays, out_ndim):
    """shard_map ``fn`` over the flat 8-ring: each input is global-layout
    ``(8, *payload)``; ``fn`` sees the payloads (leading shard dim
    stripped) and its output is returned in global layout ``(8, *out)``."""
    from repro.compat import shard_map
    specs = tuple(substrate.global_spec(cube, a.ndim - 1) for a in arrays)
    wrapped = jax.jit(shard_map(
        lambda *vs: fn(*(v[0] for v in vs))[None],
        mesh=cube.mesh, in_specs=specs,
        out_specs=substrate.global_spec(cube, out_ndim),
        check_vma=False))
    return np.asarray(wrapped(*arrays))


# ------------------------------------------------------------ ring attention
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2)])       # MHA + GQA 2:1
@pytest.mark.parametrize("causal,window", [(True, -1), (True, 16),
                                           (False, -1)])
def test_ring_attention_documented_tolerance(cube_ring8, dtype, H, KV,
                                             causal, window):
    """Shard-rotated kv attention vs the full-sequence oracle, asserting
    the documented RING_ATTN_TOL budget for the dtype."""
    import jax.numpy as jnp
    g, B, S_loc, hd = 8, 2, 16, 16
    dt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (g, B, S_loc, H, hd), dt)
    k = jax.random.normal(ks[1], (g, B, S_loc, KV, hd), dt)
    v = jax.random.normal(ks[2], (g, B, S_loc, KV, hd), dt)
    comm = cube_ring8.comm("d")

    got = _run_ring8(
        cube_ring8,
        lambda qi, ki, vi: ring_attention(comm, qi, ki, vi, causal=causal,
                                          window=window),
        np.asarray(q.astype(jnp.float32)).astype(dtype),
        np.asarray(k.astype(jnp.float32)).astype(dtype),
        np.asarray(v.astype(jnp.float32)).astype(dtype),
        out_ndim=4)
    # oracle: concatenate the shard chunks into the global sequence
    to_full = lambda a: jnp.moveaxis(jnp.asarray(np.asarray(
        a.astype(jnp.float32))), 0, 1).reshape(B, g * S_loc, -1, hd)
    want = reference_attention(to_full(q).astype(dt), to_full(k).astype(dt),
                               to_full(v).astype(dt), causal=causal,
                               window=window)
    got_full = np.moveaxis(got, 0, 1).reshape(B, g * S_loc, H, hd)
    np.testing.assert_allclose(got_full.astype(np.float32),
                               np.asarray(want, np.float32),
                               atol=RING_ATTN_TOL[dtype])


# ----------------------------------------------------- matmul comm fusions
def test_all_gather_matmul_bit_identical(cube_ring8):
    """ag_prologue with a row-wise block_fn (channel scale + up-projection)
    is bitwise equal to gathering first and computing after, on
    integer-valued fp32 (exact products and sums, so the backend's choice
    of matmul accumulation order per block shape cannot show)."""
    comm = cube_ring8.comm("d")
    x = substrate.integer_payload(cube_ring8, (2, 4, 6), seed=3)
    rng = np.random.RandomState(3)
    gamma = rng.randint(-3, 4, (6,)).astype(np.float32)
    wu = rng.randint(-3, 4, (6, 5)).astype(np.float32)
    block_fn = lambda b: (b * gamma) @ wu

    fused = _run_ring8(
        cube_ring8,
        lambda v: all_gather_matmul(comm, v, axis=1, block_fn=block_fn),
        x, out_ndim=3)
    unfused = _run_ring8(
        cube_ring8,
        lambda v: block_fn(comm.all_gather(v, axis=1)),
        x, out_ndim=3)
    np.testing.assert_array_equal(fused, unfused)


@pytest.mark.parametrize("op", ["add", "min"])
def test_matmul_reduce_scatter_bit_identical(cube_ring8, op):
    """rs_epilogue on integer-valued fp32: the lazy-tile ring epilogue is
    bitwise equal to materializing h @ w and reduce-scattering it."""
    comm = cube_ring8.comm("d")
    h = substrate.integer_payload(cube_ring8, (16, 4), seed=5)  # (8, 16, 4)
    w = np.random.RandomState(5).randint(-3, 4, (4, 6)).astype(np.float32)

    fused = _run_ring8(
        cube_ring8,
        lambda v: matmul_reduce_scatter(comm, v, w, axis=0, op=op),
        h, out_ndim=2)
    unfused = _run_ring8(
        cube_ring8,
        lambda v: comm.reduce_scatter(v @ w, axis=0, op=op),
        h, out_ndim=2)
    np.testing.assert_array_equal(fused, unfused)


def test_matmul_reduce_scatter_rejects_indivisible(cube_ring8):
    comm = cube_ring8.comm("d")
    with pytest.raises(ValueError, match="not divisible"):
        _run_ring8(cube_ring8,
                   lambda v: matmul_reduce_scatter(comm, v, np.eye(
                       4, dtype=np.float32), axis=0),
                   np.zeros((8, 12, 4), np.float32), out_ndim=2)


# ------------------------------------------------------- model call sites
def test_fused_comm_model_forward_matches_unfused():
    """ModelConfig.fused_comm reroutes attn_block/dense_ffn through the
    fused kernels (ring attention over cp, gather-prologue / scatter-
    epilogue over tp); a full forward agrees with the unfused pipeline
    within the propagated ring-attention tolerance."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.configs import get
    from repro.launch.mesh import make_mesh
    from repro.models.lm import Model
    from repro.models.params import init_params, param_specs
    from repro.models.topology import build_topology
    from repro.runtime.trainer import input_batch_specs
    from tests.test_models import make_batch

    substrate.ensure_virtual_devices(8)
    cfg = dataclasses.replace(get("qwen3_1_7b").scaled_for_smoke(), tp=2)
    mesh = make_mesh((2, 4), ("data", "model"))
    # global_batch 2 < data capacity 4: the surplus becomes cp=2, so the
    # fused path exercises ring attention, not just the matmul fusions
    topo = build_topology(cfg, mesh, global_batch=2)
    assert topo.cp and topo.tp
    params = init_params(cfg, topo, seed=0)
    batch = make_batch(cfg, B=2, S=32)

    def logits_for(c):
        model = Model(c, topo)
        fwd = jax.jit(shard_map(
            model.forward_logits, mesh=topo.cube.mesh,
            in_specs=(param_specs(c, topo), input_batch_specs(c, topo)),
            out_specs=P(topo.dp, None, topo.tp), check_vma=False))
        return np.asarray(fwd(params, batch), np.float32)

    base = logits_for(cfg)
    fused = logits_for(dataclasses.replace(cfg, fused_comm=True))
    assert base.shape == fused.shape
    assert np.isfinite(fused).all()
    # the model runs bf16 activations, so the budget is the bf16 ring
    # tolerance (one bf16 ulp of re-rounding per merged partial), amplified
    # by the layer stack / logit projection
    np.testing.assert_allclose(fused, base, atol=RING_ATTN_TOL["bfloat16"])
