"""Compiles for a described TPU v5e, with no chip attached: the Pallas
kernels at real widths, the qwen3-1.7b decode step at published widths
with one layer, and the serve engine's paged step at two and four layers
(the train step takes ~20 s to compile, too long to keep here). What the chip's compiler refuses (an unaligned tile, a
primitive with no kernel lowering, a program over the 16 GB of HBM) fails
here, at no chip time. Nothing runs, so these say nothing about results
or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import os

import pytest

HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # never read back without the chip: keep it out for these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e.devices[0])


def _structs(sharding, *shapes_dtypes):
    import jax
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes_dtypes]


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    return used


# (B, S, H, KV, hd, causal, window): qwen3-1.7b causal at 2048, the same
# heads with a sliding window, and a non-causal (encoder) call
@pytest.mark.parametrize("B,S,H,KV,hd,causal,window", [
    (1, 2048, 16, 8, 128, True, -1),
    (1, 2048, 16, 8, 128, True, 512),
    (2, 1024, 16, 8, 128, False, -1),
])
def test_flash_kernel_compiles(one_chip, B, S, H, KV, hd, causal, window):
    import jax
    import jax.numpy as jnp
    from repro.kernels.attention.flash import flash_attention
    q, k, v = _structs(one_chip, ((B, S, H, hd), jnp.bfloat16),
                       ((B, S, KV, hd), jnp.bfloat16),
                       ((B, S, KV, hd), jnp.bfloat16))
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, window=window)).lower(q, k, v).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


# (B, S, H, KV, hd, window, temp limit in bytes): phi3-mini-4k's training
# attention (full MHA at head_dim 96, banded at window 2047; XLA's own VJP
# of the blockwise forward took 3.78 GB) and qwen3-1.7b's at the train
# cell's 4 rows (GQA, causal; 4.44 GB before)
@pytest.mark.parametrize("B,S,H,KV,hd,window,limit", [
    (1, 4096, 32, 32, 96, 2047, 0.5e9),
    (4, 2048, 16, 8, 128, -1, 0.8e9),
])
def test_training_attention_grad_compiles(one_chip, B, S, H, KV, hd, window,
                                          limit):
    """jax.grad of the training attention: the FlashAttention-2 backward
    keeps no score matrix, so its temporary stays under ``limit``."""
    import jax
    import jax.numpy as jnp
    from repro.models.layers import chunked_attention
    q, k, v = _structs(one_chip, ((B, S, H, hd), jnp.bfloat16),
                       ((B, S, KV, hd), jnp.bfloat16),
                       ((B, S, KV, hd), jnp.bfloat16))
    compiled = jax.jit(jax.grad(lambda q, k, v: jnp.sum(chunked_attention(
        q, k, v, causal=True, window=window).astype(jnp.float32)),
        (0, 1, 2))).lower(q, k, v).compile()
    _fits(compiled)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < limit, temp


@pytest.mark.parametrize("chunk", [32, 64])
def test_rwkv6_kernel_compiles(one_chip, chunk):
    """rwkv6-7b: d_model 4096 in heads of 64."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.rwkv6.rwkv6 import rwkv6_chunked
    B, S, H, K = 1, 2048, 64, 64
    r, k, v, lw = _structs(one_chip, *[((B, S, H, K), jnp.bfloat16)] * 3,
                           ((B, S, H, K), jnp.float32))
    (u,) = _structs(one_chip, ((H, K), jnp.float32))
    compiled = jax.jit(lambda *a: rwkv6_chunked(*a, chunk=chunk)).lower(
        r, k, v, lw, u).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def _qwen3_one_layer():
    from repro import configs
    return dataclasses.replace(configs.get("qwen3-1.7b"), n_layers=1)


def test_qwen3_one_layer_decode_step_compiles(v5e):
    """The serve launcher's decode step over a bf16 cache."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.serve import make_decode_step, serve_topology
    from repro.models.params import param_structs
    from repro.models.serving import cache_structs, make_serve_plan
    cfg = _qwen3_one_layer()
    topo = serve_topology(cfg, devices=v5e.devices[:1])
    plan = make_serve_plan(cfg, topo, S_ctx=256, global_batch=4)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                       sharding=s.sharding),
        param_structs(cfg, topo))
    tok = jax.ShapeDtypeStruct((4,), jnp.int32,
                               sharding=topo.cube.sharding(P()))
    compiled = make_decode_step(cfg, topo, plan).lower(
        params, cache_structs(cfg, topo, plan), tok, tok).compile()
    _fits(compiled)


def _compiled_engine_step(v5e, n_layers):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro import configs
    from repro.launch.serve import serve_topology
    from repro.models.params import param_structs
    from repro.models.serving import make_serve_plan
    from repro.serving.engine import make_step
    from repro.serving.pages import make_page_plan, paged_cache_defs
    B, S_ctx = 32, 1024
    cfg = dataclasses.replace(configs.get("qwen3-1.7b"), n_layers=n_layers)
    topo = serve_topology(cfg, devices=v5e.devices[:1])
    plan = make_serve_plan(cfg, topo, S_ctx=S_ctx, global_batch=B)
    pplan = make_page_plan(plan, topo, page_size=4)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                       sharding=s.sharding),
        param_structs(cfg, topo))
    pools = jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d[0], d[2],
                                       sharding=topo.cube.sharding(d[1])),
        paged_cache_defs(cfg, topo, plan, pplan),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    rep = topo.cube.sharding(P())

    def s(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=rep)

    args = (params, pools, s((B, pplan.n_blocks)), s((B,)), s((B,)),
            s((B,), jnp.bool_), s((B, S_ctx)), s((B,), jnp.bool_),
            s((B,)), s((B,)), s((B, S_ctx)), s((B,)), s((B,), jnp.bool_),
            s((B,), jnp.float32), s((2,), jnp.uint32))
    compiled = make_step(cfg, topo, plan, pplan).lower(*args).compile()
    _fits(compiled)
    return compiled


# one layer's k+v view of every lane at the serve cell's shape, bf16
VIEW_BYTES = 2 * 32 * 1024 * 8 * 128 * 2


@pytest.fixture
def bf16_compute(monkeypatch):
    """The step computes in bfloat16, as served, whatever compute dtype
    another test module has set."""
    import jax.numpy as jnp
    from repro.models import blocks, lm, params
    for mod in (params, blocks, lm):
        monkeypatch.setattr(mod, "COMPUTE_DTYPE", jnp.bfloat16)


def test_qwen3_engine_step_temp_is_one_layer(v5e, bf16_compute):
    """The serve engine's paged step at the serve cell's 32 lanes, S_ctx
    1024, page 4: it reads one layer's keys at a time and writes one row
    per lane, so its temporary stays under 1 GB and two more layers add
    less than one layer's view (the whole-view gather took 7.65 GB at 28
    layers)."""
    t2 = _compiled_engine_step(v5e, 2).memory_analysis().temp_size_in_bytes
    t4 = _compiled_engine_step(v5e, 4).memory_analysis().temp_size_in_bytes
    assert t2 < 2 ** 30, t2
    assert t4 - t2 < VIEW_BYTES, (t2, t4, VIEW_BYTES)


def test_qwen3_engine_step_reads_pages_in_place(v5e, bf16_compute):
    """On the chip the engine's step attends through the paged decode
    kernel: its HLO holds the kernel's custom call and no (32, 1024, 8,
    128) view of any layer, and it fits in HBM."""
    import re
    compiled = _compiled_engine_step(v5e, 2)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%paged_decode_attention[.\d]* = ", text)
    assert "[32,1024,8,128]" not in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"paged step temp {temp} B at 2 layers (gathering each layer's "
          f"view, the step held 68.5 MB)")
    assert temp < VIEW_BYTES / 2, temp
