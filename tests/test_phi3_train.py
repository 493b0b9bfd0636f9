"""Phi-3-mini through the program's training path (``Model.loss_shard``
under ``shard_map``, as ``make_train_step`` runs it) against the
benchmark's plain reference ``bench/configs/ref_dense_lm.py``, at a
smoke size on the CPU: the benchmark configuration's keys with the widths
cut, full MHA (kv heads = heads), a sliding window of 8 over rows of 32,
so that training takes the banded attention path and its backward. The
weights are the benchmark's seeded ones (``bench/weights.py``)."""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             head_dim=16, intermediate_size=96, vocab_size=512,
             num_hidden_layers=2, sliding_window=8)
ROWS, SEQ, SEED = 2, 32, 2 ** 33 + 5

# float32 compute on both sides: what is left is the order of the sums
# (blockwise online softmax and chunked cross-entropy against one softmax
# per row); measured, the same loss and 3.3e-7 on the worst leaf's
# gradient, where a window one key wider reads 2e-5 and 0.30
LOSS_TOL = 1e-5
GRAD_TOL = 1e-4


def _config(**kw):
    from bench import harness
    c = harness.load_json(harness.BENCH / "configs" / "phi3-mini-3.8b-8L.json")
    c.update(SMALL, **kw)
    return c


def _reference():
    from bench import harness
    path = harness.BENCH / "configs" / "ref_dense_lm.py"
    spec = importlib.util.spec_from_file_location("ref_dense_lm_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _batch(c):
    rng = np.random.default_rng(SEED)
    t = rng.integers(0, c["vocab_size"], (ROWS, SEQ + 1), dtype=np.int32)
    return t[:, :-1], t[:, 1:]


def _program(c, tokens, labels):
    """Loss and gradients of the program, leaves named as the
    reference's."""
    from jax.sharding import PartitionSpec as P
    from bench import weights
    from repro.compat import shard_map
    from repro.launch.train import train_topology
    from repro.models.lm import Model
    from repro.models.params import param_specs, param_structs
    from repro.runtime.trainer import input_batch_specs
    cfg, topo = train_topology(weights.program_config(c), global_batch=ROWS,
                               devices=jax.devices()[:1])
    assert cfg.n_kv_heads == cfg.n_heads and cfg.window == 8
    shard = jax.tree.map(lambda s: s.sharding, param_structs(cfg, topo))
    params = weights.make_program_weights(SEED, c, jnp.float32, shard)
    model = Model(cfg, topo)
    specs = param_specs(cfg, topo)
    fn = jax.jit(shard_map(
        jax.value_and_grad(lambda p, b: model.loss_shard(p, b)[0]),
        mesh=topo.cube.mesh, in_specs=(specs, input_batch_specs(cfg, topo)),
        out_specs=(P(), specs)))
    loss, g = fn(params, {"tokens": jnp.asarray(tokens),
                          "labels": jnp.asarray(labels)})
    grads = {"layers": dict(g["units"]["p0"]),
             "top": {n: g[n] for n in ("embed", "final_norm", "lm_head")}}
    return float(loss), grads


def _ref(c, tokens, labels):
    ref = _reference()
    params = ref.init_params(c, SEED)
    total, g = jax.value_and_grad(lambda p: ref.loss_sum(
        c, p, tokens, labels, "f32"))(params)
    n = tokens.size
    return float(total) / n, jax.tree.map(lambda x: x / n, g)


def _worst_leaf(got, want):
    gaps = {f"{part}.{k}": float(jnp.linalg.norm(got[part][k] - want[part][k])
                                 / jnp.linalg.norm(want[part][k]))
            for part in want for k in want[part]}
    return max(gaps.values()), max(gaps, key=gaps.get)


@pytest.fixture
def f32_compute(monkeypatch):
    from repro.models import blocks, lm, params
    for mod in (params, blocks, lm):
        monkeypatch.setattr(mod, "COMPUTE_DTYPE", jnp.float32)


def test_phi3_loss_and_gradients_match_the_reference(f32_compute):
    c = _config()
    tokens, labels = _batch(c)
    loss, grads = _program(c, tokens, labels)
    ref_loss, ref_grads = _ref(c, tokens, labels)
    assert abs(loss - ref_loss) / ref_loss < LOSS_TOL, (loss, ref_loss)
    worst, at = _worst_leaf(grads, ref_grads)
    assert worst < GRAD_TOL, (worst, at)
    # the window is seen: the reference one key wider (q - k <= 8, the
    # other convention transformers has used) is far outside the limits
    _, wide_grads = _ref(_config(sliding_window=9), tokens, labels)
    assert _worst_leaf(grads, wide_grads)[0] > 10 * GRAD_TOL
