"""Seeded weights of a dense decoder LM, made on the device, and the
program's model configuration for a configuration file.

The benchmark makes the weights, not the program: the program is handed
them, and the plain reference makes the same ones again from the same seed.
Every tensor of layer ``l`` is drawn from ``fold_in(fold_in(key, tensor),
l)``, so one layer can be made alone (the reference, layer by layer) or all
layers stacked along a leading axis (the program's layout), with the same
values either way.

Layout (the program's, at one model shard): ``embed (V, D)``; per layer
``ln (D,)``, ``wq (D, H*hd)``, ``wkv (D, KV*2*hd)`` with columns laid out
``(KV, 2, hd)`` (k then v of each kv head), ``wo (H*hd, D)``, ``q_norm
(hd,)``, ``k_norm (hd,)``, ``fln (D,)``, ``wg (D, F)``, ``wu (D, F)``, ``wd
(F, D)``; ``final_norm (D,)``; ``lm_head (D, V)`` unless tied. A norm's
stored value is its gain minus one.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# per-layer tensors in a fixed order: the order is part of the key
LAYER_TENSORS = ("ln", "wq", "wkv", "wo", "q_norm", "k_norm", "fln", "wg",
                 "wu", "wd")
TOP_TENSORS = ("embed", "final_norm", "lm_head")
NORM_STD = 0.1            # norm gains are 1 + N(0, 0.1): every norm matters


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(np.uint32(seed & 0xFFFFFFFF)),
                              np.uint32((seed >> 32) & 0xFFFFFFFF))


def layer_shapes(c: dict) -> dict:
    D, H, KV, hd, F = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    shapes = {"ln": (D,), "wq": (D, H * hd), "wkv": (D, KV * 2 * hd),
              "wo": (H * hd, D), "fln": (D,), "wg": (D, F), "wu": (D, F),
              "wd": (F, D)}
    if c.get("qk_norm"):
        shapes["q_norm"] = (hd,)
        shapes["k_norm"] = (hd,)
    return shapes


def top_shapes(c: dict) -> dict:
    D, V = c["hidden_size"], c["vocab_size"]
    shapes = {"embed": (V, D), "final_norm": (D,)}
    if not c["tie_word_embeddings"]:
        shapes["lm_head"] = (D, V)
    return shapes


def _std(name: str, c: dict) -> float:
    if name.endswith("norm") or name in ("ln", "fln"):
        return NORM_STD
    if name in ("wo", "wd"):
        return 0.02 / math.sqrt(2 * c["num_hidden_layers"])
    if name == "embed":
        return 1.0 / math.sqrt(c["hidden_size"])
    return 0.02


def _draw(key, name, shape, c, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * _std(name, c)).astype(dtype)


def layer_tensor(key, c: dict, name: str, layer, dtype=jnp.float32):
    """One tensor of one layer (``layer`` may be traced)."""
    k = jax.random.fold_in(jax.random.fold_in(key, LAYER_TENSORS.index(name)),
                           layer)
    return _draw(k, name, layer_shapes(c)[name], c, dtype)


def top_tensor(key, c: dict, name: str, dtype=jnp.float32):
    k = jax.random.fold_in(key, 100 + TOP_TENSORS.index(name))
    return _draw(k, name, top_shapes(c)[name], c, dtype)


def layer_weights(key, c: dict, layer, dtype=jnp.float32) -> dict:
    return {n: layer_tensor(key, c, n, layer, dtype)
            for n in layer_shapes(c)}


def program_tree(key, c: dict, dtype=jnp.float32) -> dict:
    """Every weight in the program's tree: layers stacked under
    ``units/p0`` (a dense model repeats a one-layer unit)."""
    L = c["num_hidden_layers"]
    layers = jax.vmap(lambda l: layer_weights(key, c, l, dtype))(
        jnp.arange(L))
    tree = {"units": {"p0": layers}}
    for n in top_shapes(c):
        tree[n] = top_tensor(key, c, n, dtype)
    return tree


def make_program_weights(seed: int, c: dict, dtype, shardings):
    """All weights in one jitted call on the device, placed as the program
    wants them (``shardings``: a tree matching :func:`program_tree`). The
    key is an argument, so one compiled program serves every seed."""
    return jax.jit(lambda key: program_tree(key, c, dtype),
                   out_shardings=shardings)(seed_key(seed))


def program_config(c: dict):
    """The program's ModelConfig: its preset, with every size from the
    configuration file."""
    import dataclasses
    from repro import configs
    base = configs.get(c["program"]["arch"])
    return dataclasses.replace(
        base, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), qk_norm=bool(c.get("qk_norm")),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        window=int(c.get("sliding_window") or -1))
