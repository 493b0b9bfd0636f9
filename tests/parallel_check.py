"""Parallel-consistency oracle: the sharded (FSDP+TP+EP, manual-SPMD) loss
and gradients must match a single-device run of the same tiny config.

Run in a subprocess: python tests/parallel_check.py
Prints ALL-OK on success.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
from repro.compat import shard_map
from jax.sharding import PartitionSpec as P

import repro.models.params as params_mod
import repro.models.blocks as blocks_mod
import repro.models.lm as lm_mod

# Run the oracle in fp32 so any mismatch is structural, not rounding.
params_mod.COMPUTE_DTYPE = jnp.float32
blocks_mod.COMPUTE_DTYPE = jnp.float32
lm_mod.COMPUTE_DTYPE = jnp.float32

# The MoE load-balance aux loss is computed per dispatch group (standard
# practice at scale); it legitimately differs from the single-device global
# value, so the strict consistency check runs on the CE loss alone.
lm_mod.AUX_COEF = 0.0

from repro.configs import get
from repro.launch.mesh import make_mesh
from repro.models.lm import Model
from repro.models.params import init_params, param_specs
from repro.models.topology import build_topology
from repro.runtime.trainer import input_batch_specs

TOL = dict(rtol=5e-2, atol=5e-3)


def grads_fn(cfg, topo):
    model = Model(cfg, topo)
    specs = param_specs(cfg, topo)

    def f(params, batch):
        # vma-aware autodiff inserts every needed gradient reduction
        (loss, metrics), grads = jax.value_and_grad(
            model.loss_shard, has_aux=True)(params, batch)
        return loss, grads

    bspecs = input_batch_specs(cfg, topo)
    return jax.jit(shard_map(
        f, mesh=topo.cube.mesh, in_specs=(specs, bspecs),
        out_specs=(P(), specs), check_vma=True))


def make_batch(cfg, rng, B=4, S=32):
    batch = {
        "tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32),
    }
    if cfg.frontend == "patch":
        batch["patches"] = jnp.asarray(
            rng.randn(B, cfg.frontend_tokens, cfg.frontend_dim), jnp.float32)
    if cfg.is_encoder_decoder:
        batch["frames"] = jnp.asarray(
            rng.randn(B, S, cfg.frontend_dim), jnp.float32)
    return batch


CASES = {
    # arch -> parallelism override for the 8-device mesh (2 pods x 2 x 2)
    "qwen3-1.7b": dict(tp=2),
    "gemma3-1b": dict(tp=2),
    "mixtral-8x7b": dict(ep=2, etp=2, tp=4, capacity_factor=8.0),
    "qwen2-moe-a2.7b": dict(ep=2, etp=1, tp=2, capacity_factor=8.0),
    "rwkv6-7b": dict(tp=2),
    "jamba-1.5-large-398b": dict(ep=2, etp=1, tp=2, capacity_factor=8.0),
    "whisper-base": dict(tp=2),
    "llava-next-34b": dict(tp=2),
    "internlm2-20b": dict(tp=2),
    "phi3-mini-3.8b": dict(tp=2),
}


def run_case(arch, overrides):
    cfg = dataclasses.replace(get(arch).scaled_for_smoke(), **overrides)
    rng = np.random.RandomState(7)
    batch = make_batch(cfg, rng)

    # reference: single device (every hypercube dim = 1)
    ref_cfg = dataclasses.replace(cfg, tp=1, ep=1, etp=1)
    mesh1 = make_mesh((1, 1), ("data", "model"))
    topo1 = build_topology(ref_cfg, mesh1)
    params = init_params(ref_cfg, topo1, seed=3)
    loss1, g1 = grads_fn(ref_cfg, topo1)(params, batch)

    # sharded: multi-pod mesh (pod=2, data=2, model=2); model axes per case
    mesh8 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    topo8 = build_topology(cfg, mesh8)
    fn8 = grads_fn(cfg, topo8)
    loss8, g8 = fn8(params, batch)
    np.testing.assert_allclose(np.asarray(loss8), np.asarray(loss1), **TOL)

    flat1, tdef = jax.tree.flatten(jax.device_get(g1))
    flat8 = list(map(np.asarray, tdef.flatten_up_to(jax.device_get(g8))))
    worst = 0.0
    for a, b in zip(flat1, flat8):
        denom = np.maximum(np.abs(a).max(), 1e-3)
        worst = max(worst, float(np.abs(a - b).max() / denom))
    assert worst < 5e-3, f"{arch}: worst rel grad diff {worst}"

    print(f"ok: {arch} loss={float(loss1):.4f} "
          f"worst-rel-grad-diff={worst:.4f}")


def main():
    for arch, ov in CASES.items():
        run_case(arch, ov)
    print("ALL-OK")


if __name__ == "__main__":
    main()
