"""Serving launcher: batched prefill + decode loop.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse


def serve_topology(cfg, *, devices=None):
    """The decode topology over ``devices`` (default: every visible
    device): all of them on the model axes, the batch replicated."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.models.topology import build_serve_topology
    devices = list(jax.devices() if devices is None else devices)
    mesh = make_mesh((len(devices), 1), ("data", "model"), devices=devices)
    return build_serve_topology(cfg, mesh)


def make_decode_step(cfg, topo, plan):
    """Jitted one-token decode over the contiguous cache:
    ``(params, cache, tokens, pos) -> (logits, cache)``, the cache donated;
    logits are vocab-sharded over the model axes."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.models.params import param_specs
    from repro.models.serving import Server, cache_specs
    server = Server(cfg, topo, plan)
    ba = plan.batch_axes or None
    cspecs = cache_specs(cfg, topo, plan)
    return jax.jit(shard_map(
        server.decode_shard, mesh=topo.cube.mesh,
        in_specs=(param_specs(cfg, topo), cspecs, P(ba), P(ba)),
        out_specs=(P(ba, topo.tp), cspecs), check_vma=False),
        donate_argnums=(1,))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.models.params import init_params
    from repro.models.serving import make_serve_plan, init_cache

    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = cfg.scaled_for_smoke()
    topo = serve_topology(cfg)
    S_ctx = args.prompt_len + args.gen
    plan = make_serve_plan(cfg, topo, S_ctx=S_ctx, global_batch=args.batch)
    print(f"arch={cfg.name} cube={topo.cube.describe()} "
          f"cache={plan.S_cache}")

    params = init_params(cfg, topo, seed=0)
    cache = init_cache(cfg, topo, plan)
    step = make_decode_step(cfg, topo, plan)

    rng = np.random.RandomState(0)
    B = args.batch
    prompt = rng.randint(0, cfg.vocab_size, (B, args.prompt_len))
    toks = jnp.asarray(prompt[:, 0], jnp.int32)
    out = []
    # teacher-forced "prefill" via decode steps (keeps the demo single-path),
    # then free-running generation
    for t in range(S_ctx - 1):
        pos = jnp.full((B,), t, jnp.int32)
        logits, cache = step(params, cache, toks, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if t + 1 < args.prompt_len:
            toks = jnp.asarray(prompt[:, t + 1], jnp.int32)
        else:
            toks = nxt
            out.append(np.asarray(nxt))
    gen = np.stack(out, axis=1)
    print(f"generated {gen.shape} tokens; sample row: {gen[0][:12]}")


if __name__ == "__main__":
    main()
