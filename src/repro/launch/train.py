"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --steps 200 --batch 8 --seq 256 [--smoke] [--ckpt-dir ckpts]

With --smoke the architecture is reduced to its CPU-runnable family config
(single device). On a real TPU deployment the same entry point runs the full
config on the production mesh (``--production`` / ``--multipod``).
"""
from __future__ import annotations

import argparse


def train_topology(cfg, *, global_batch: int, smoke: bool = False,
                   devices=None):
    """``(cfg, topo)`` this launcher trains on over ``devices`` (default:
    every visible device): the model axis takes ``min(cfg.model_parallel,
    n)`` of them (1 under ``smoke``), data parallelism the rest, and the
    config's model-parallel split is cut to match."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.models.topology import build_topology
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    mp = 1 if smoke else min(cfg.model_parallel, n)
    cfg = cfg.with_model_parallel(mp)
    mesh = make_mesh((n // mp, mp), ("data", "model"), devices=devices)
    return cfg, build_topology(cfg, mesh, global_batch=global_batch)


def init_train_state(cfg, topo, tc, *, seed: int = 0):
    """Random parameters from ``seed`` and zero optimizer state, every leaf
    created in place on its cube sharding."""
    import jax
    from repro.models.params import init_params, param_structs
    from repro.runtime.trainer import init_opt_state
    shardings = jax.tree.map(lambda s: s.sharding, param_structs(cfg, topo))
    params = jax.jit(lambda: init_params(cfg, topo, seed),
                     out_shardings=shardings)()
    return params, init_opt_state(cfg, topo, tc)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-scale)")
    ap.add_argument("--production", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fp32-moments", action="store_true")
    args = ap.parse_args()

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    from repro import configs
    from repro.checkpoint.manager import CheckpointManager
    from repro.data.pipeline import DataConfig, TokenStream
    from repro.launch.mesh import make_production_mesh
    from repro.models.params import param_specs
    from repro.models.topology import build_topology
    from repro.optim import adamw
    from repro.runtime.trainer import Trainer, TrainConfig, opt_specs

    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = cfg.scaled_for_smoke()
    if args.production or args.multipod:
        mesh = make_production_mesh(multi_pod=args.multipod)
        topo = build_topology(cfg, mesh, global_batch=args.batch)
    else:
        cfg, topo = train_topology(cfg, global_batch=args.batch,
                                   smoke=args.smoke)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"cube={topo.cube.describe()}")

    tc = TrainConfig(lr=args.lr, warmup=args.warmup,
                     total_steps=args.steps,
                     adamw=adamw.AdamWConfig(use_8bit=not args.fp32_moments))
    params, opt = init_train_state(cfg, topo, tc)

    ckpt = None
    if args.ckpt_dir:
        # topology-bound: save gathers through one rooted-gather program,
        # restore re-places every leaf through one rooted-scatter program
        # planned for THIS cube -- resuming on a different mesh shape than
        # the checkpoint was written on needs no conversion step
        ckpt = CheckpointManager(
            args.ckpt_dir, topo=topo,
            specs={"params": param_specs(cfg, topo),
                   "opt": opt_specs(cfg, topo, tc)})
    start = 0
    if ckpt and args.resume and ckpt.latest_step() is not None:
        start = ckpt.latest_step()
        st = ckpt.restore(start)
        params, opt = st.params, st.opt
        print(f"resumed from step {start}")

    dc = DataConfig(seq_len=args.seq, global_batch=args.batch,
                    vocab_size=cfg.vocab_size)
    stream = TokenStream(cfg, dc)

    trainer = Trainer(cfg, topo, tc, checkpointer=ckpt)

    def batches():
        import jax.numpy as jnp
        for step in range(start, args.steps):
            b = stream.global_batch_at(step)
            yield {k: jnp.asarray(v) for k, v in b.items()}

    params, opt, hist = trainer.run(
        params, opt, batches(), start_step=start,
        checkpoint_every=args.ckpt_every, log_every=max(args.steps // 20, 1))
    if ckpt:
        ckpt.wait()
    print(f"final loss {hist[-1]['loss']:.4f} "
          f"(first {hist[0]['loss']:.4f}); straggler steps: "
          f"{trainer.slow_steps}")


if __name__ == "__main__":
    main()
