"""Traffic of kind ``train``: the program's ``Trainer.run`` on seeded
rows, one step per call, as a training job drives it.

Set-up makes the weights from the seed, builds the trainer and drives it
through the job's first ``checked_steps`` steps with the window's own call
and feed: those steps compile the step and give the readings that the
reference checks. The window then runs whole steps for ``--seconds``.
"""
from __future__ import annotations

import gc
import math

import numpy as np

from bench import compare, flops, harness, weights


def rows_at(seed: int, step: int, rows: int, seq: int, vocab: int):
    """Tokens and next-token labels of step ``step``: rows that all
    differ, drawn from the seed alone."""
    rng = np.random.default_rng([int(seed), step])
    t = rng.integers(0, vocab, (rows, seq + 1), dtype=np.int32)
    return t[:, :-1], t[:, 1:]


def leaf_norms(tree, n_layers: int) -> dict:
    """Per-leaf norms of a program tree (layers split), traced."""
    import jax.numpy as jnp
    out = {}
    for n, a in tree.items():
        if n == "units":
            for k, s in a["p0"].items():
                sq = jnp.sum(jnp.square(s.astype(jnp.float32)),
                             axis=tuple(range(1, s.ndim)))
                out.update({f"layer{l}.{k}": jnp.sqrt(sq[l])
                            for l in range(n_layers)})
        else:
            out[f"top.{n}"] = jnp.linalg.norm(a.astype(jnp.float32))
    return out


def first_grad_norms(mu: dict, b1: float, n_layers: int) -> dict:
    """Per-leaf norms of the clipped gradient the optimizer got at step 1,
    from its state after that step: m1 = (1 - b1) g, m kept as int8 codes
    per row (signed square-root companding) with the row's scale."""
    import jax
    import jax.numpy as jnp

    def g(leaf):
        q = leaf["m_q"].astype(jnp.float32)
        return jnp.sign(q) * jnp.square(q / 127.0) * leaf["m_s"] / (1 - b1)
    fn = jax.jit(lambda mu: leaf_norms(jax.tree.map(
        g, mu, is_leaf=lambda x: isinstance(x, dict) and "m_q" in x),
        n_layers))
    return {k: float(v) for k, v in fn(mu).items()}


def change_norms(params, seed: int, c: dict, n_layers: int) -> dict:
    """Per-leaf norms of params minus the seeded initial weights, made
    again on the device inside the same program."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda p, key: leaf_norms(jax.tree.map(
        jnp.subtract, p, weights.program_tree(key, c)), n_layers))
    return {k: float(v) for k, v in fn(params, weights.seed_key(seed)).items()}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.launch.train import train_topology
    from repro.models.params import param_structs
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.trainer import TrainConfig, Trainer, init_opt_state

    c, tr, seed = ctx.cell.config, ctx.cell.traffic, ctx.seed
    o = tr["optimizer"]
    rows, seq, vocab = tr["rows"], tr["seq"], c["vocab_size"]
    cfg, topo = train_topology(weights.program_config(c), global_batch=rows,
                               devices=ctx.devices)
    tc = TrainConfig(lr=o["lr"], warmup=0, total_steps=2 ** 30,
                     clip_norm=o["clip_norm"],
                     adamw=AdamWConfig(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                                       weight_decay=o["weight_decay"],
                                       use_8bit=o["moments"] == "int8"))
    with harness.annotate("bench.setup.weights"):
        shard = jax.tree.map(lambda s: s.sharding, param_structs(cfg, topo))
        params = weights.make_program_weights(seed, c, jnp.float32, shard)
        opt = init_opt_state(cfg, topo, tc)
    trainer = Trainer(cfg, topo, tc)
    ctx.phase("weights")

    def feed(step):
        t, l = rows_at(seed, step, rows, seq, vocab)
        return {"tokens": jnp.asarray(t), "labels": jnp.asarray(l)}

    # the checked steps: the window's own call and feed
    prog = {"losses": [], "gnorms": []}
    step = 0
    for step in range(tr["checked_steps"]):
        params, opt, hist = trainer.run(params, opt, [feed(step)],
                                        start_step=step, log_every=0)
        prog["losses"].append(hist[0]["loss"])
        prog["gnorms"].append(hist[0]["grad_norm"])
        if step == 0:
            ctx.phase("first step")
            prog["first_grad"] = first_grad_norms(opt["mu"], o["b1"],
                                                  cfg.n_layers)
    ctx.phase("checked steps")
    prog["change"] = change_norms(params, seed, c, cfg.n_layers)
    step += 1

    # the window: whole steps for --seconds
    ctx.start_window()
    t0 = harness.now()
    n_steps = bad = 0
    while True:
        params, opt, hist = trainer.run(params, opt, [feed(step)],
                                        start_step=step, log_every=0)
        step += 1
        n_steps += 1
        bad += not math.isfinite(hist[0]["loss"])
        t1 = harness.now()
        if t1 - t0 >= ctx.seconds:
            break
    ctx.end_window()
    window_s = t1 - t0
    tokens_per_s = n_steps * rows * seq / window_s

    trace = {}
    if ctx.trace:
        with harness.traced(trace):
            for _ in range(tr["trace_steps"]):
                with harness.annotate("bench.feed"):
                    b = feed(step)
                with harness.annotate("bench.train_step"):
                    params, opt, hist = trainer.run(
                        params, opt, [b], start_step=step, log_every=0)
                step += 1
    peak = harness.peak_bytes(ctx.devices)
    del params, opt, trainer
    gc.collect()

    # the reference, once the program's state is freed
    ref = ctx.cell.reference.train(
        c, o, seed, [rows_at(seed, s, rows, seq, vocab)
                     for s in range(tr["checked_steps"])])
    numbers = compare.train_numbers(prog, ref)
    ctx.note(f"readings program {prog['losses']} {prog['gnorms']} "
             f"reference {ref['losses']} {ref['gnorms']} "
             f"worst leaves {numbers['_where']}")
    return {
        "attempted": n_steps, "failed": bad, "peak_bytes": peak,
        "values": {"train_tokens_per_s": tokens_per_s},
        "rec": {"trace": trace, "tokens_per_s": tokens_per_s,
                "flops_per_token": flops.train_flops_per_token(c, seq)},
        "numbers": numbers,
    }
