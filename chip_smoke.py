#!/usr/bin/env python3
"""Bring-up check on the TPU: the trainer and the serve engine at the
published widths of qwen3-1.7b, through the repo's own entry points.

    python chip_smoke.py               one chip: a few train steps, then the
                                       serve engine answering a Poisson trace
    python chip_smoke.py --four-chips  one 2x2 host: the cube's eight
                                       primitives against the NumPy oracles,
                                       and the model-sharded train step
                                       against the same step on one chip

Earlier lines say what each phase did, with its compile seconds and wall
times. Those are bring-up timings, not benchmark numbers. The last line is
one JSON object, ``{"ok": ..., "device": {"platform", "kind", "count"}}``,
with the device as JAX reports it. Off TPU, or when any phase fails, it
says ``"ok": false`` and the exit code is 1.

The persistent compilation cache follows ``repro.launch.cache``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
ARCH = "qwen3-1.7b"

# Training keeps fp32 master weights, fp32 gradients and 8-bit AdamW
# moments: about 10 bytes per parameter. All 28 layers are 2.03 B params,
# about 20 GB, over the chip's 16 GB. 8 layers are 1.03 B params, about
# 10 GB; compiled for a described v5e at batch 1 x 2048 tokens the step's
# memory_analysis() peak is 11.9 GB (batch 2 would be 14.1 GB).
TRAIN_LAYERS = 8
TRAIN_BATCH = 1
TRAIN_SEQ = 2048
TRAIN_STEPS = 6
# Adam moves every weight by about lr per step in the gradient's sign; at
# 1e-3 the 2048-wide layers overshot (loss 12.35 -> 17.56 on the third
# step on a v5e), at 1e-4 each step moves an output by ~0.2.
TRAIN_LR = 1e-4

# Serving holds bf16 weights only (2 bytes per parameter): all 28 layers
# are 4.1 GB, so the engine runs the full depth.
SERVE_REQUESTS = 8
SERVE_SLOTS = 4
SERVE_CTX = 128
LOGIT_PROMPT = 32
# Decode-through-the-cache logits vs the full-sequence forward: both run
# bf16 activations with fp32 accumulation but round at different points
# (one query against the bf16 cache vs blockwise prefill attention), so
# each layer adds a few bf16 unit roundoffs (2^-8 = 0.4%) of relative
# error. Over 28 layers that stays within 5% of the largest logit.
LOGIT_RTOL = 5e-2

# Four chips: integer-valued fp32 payloads of 256 x 4096 per device (4 MiB)
# make every reduction exact, so each flow must match the oracle bitwise.
PRIMITIVE_PAYLOAD = (256, 4096)
SHARDED_STEPS = 3
# Sharded (data 1 x model 4) vs one-chip train step on the same params and
# batches: tp=4 rounds each partial out-projection / FFN / vocab sum to bf16
# before reducing it across chips, a different order than one chip's single
# sum. Per token that is a few bf16 unit roundoffs; the loss averages 2048
# tokens, so 1% on the loss is a wide margin. The gradient norm sums squares
# of the per-element differences too, so it gets 5%.
SHARDED_LOSS_RTOL = 1e-2
SHARDED_GNORM_RTOL = 5e-2


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


@contextlib.contextmanager
def compile_seconds():
    """Sum of JAX's trace + lower + backend-compile durations inside the
    block (a one-element list, read after the block)."""
    import jax.monitoring
    events = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    total = [0.0]

    def listen(event, secs, **_):
        if event in events:
            total[0] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield total
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------- train
def train_phase(cfg, *, batch: int, seq: int, steps: int, lr: float,
                devices=None) -> dict:
    """``steps`` train steps through ``Trainer.run`` on ``TokenStream``
    batches, with the mesh and topology the training launcher builds.
    Returns per-step losses, gradient norms and wall seconds."""
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import DataConfig, TokenStream
    from repro.launch.train import init_train_state, train_topology
    from repro.runtime.trainer import TrainConfig, Trainer

    cfg, topo = train_topology(cfg, global_batch=batch, devices=devices)
    tc = TrainConfig(lr=lr, warmup=0, total_steps=steps)
    params, opt = init_train_state(cfg, topo, tc, seed=SEED)
    stream = TokenStream(cfg, DataConfig(
        seq_len=seq, global_batch=batch, vocab_size=cfg.vocab_size,
        seed=SEED))
    trainer = Trainer(cfg, topo, tc)
    losses, gnorms, walls = [], [], []
    with compile_seconds() as comp:
        for s in range(steps):
            b = {k: jnp.asarray(v)
                 for k, v in stream.global_batch_at(s).items()}
            t0 = time.perf_counter()
            # run() blocks on the updated params and opt state
            params, opt, hist = trainer.run(params, opt, [b], start_step=s,
                                            log_every=0)
            walls.append(time.perf_counter() - t0)
            losses.append(hist[0]["loss"])
            gnorms.append(hist[0]["grad_norm"])
    devs = {d for leaf in jax.tree.leaves(params)
            for d in leaf.sharding.device_set}
    return {"cube": topo.cube.describe(), "params": cfg.param_count(),
            "losses": losses, "grad_norms": gnorms, "step_wall_s": walls,
            "compile_s": comp[0], "param_devices": len(devs)}


def check_train(r: dict) -> None:
    import numpy as np
    losses = np.asarray(r["losses"])
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite train loss: {r['losses']}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train loss did not fall: {r['losses']}")


# ------------------------------------------------------------------- serve
def init_serve_params(cfg, topo):
    """Random bf16 weights from ``SEED``, created in place on the serve
    cube (the fp32 masters never exist whole)."""
    import jax
    import jax.numpy as jnp
    from repro.models.params import init_params, param_structs
    return jax.jit(
        lambda: jax.tree.map(lambda p: p.astype(jnp.bfloat16),
                             init_params(cfg, topo, SEED)),
        out_shardings=jax.tree.map(lambda s: s.sharding,
                                   param_structs(cfg, topo)))()


def decode_vs_forward(cfg, topo, params, prompt) -> float:
    """Largest |decode logit - forward logit| over the prompt, relative to
    the largest |forward logit|: the serve launcher's decode step teacher-
    forced through its cache, one token at a time, against
    ``Model.forward_logits`` over the same tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.launch.serve import make_decode_step
    from repro.models.lm import Model
    from repro.models.params import param_specs
    from repro.models.serving import init_cache, make_serve_plan

    plan = make_serve_plan(cfg, topo, S_ctx=len(prompt), global_batch=1)
    step = make_decode_step(cfg, topo, plan)
    cache = init_cache(cfg, topo, plan)
    rows = []
    for t, tok in enumerate(prompt):
        logits, cache = step(params, cache, jnp.asarray([tok], jnp.int32),
                             jnp.asarray([t], jnp.int32))
        rows.append(np.asarray(logits[0], np.float32))
    dec = np.stack(rows)
    model = Model(cfg, topo)
    fwd = jax.jit(shard_map(
        model.forward_logits, mesh=topo.cube.mesh,
        in_specs=(param_specs(cfg, topo), {"tokens": P(topo.dp, None)}),
        out_specs=P(topo.dp, None, topo.tp), check_vma=False))
    ref = np.asarray(fwd(params, {"tokens": jnp.asarray([prompt],
                                                        jnp.int32)})[0],
                     np.float32)
    if not (np.isfinite(dec).all() and np.isfinite(ref).all()):
        raise AssertionError("non-finite logits")
    return float(np.abs(dec - ref).max() / np.abs(ref).max())


def serve_phase(cfg, *, n_requests: int, slots: int, s_ctx: int,
                prompt_len: int, devices=None) -> dict:
    """``ServeEngine`` over a Poisson trace on the serve launcher's
    topology; every request must finish with the tokens it asked for.
    Then the decode-vs-forward logits check on one prompt."""
    import numpy as np
    from repro.launch.serve import serve_topology
    from repro.models.serving import make_serve_plan
    from repro.serving import ServeEngine, poisson_trace

    topo = serve_topology(cfg, devices=devices)
    params = init_serve_params(cfg, topo)
    plan = make_serve_plan(cfg, topo, S_ctx=s_ctx, global_batch=slots)
    reqs = poisson_trace(n_requests, rate=0.25, plen_range=(8, 48),
                         max_new_range=(8, 32), vocab=cfg.vocab_size,
                         seed=SEED)
    want = {r.rid: r.max_new for r in reqs}
    with compile_seconds() as comp:
        eng = ServeEngine(cfg, topo, plan, params, seed=SEED)
        t0 = time.perf_counter()
        m = eng.run(reqs)
        wall = time.perf_counter() - t0
    done = {r.rid: r for r in m["finished"]}
    if sorted(done) != sorted(want):
        raise AssertionError(f"finished {sorted(done)} of {sorted(want)}")
    for rid, r in done.items():
        if len(r.out_tokens) != want[rid]:
            raise AssertionError(
                f"request {rid}: {len(r.out_tokens)} of {want[rid]} tokens")
        if not all(0 <= t < cfg.vocab_size for t in r.out_tokens):
            raise AssertionError(f"request {rid}: token out of vocab")
    prompt = np.random.RandomState(SEED).randint(
        0, cfg.vocab_size, prompt_len).tolist()
    with compile_seconds() as comp_l:
        rel = decode_vs_forward(cfg, topo, params, prompt)
    if not rel <= LOGIT_RTOL:
        raise AssertionError(
            f"decode logits differ from forward by {rel} > {LOGIT_RTOL} "
            "of the largest logit")
    return {"cube": topo.cube.describe(), "params": cfg.param_count(),
            "requests": len(done), "tokens": m["generated_tokens"],
            "steps": m["steps"], "run_wall_s": wall,
            "step_p50_s": eng.metrics.quantile("serve.step_seconds", 0.5),
            "step_max_s": eng.metrics.quantile("serve.step_seconds", 1.0),
            "compile_s": comp[0], "logit_check_compile_s": comp_l[0],
            "logit_rel_err": rel}


# ------------------------------------------------------------- four chips
def primitives_phase(devices, payload=PRIMITIVE_PAYLOAD) -> dict:
    """The eight primitives over a 2x2 cube on ``devices``, at every dim
    selection, with ``auto`` and every Table II stage the registry offers,
    each compared bitwise with ``repro.testing.oracles`` on integer-valued
    payloads. Every result must span all of ``devices``."""
    import jax
    import numpy as np
    from repro.compat import make_mesh, shard_map
    from repro.core.comm import applicability
    from repro.core.hypercube import Hypercube
    from repro.testing import oracles, substrate

    mesh = make_mesh((2, 2), ("x", "y"), devices=devices)
    cube = Hypercube.build(mesh, {"x": 2, "y": 2})
    nd = len(cube.dim_sizes)
    spec = substrate.global_spec(cube, len(payload))
    want_devs = set(devices)
    table = applicability()
    n_checked = 0

    def placed(arr, what):
        got = {s.device for s in arr.addressable_shards}
        if arr.sharding.device_set != want_devs or len(got) != len(want_devs):
            raise AssertionError(f"{what}: placed on {sorted(map(str, got))}")

    def same(got, want, what):
        nonlocal n_checked
        if got.shape != want.shape or not np.array_equal(got, want):
            raise AssertionError(f"{what}: differs from the oracle")
        n_checked += 1

    for bitmap in ("10", "01", "11"):
        names = cube.dims_from_bitmap(bitmap)
        idx = tuple(cube.dim_names.index(d) for d in names)
        comm = cube.comm(names)
        x = substrate.integer_payload(cube, payload, seed=int(bitmap, 2))
        xd = jax.device_put(x, cube.sharding(spec))
        placed(xd, f"input[{bitmap}]")
        pe_pe = {
            "all_reduce": (
                lambda v, a: comm.all_reduce(v, algorithm=a),
                oracles.all_reduce(x, nd, idx)),
            "reduce_scatter": (
                lambda v, a: comm.reduce_scatter(v, axis=nd + 1, algorithm=a),
                oracles.reduce_scatter(x, nd, idx, axis=1)),
            "all_gather": (
                lambda v, a: comm.all_gather(v, axis=nd, algorithm=a),
                oracles.all_gather(x, nd, idx, axis=0)),
            "all_to_all": (
                lambda v, a: comm.all_to_all(v, split_axis=nd + 1,
                                             concat_axis=nd + 1, algorithm=a),
                oracles.all_to_all(x, nd, idx, split_axis=1, concat_axis=1)),
        }
        for prim, (fn, want) in pe_pe.items():
            for alg in ("auto",) + table[prim]:
                f = jax.jit(shard_map(
                    lambda v, _f=fn, _a=alg: _f(v, _a), mesh=cube.mesh,
                    in_specs=spec, out_specs=spec, check_vma=False))
                out = f(xd)
                placed(out, f"{prim}[{bitmap},{alg}]")
                same(np.asarray(out), want, f"{prim}[{bitmap},{alg}]")

        # rooted primitives: the host is the root
        g = cube.group_size(names)
        host = substrate.integer_payload(cube, payload, seed=7)[
            (0,) * nd].repeat(g, axis=0)              # (g * rows, cols)
        for alg in ("auto",) + table["scatter"]:
            dev = comm.scatter(host, axis=0, algorithm=alg)
            placed(dev, f"scatter[{bitmap},{alg}]")
            same(substrate.local_blocks(cube, dev),
                 oracles.scatter(host, cube.dim_sizes, idx, axis=0),
                 f"scatter[{bitmap},{alg}]")
        dev = comm.scatter(host, axis=0)
        for alg in ("auto",) + table["gather"]:
            same(np.asarray(comm.gather(dev, algorithm=alg)), host,
                 f"gather[{bitmap},{alg}]")
        for alg in ("auto",) + table["reduce"]:
            same(np.asarray(comm.reduce(dev, op="add", axis=0,
                                        algorithm=alg)),
                 oracles.reduce(host, axis=0), f"reduce[{bitmap},{alg}]")
        for alg in ("auto",) + table["broadcast"]:
            b = comm.broadcast(host, algorithm=alg)
            placed(b, f"broadcast[{bitmap},{alg}]")
            same(substrate.local_blocks(cube, b),
                 oracles.broadcast(host, cube.dim_sizes),
                 f"broadcast[{bitmap},{alg}]")
    return {"cube": cube.describe(), "checks": n_checked,
            "payload_mib_per_device": math.prod(payload) * 4 / 2 ** 20}


def sharded_step_phase(cfg, devices, *, batch: int, seq: int,
                       steps: int) -> dict:
    """The train step as the launcher lays it out on all of ``devices``
    (data 1 x model 4), then the same steps on ``devices[0]`` alone, from
    the same seed and batches."""
    many = train_phase(cfg, batch=batch, seq=seq, steps=steps, lr=TRAIN_LR,
                       devices=devices)
    if many["param_devices"] != len(devices):
        raise AssertionError(
            f"params on {many['param_devices']} of {len(devices)} devices")
    gc.collect()
    one = train_phase(cfg, batch=batch, seq=seq, steps=steps, lr=TRAIN_LR,
                      devices=devices[:1])
    for name, rtol in (("losses", SHARDED_LOSS_RTOL),
                       ("grad_norms", SHARDED_GNORM_RTOL)):
        for a, b in zip(many[name], one[name]):
            if not abs(a - b) <= rtol * abs(b):
                raise AssertionError(
                    f"{name}: sharded {many[name]} vs one chip {one[name]} "
                    f"beyond rtol {rtol}")
    return {"sharded": many, "one_chip": one}


# -------------------------------------------------------------------- main
def run_one_chip(device) -> None:
    from repro import configs
    base = configs.get(ARCH)
    log("config", f"{ARCH}: d_model {base.d_model}, {base.n_heads} heads / "
        f"{base.n_kv_heads} kv heads x {base.head_dim}, d_ff {base.d_ff}, "
        f"vocab {base.vocab_size}, {base.n_layers} layers (all widths as "
        "published; random weights from seed "
        f"{SEED})")
    log("train", f"depth {TRAIN_LAYERS} of {base.n_layers}: fp32 master + "
        "fp32 grads + 8-bit AdamW is ~10 B/param; 28 layers = 2.03 B params "
        "(~20 GB) > 16 GB HBM, 8 layers = 1.03 B (~10 GB, 11.9 GB "
        f"compiled peak at batch {TRAIN_BATCH} x {TRAIN_SEQ})")
    r = train_phase(dataclasses.replace(base, n_layers=TRAIN_LAYERS),
                    batch=TRAIN_BATCH, seq=TRAIN_SEQ, steps=TRAIN_STEPS,
                    lr=TRAIN_LR)
    check_train(r)
    log("train", f"cube {r['cube']} params {r['params']} losses "
        f"{r['losses']} grad_norms {r['grad_norms']}")
    log("train", f"bring-up timings, not benchmark numbers: compile_s "
        f"{r['compile_s']} step_wall_s {r['step_wall_s']} (first step "
        f"includes compile) peak_bytes_in_use {peak_bytes(device)}")
    gc.collect()

    log("serve", f"depth {base.n_layers} of {base.n_layers}: bf16 weights "
        "are 2 B/param, 4.1 GB")
    r = serve_phase(base, n_requests=SERVE_REQUESTS, slots=SERVE_SLOTS,
                    s_ctx=SERVE_CTX, prompt_len=LOGIT_PROMPT)
    log("serve", f"cube {r['cube']} params {r['params']}: {r['requests']} "
        f"requests, {r['tokens']} tokens in {r['steps']} engine steps; "
        f"decode-vs-forward logits rel err {r['logit_rel_err']} "
        f"(limit {LOGIT_RTOL})")
    log("serve", f"bring-up timings, not benchmark numbers: compile_s "
        f"{r['compile_s']} run_wall_s {r['run_wall_s']} step_p50_s "
        f"{r['step_p50_s']} step_max_s {r['step_max_s']} "
        f"logit_check_compile_s {r['logit_check_compile_s']} "
        f"peak_bytes_in_use {peak_bytes(device)}")


def run_four_chips(devices) -> None:
    from repro import configs
    with compile_seconds() as comp:
        t0 = time.perf_counter()
        r = primitives_phase(devices)
        wall = time.perf_counter() - t0
    log("primitives", f"cube {r['cube']}: {r['checks']} primitive x "
        "selection x algorithm checks bit-identical to the oracles at "
        f"{r['payload_mib_per_device']} MiB/device")
    log("primitives", f"bring-up timings, not benchmark numbers: compile_s "
        f"{comp[0]} wall_s {wall}")
    cfg = dataclasses.replace(configs.get(ARCH), n_layers=TRAIN_LAYERS)
    r = sharded_step_phase(cfg, devices, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                           steps=SHARDED_STEPS)
    for key in ("sharded", "one_chip"):
        s = r[key]
        log("sharded-step", f"{key}: cube {s['cube']} losses {s['losses']} "
            f"grad_norms {s['grad_norms']}; bring-up timings, not benchmark "
            f"numbers: compile_s {s['compile_s']} step_wall_s "
            f"{s['step_wall_s']}")
    log("sharded-step", f"within loss rtol {SHARDED_LOSS_RTOL}, grad-norm "
        f"rtol {SHARDED_GNORM_RTOL}; peak_bytes_in_use per device "
        f"{[peak_bytes(d) for d in devices]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Bring-up check of the trainer and serve engine on TPU.")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-host phases: cube primitives vs "
                    "oracles, sharded train step vs one chip")
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    ok = False
    try:
        if device["platform"] != "tpu":
            raise RuntimeError(f"no TPU: JAX reports {device}")
        log("setup", f"devices {device}; compilation cache {cache_dir}")
        if args.four_chips:
            if len(devs) < 4:
                raise RuntimeError(f"--four-chips needs 4 devices: {device}")
            run_four_chips(devs[:4])
        else:
            run_one_chip(devs[0])
        ok = True
    except Exception:
        traceback.print_exc()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
