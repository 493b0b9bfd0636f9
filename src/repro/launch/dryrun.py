"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production mesh and record memory / cost / collective-schedule
analysis for the roofline.

MUST set the fake device count before any other import -- jax locks the
device count on first backend init.
"""
import os
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                               + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from repro.compat import shard_map
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core.comm import CommTrace
from repro.telemetry import drift as drift_mod
from repro.telemetry import metrics as telemetry_metrics
from repro.launch.mesh import make_production_mesh
from repro.models.config import ModelConfig
from repro.models.lm import Model
from repro.models.params import param_structs, param_specs
from repro.models.serving import (
    Server, make_serve_plan, cache_structs, cache_specs)
from repro.models.topology import build_topology, build_serve_topology
from repro.runtime.trainer import (
    TrainConfig, make_train_step, opt_structs, input_batch_specs)

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\b")
TYPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|s16|s8|u64|u32|u16|u8|pred)"
                     r"\[([0-9,]*)\]")
DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
               "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1}


def _shape_bytes(m) -> int:
    dt, dims = m
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES[dt]


def parse_collectives(hlo: str) -> dict:
    """Sum result bytes of every collective op in (post-optimization) HLO.

    Post-opt HLO prints operands as names, so we account with the *result*
    type (between '=' and the op name); the roofline converts result bytes to
    wire bytes per-primitive (AG: (g-1)/g x result; RS: result x (g-1);
    AR: 2 x (g-1)/g x result; AA: (g-1)/g x result; permute: 1x)."""
    out = {}
    for line in hlo.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or "=" not in line:
            continue
        op = m.group(1)
        sync = f" {op}(" in line
        start = f" {op}-start(" in line
        if not (sync or start):
            continue
        lhs = line.split(f" {op}", 1)[0]
        lhs = lhs.split("=", 1)[-1]
        types = TYPE_RE.findall(lhs)
        if not types:
            continue
        # sync ops: single result type; -start ops: tuple (operand, result)
        nbytes = _shape_bytes(types[-1])
        g = 0
        rg = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
        if rg:
            g = len(rg.group(1).split(","))
        else:
            rg = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
            if rg:
                g = int(rg.group(2))
        d = out.setdefault(op, {"count": 0, "result_bytes": 0,
                                "by_group": {}})
        d["count"] += 1
        d["result_bytes"] += nbytes
        bg = d["by_group"].setdefault(str(g), {"count": 0, "bytes": 0})
        bg["count"] += 1
        bg["bytes"] += nbytes
    return out


# HLO collective ops each (primitive, executed flow) must leave in the
# compiled module.  Rooted host primitives map to boundary transfers (no
# collective op) and are excluded.  The registry bodies are the source of
# truth: naive/pr emulate the host flow with a full all-gather, im ladders
# are ppermute chains, the hierarchical/compressed splits are RS + AG.
_EXPECTED_HLO = {
    ("all_reduce", "hierarchical"): {"reduce-scatter", "all-gather"},
    ("all_reduce", "compressed"): {"reduce-scatter", "all-gather"},
    ("all_reduce", "im"): {"all-reduce"},
    ("all_reduce", "naive"): {"all-gather"},
    ("all_reduce", "pr"): {"all-gather"},
    ("all_reduce", "ring"): {"collective-permute"},
    ("all_reduce", "tree"): {"collective-permute"},
    ("reduce_scatter", "im"): {"reduce-scatter"},
    ("reduce_scatter", "naive"): {"all-gather"},
    ("reduce_scatter", "pr"): {"all-gather"},
    ("all_gather", "im"): {"all-gather"},
    ("all_gather", "cm"): {"all-gather"},
    ("all_gather", "pr"): {"all-gather"},
    ("all_gather", "naive"): {"all-reduce"},
    ("all_to_all", "cm"): {"all-to-all"},
    ("all_to_all", "im"): {"collective-permute"},
    ("all_to_all", "naive"): {"all-gather"},
    ("all_to_all", "pr"): {"all-gather"},
}


def comm_drift(trace_summary: dict, collectives: dict) -> dict:
    """Cross-check the planner's recorded schedule (``CommTrace.summary()``)
    against the HLO-parsed ``collectives`` section of the same cell.

    Every (primitive, flow) the communicator dispatched must leave its
    expected collective ops in the compiled module; an expected op kind that
    never appears means the runtime executed something other than what the
    planner recorded (planner/runtime drift).  The byte comparison is
    informational only -- the HLO additionally contains autodiff-transposed
    collectives the trace cannot see -- except in one direction: compiled
    wire traffic below the drift band's low edge
    (:data:`repro.telemetry.drift.DEFAULT_BAND`, shared with the live
    drift monitor) flags over-estimation.
    """
    expected: set[str] = set()
    flows = []
    for key in trace_summary.get("by_flow", {}):
        primitive, flow = key.split("/", 1)
        want = _EXPECTED_HLO.get((primitive, flow))
        if want is None:       # rooted primitives: boundary transfer, no op
            continue
        flows.append(key)
        expected |= want
    present = {op for op, d in collectives.items() if d.get("count")}
    missing = sorted(expected - present)

    trace_bytes = (trace_summary.get("ici_bytes", 0.0)
                   + trace_summary.get("dcn_bytes", 0.0))
    hlo_bytes = sum(d.get("result_bytes", 0) for d in collectives.values())
    ratio = (hlo_bytes / trace_bytes) if trace_bytes > 0 else None
    drift = bool(missing) or (bool(flows) and trace_bytes > 0
                              and (hlo_bytes == 0
                                   or (ratio is not None
                                       and drift_mod.underrun(ratio))))
    return {"drift": drift, "missing_ops": missing,
            "checked_flows": sorted(flows),
            "expected_ops": sorted(expected), "hlo_ops": sorted(present),
            "hlo_over_trace_bytes": ratio}


def input_structs(cfg: ModelConfig, topo, shape: dict):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    S, B = shape["seq"], shape["batch"]
    sh = topo.cube.sharding
    dp = topo.dp

    def struct(shp, dt, spec):
        return jax.ShapeDtypeStruct(shp, dt, sharding=sh(spec))

    if shape["kind"] in ("train", "prefill"):
        batch = {"tokens": struct((B, S), jnp.int32, P(dp, None)),
                 "labels": struct((B, S), jnp.int32, P(dp, None))}
        if cfg.frontend == "patch":
            batch["patches"] = struct((B, cfg.frontend_tokens,
                                       cfg.frontend_dim), jnp.bfloat16,
                                      P(dp, None, None))
        if cfg.is_encoder_decoder:
            batch["frames"] = struct((B, S, cfg.frontend_dim), jnp.bfloat16,
                                     P(dp, None, None))
            # decoder operates on S/4 text tokens
            batch["tokens"] = struct((B, S // 4), jnp.int32, P(dp, None))
            batch["labels"] = struct((B, S // 4), jnp.int32, P(dp, None))
        if shape["kind"] == "prefill":
            batch.pop("labels")
        return batch
    raise ValueError(shape)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool) -> dict:
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "params_total": cfg.param_count(),
           "params_active": cfg.active_param_count()}

    if shape_name == "long_500k" and not cfg.subquadratic:
        rec["status"] = "skipped"
        rec["reason"] = ("pure full attention: 500k-token decode requires "
                         "sub-quadratic attention memory (see DESIGN.md)")
        return rec

    t0 = time.monotonic()
    # Dispatch happens at trace time, so lowering under a CommTrace records
    # the planned schedule of every communicator call site (one event per
    # textual site; scanned layers trace once).
    trace = CommTrace()
    from repro.core import program as program_mod
    lower_stats0 = dict(program_mod.LOWER_STATS)
    # per-cell telemetry scope: the comm/program/planner counters fired
    # while this cell lowers land in a fresh registry (no cross-cell
    # pollution), snapshotted into rec["telemetry"] below
    tscope = telemetry_metrics.scoped_metrics()
    if shape["kind"] == "train":
        topo = build_topology(cfg, mesh, global_batch=shape["batch"])
        tc = TrainConfig()
        step = make_train_step(cfg, topo, tc)
        pst = param_structs(cfg, topo)
        ost = opt_structs(cfg, topo, tc)
        bst = input_structs(cfg, topo, shape)
        with trace, tscope as treg:
            lowered = step.lower(pst, ost, bst)
    elif shape["kind"] == "prefill":
        topo = build_topology(cfg, mesh, global_batch=shape["batch"])
        server = Server(cfg, topo, None)
        specs = param_specs(cfg, topo)
        bst = input_structs(cfg, topo, shape)
        bspecs = {k: P(topo.dp, *([None] * (len(v.shape) - 1)))
                  for k, v in bst.items()}
        fn = shard_map(server.prefill_shard, mesh=topo.cube.mesh,
                       in_specs=(specs, bspecs),
                       out_specs=(P(topo.dp, topo.tp), _prefill_cache_spec(
                           server, cfg, topo)),
                       check_vma=False)
        with trace, tscope as treg:
            lowered = jax.jit(fn).lower(param_structs(cfg, topo), bst)
    else:  # decode
        topo = build_serve_topology(cfg, mesh)
        plan = make_serve_plan(cfg, topo, S_ctx=shape["seq"],
                               global_batch=shape["batch"])
        rec["serve_plan"] = dict(S_cache=plan.S_cache,
                                 batch_axes=plan.batch_axes,
                                 kv_axes=plan.kv_axes)
        server = Server(cfg, topo, plan)
        specs = param_specs(cfg, topo)
        cspecs = cache_specs(cfg, topo, plan)
        ba = plan.batch_axes or None
        B = plan.global_batch
        tok = jax.ShapeDtypeStruct((B,), jnp.int32,
                                   sharding=topo.cube.sharding(P(ba)))
        pos = jax.ShapeDtypeStruct((B,), jnp.int32,
                                   sharding=topo.cube.sharding(P(ba)))
        fn = shard_map(server.decode_shard, mesh=topo.cube.mesh,
                       in_specs=(specs, cspecs, P(ba), P(ba)),
                       out_specs=(P(ba, topo.tp), cspecs),
                       check_vma=False)
        with trace, tscope as treg:
            lowered = jax.jit(fn, donate_argnums=(1,)).lower(
                param_structs(cfg, topo), cache_structs(cfg, topo, plan),
                tok, pos)
    rec["cube"] = topo.cube.describe()
    rec["comm_trace"] = trace.summary()
    # estimate provenance: which cost model priced this cell's schedule
    # ("analytic" constants vs an installed measured CommProfile)
    rec["est_sources"] = rec["comm_trace"].get("est_sources", {})
    # deferred-program reuse during this cell's trace: schedules built vs
    # served from the cross-program lower cache (reuse shows up here when
    # a cell traces the same program structure more than once)
    rec["program_cache"] = {
        k: program_mod.LOWER_STATS[k] - lower_stats0[k]
        for k in program_mod.LOWER_STATS}
    # metrics fired while the cell lowered (dispatch happens at trace
    # time, so comm/program/planner instrumentation all landed in treg)
    rec["telemetry"] = treg.snapshot()
    rec["lower_s"] = round(time.monotonic() - t0, 1)

    t1 = time.monotonic()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.monotonic() - t1, 1)

    mem = compiled.memory_analysis()
    print(mem)
    rec["memory"] = {
        k: int(getattr(mem, k)) for k in
        ("argument_size_in_bytes", "output_size_in_bytes",
         "temp_size_in_bytes", "generated_code_size_in_bytes")
        if hasattr(mem, k)}
    cost = compiled.cost_analysis()
    print({k: v for k, v in cost.items()
           if k in ("flops", "bytes accessed")})
    rec["cost"] = {k: float(cost[k]) for k in
                   ("flops", "bytes accessed", "transcendentals",
                    "optimal_seconds") if k in cost}
    hlo = compiled.as_text()
    rec["collectives"] = parse_collectives(hlo)
    rec["planner_drift"] = comm_drift(rec["comm_trace"], rec["collectives"])
    rec["status"] = "ok"
    return rec


def _prefill_cache_spec(server, cfg, topo):
    """out_specs for the prefill cache: sequence over sp, stacked leaves."""
    from repro.models.config import ATTN, MAMBA, RWKV, RWKVCM
    unit = cfg.unit()
    out = {}
    for p in range(unit):
        mixer = cfg.mixers()[p]
        d = {}
        if mixer == ATTN:
            d["k"] = P(None, topo.dp, topo.sp, None, None)
            d["v"] = P(None, topo.dp, topo.sp, None, None)
            if cfg.is_encoder_decoder:
                d["xk"] = P(None, topo.dp, topo.sp, None, None)
                d["xv"] = P(None, topo.dp, topo.sp, None, None)
        elif mixer == MAMBA:
            d["ssm"] = P(None, topo.dp, topo.tp, None)
            d["conv"] = P(None, topo.dp, None, topo.tp)
        elif mixer == RWKV:
            d["state"] = P(None, topo.dp, topo.tp, None, None)
            d["shift"] = P(None, topo.dp, None)
        if cfg.ffns()[p] == RWKVCM:
            d["cm_shift"] = P(None, topo.dp, None)
        out[f"p{p}"] = d
    return out


def run_probe(arch: str, shape_name: str, *, multi_pod: bool,
              cfg_transform=None, **lower_kw) -> dict:
    """Two-point cost probe: XLA's cost_analysis counts a scan body once
    (not x trip count), so lower the same cell with n_layers = 1 unit and
    2 units and extrapolate linearly:

        cost(L units) = c1 + (L - 1) * (c2 - c1)

    which captures every per-layer term (fwd scan, remat bwd scan, per-layer
    collectives) exactly, and constant terms (embed/CE/IO) in the intercept.
    """
    import dataclasses as dc
    from repro.models import layers as layers_mod
    cfg0 = configs.get(arch)
    if cfg_transform is not None:
        cfg0 = cfg_transform(cfg0)
    unit = cfg0.unit()
    n_units = cfg0.n_layers // unit
    probes = []
    layers_mod.COST_PROBE = True
    try:
        for k in (1, 2):
            cfg = dc.replace(cfg0, n_layers=unit * k,
                             n_enc_layers=min(k, cfg0.n_enc_layers)
                             if cfg0.is_encoder_decoder else 0)
            probes.append(_lower_cell_cfg(cfg, shape_name,
                                          multi_pod=multi_pod, **lower_kw))
    finally:
        layers_mod.COST_PROBE = False
    c1, c2 = probes

    def xp(a, b):
        return a + (n_units - 1) * (b - a)

    cost = {k: xp(c1["cost"].get(k, 0.0), c2["cost"].get(k, 0.0))
            for k in set(c1["cost"]) | set(c2["cost"])}
    # extrapolate collective bytes per (op, group)
    coll = {}
    ops = set(c1["collectives"]) | set(c2["collectives"])
    for op in ops:
        d1 = c1["collectives"].get(op, {"by_group": {}})
        d2 = c2["collectives"].get(op, {"by_group": {}})
        groups = set(d1["by_group"]) | set(d2["by_group"])
        by_group = {}
        for g in groups:
            b1 = d1["by_group"].get(g, {"bytes": 0, "count": 0})
            b2 = d2["by_group"].get(g, {"bytes": 0, "count": 0})
            by_group[g] = {"bytes": xp(b1["bytes"], b2["bytes"]),
                           "count": xp(b1["count"], b2["count"])}
        coll[op] = {"by_group": by_group,
                    "result_bytes": sum(v["bytes"] for v in by_group.values()),
                    "count": sum(v["count"] for v in by_group.values())}
    return {"cost_x": cost, "collectives_x": coll,
            "probe_raw": [{"cost": c1["cost"], "collectives": c1["collectives"]},
                          {"cost": c2["cost"], "collectives": c2["collectives"]}],
            "n_units": n_units}


def _lower_cell_cfg(cfg, shape_name: str, *, multi_pod: bool,
                    resident: bool = False,
                    cache_dtype: str = "bf16",
                    serve_bf16: bool = False,
                    lowp: int = 0) -> dict:
    from repro.models import layers as layers_mod
    if lowp:
        layers_mod.LOWP = int(lowp)
    try:
        return _lower_cell_cfg_inner(
            cfg, shape_name, multi_pod=multi_pod, resident=resident,
            cache_dtype=cache_dtype, serve_bf16=serve_bf16)
    finally:
        layers_mod.LOWP = 0


def _lower_cell_cfg_inner(cfg, shape_name: str, *, multi_pod: bool,
                          resident: bool = False,
                          cache_dtype: str = "bf16",
                          serve_bf16: bool = False) -> dict:
    """Lower+compile one cell for an explicit cfg; return cost+collectives."""
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    if shape["kind"] == "train":
        topo = build_topology(cfg, mesh, global_batch=shape["batch"])
        tc = TrainConfig()
        step = make_train_step(cfg, topo, tc)
        lowered = step.lower(param_structs(cfg, topo),
                             opt_structs(cfg, topo, tc),
                             input_structs(cfg, topo, shape))
    elif shape["kind"] == "prefill":
        topo = build_topology(cfg, mesh, global_batch=shape["batch"])
        server = Server(cfg, topo, None)
        specs = param_specs(cfg, topo)
        bst = input_structs(cfg, topo, shape)
        bspecs = {k: P(topo.dp, *([None] * (len(v.shape) - 1)))
                  for k, v in bst.items()}
        fn = shard_map(server.prefill_shard, mesh=topo.cube.mesh,
                       in_specs=(specs, bspecs),
                       out_specs=(P(topo.dp, topo.tp),
                                  _prefill_cache_spec(server, cfg, topo)),
                       check_vma=False)
        lowered = jax.jit(fn).lower(param_structs(cfg, topo), bst)
    else:
        topo = build_serve_topology(cfg, mesh)
        plan = make_serve_plan(cfg, topo, S_ctx=shape["seq"],
                               global_batch=shape["batch"],
                               cache_dtype=cache_dtype)
        server = Server(cfg, topo, plan, resident=resident)
        specs = server.model.specs
        cspecs = cache_specs(cfg, topo, plan)
        ba = plan.batch_axes or None
        B = plan.global_batch
        tok = jax.ShapeDtypeStruct((B,), jnp.int32,
                                   sharding=topo.cube.sharding(P(ba)))
        pos = jax.ShapeDtypeStruct((B,), jnp.int32,
                                   sharding=topo.cube.sharding(P(ba)))
        fn = shard_map(server.decode_shard, mesh=topo.cube.mesh,
                       in_specs=(specs, cspecs, P(ba), P(ba)),
                       out_specs=(P(ba, topo.tp), cspecs), check_vma=False)
        def _dt(d):
            if serve_bf16 and d.dtype == jnp.float32:
                return jnp.bfloat16        # bf16-resident serve weights
            return d.dtype
        pstructs = jax.tree.map(
            lambda d, s: jax.ShapeDtypeStruct(
                d.shape, _dt(d), sharding=topo.cube.sharding(s)),
            param_defs_tree(cfg, topo), specs,
            is_leaf=lambda x: not isinstance(x, dict))
        lowered = jax.jit(fn, donate_argnums=(1,)).lower(
            pstructs, cache_structs(cfg, topo, plan), tok, pos)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    return {"cost": {k: float(cost[k]) for k in
                     ("flops", "bytes accessed", "transcendentals")
                     if k in cost},
            "collectives": parse_collectives(compiled.as_text())}


def param_defs_tree(cfg, topo):
    from repro.models.params import param_defs, ParamDef
    defs = param_defs(cfg, topo)
    return jax.tree.map(lambda d: d, defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--probe", action="store_true",
                    help="add two-point cost probes to existing cell JSONs")
    ap.add_argument("--profile", default=None, metavar="PROFILE_JSON",
                    help="price comm_trace estimates from a tuned "
                         "CommProfile instead of the analytic constants "
                         "(cells record est_source='measured')")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    import contextlib
    profile_ctx = contextlib.nullcontext()
    if args.profile:
        from repro.core.planner import install_profile
        from repro.tuning import CommProfile
        # no cube check: the production mesh is a fake-device stand-in, so
        # fingerprint enforcement is the caller's call here
        profile_ctx = install_profile(CommProfile.load(args.profile))

    if args.probe:
        with profile_ctx:
            probe_pass(args)
        return

    os.makedirs(args.out, exist_ok=True)
    cells = []
    archs = list(configs.ALIASES) if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.all else [args.multipod]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cells.append((arch, shape, mp))

    with profile_ctx:
        for arch, shape, mp in cells:
            tag = f"{arch}_{shape}_{'multipod' if mp else 'pod'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"== {tag}: cached")
                continue
            print(f"== {tag}")
            try:
                rec = run_cell(arch, shape, multi_pod=mp)
            except Exception as e:
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if mp else "16x16",
                       "status": "error", "error": repr(e),
                       "trace": traceback.format_exc()[-4000:]}
                print(rec["trace"])
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"   -> {rec['status']}")


def probe_pass(args):
    """Add cost probes to already-recorded cells (skips skipped/errored)."""
    archs = list(configs.ALIASES) if not args.arch else [args.arch]
    shapes = list(SHAPES) if not args.shape else [args.shape]
    meshes = [False, True] if not args.arch or args.all else [args.multipod]
    if args.arch and not args.all:
        meshes = [args.multipod]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'multipod' if mp else 'pod'}"
                path = os.path.join(args.out, tag + ".json")
                if not os.path.exists(path):
                    continue
                rec = json.load(open(path))
                if rec.get("status") != "ok" or "cost_x" in rec:
                    continue
                print(f"== probe {tag}")
                try:
                    rec.update(run_probe(arch, shape, multi_pod=mp))
                except Exception as e:
                    rec["probe_error"] = repr(e)
                    print("   probe failed:", repr(e))
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
