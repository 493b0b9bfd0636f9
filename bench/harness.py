"""What every run shares: finding a cell's pieces by name, the device
check, the compile counter, the traced stretch and the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_piece_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its pieces loaded."""
    name: str
    chips: int
    config: dict               # the configuration file
    traffic: dict              # the traffic mix's data file
    end_to_end: list           # metric entries this cell reports
    per_layer: list

    @property
    def reference(self):
        """The configuration's plain reference, beside its file."""
        return _module(BENCH / "configs" / (self.config["reference"] + ".py"))

    def runner(self):
        """The general runner of this kind of traffic."""
        return _module(BENCH / "runners" / (self.traffic["kind"] + ".py"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec: dict | None = None) -> Cell:
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    per_layer = [m for m in spec["per_layer"] if _applies(m, name)]
    e2e_names = {m["name"] for m in spec["end_to_end"] if _applies(m, name)}
    return Cell(
        name=name, chips=int(wl["chips"]),
        config=load_json(ROOT / cfg_entry["file"]),
        traffic=load_json(BENCH / "traffic" / (wl["traffic"] + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in per_layer if m["moves"] in e2e_names])


def read_metric(name: str, rec: dict):
    """A per-layer metric from the run's records: ``metrics/<name>.py``'s
    ``read(rec)``, which returns None where it finds nothing to read."""
    return _module(BENCH / "metrics" / (name + ".py")).read(rec)


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table["devices"][device_kind]


def chips(n: int):
    """The first ``n`` accelerator devices; raises NoChip off TPU or with
    fewer than ``n``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports platform {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX reports {len(devs)}")
    return devs[:n]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileCounter:
    """JAX's own compile events: seconds spent tracing, lowering and
    compiling, how many programs were compiled or fetched from the
    persistent cache (``compiles``), and how many of those the cache did
    not hold (``misses``)."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    CACHE = ("/jax/compilation_cache/compile_requests_use_cache",
             "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.traces = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        jax.monitoring.register_event_listener(self._cache)

    def _cache(self, event, **_):
        self.misses += ((event == self.CACHE[0]) - (event == self.CACHE[1]))

    def _listen(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs
        if event == self.EVENTS[2]:
            self.compiles += 1
        if event == self.EVENTS[0]:
            self.traces += 1

    def mark(self) -> tuple[int, int]:
        return self.compiles, self.traces


@contextlib.contextmanager
def annotate(name: str):
    """A host span in the profiler's own trace (a no-op when not tracing)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def traced(out: dict):
    """Profile the block into ``TRACE_DIR`` and reduce it: ``out`` gets
    the reduction of ``bench.trace``."""
    import jax
    from bench import trace
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR))
    try:
        with annotate(trace.WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()
    out.update(trace.reduce_dir(TRACE_DIR))


def line(obj: dict) -> str:
    return json.dumps(obj, separators=(", ", ": "))


def report(cell: Cell, *, trace_on: bool, correct: bool, attempted: int,
           failed: int, values: dict, rec: dict, device: dict,
           checks: list) -> dict:
    """The result object. ``values``: end-to-end metric name -> number;
    ``rec``: what the per-layer readers read; ``checks``: (name, value,
    limit) of every number compared for ``correct``."""
    metrics = {}
    if trace_on:
        for m in cell.per_layer:
            v = read_metric(m["name"], rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        tr = rec.get("trace") or {}
        device = dict(device, busy_s=tr.get("busy_s"),
                      window_s=tr.get("window_s"))
    else:
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if trace_on and rec.get("trace"):
        out["breakdown"] = rec["trace"]["breakdown"]
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return out


def print_checks(checks: list) -> None:
    for n, v, lim in checks:
        print(f"check {n}: {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'OVER'}", file=sys.stderr, flush=True)


def now() -> float:
    return time.perf_counter()
