"""Traffic of kind ``serve``: an open loop of requests arriving in wall
time at a fixed rate, driven through ``ServeEngine.submit`` / ``step``.

The arrivals run without a break through three stretches: ``ramp_s``
before the window (set-up: the lanes fill to their steady occupancy), the
window itself, and up to ``drain_s`` after it, while the requests due in
the window finish under the same load. Only the requests due in the window
are measured. Each stretch's sizes and gaps come from a fixed table
(``table_seed`` and the stretch); the run's seed orders them and draws
every prompt's tokens, so every seed offers the same work in another
order. Each request is timed from when it was due: time to first token
(TTFT) is due -> first generated token on the host, time per output token
(TPOT) is the mean gap between its later tokens. A request due in the
window that never finishes counts as failed. Sampling is greedy, so a
sample of the finished requests, drawn from the seed with the longest
among them, is checked against the reference's teacher-forced logits.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import statistics
import time

import numpy as np

from bench import flops, harness, weights

WINDOW, RAMP, AFTER = 0, 1, 2       # stretches of the arrival table


@dataclasses.dataclass
class Timed:
    """A request and its times: ``due`` in seconds after the loop's start,
    every other time on the harness's clock."""
    due: float
    req: object
    submitted: float = math.nan
    admitted: float = math.nan
    first: float = math.nan
    done: float = math.nan


def lognormal_sizes(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(int)


def stretch(tr: dict, seed: int, part: int, start: float, seconds: float,
            vocab: int) -> list[Timed]:
    """Every request due in [start, start + seconds): rate x seconds of
    them, with sizes and exponential gaps from the fixed table of ``part``
    (the gaps scaled to fill the stretch), in an order drawn from the seed,
    which also draws every prompt's tokens."""
    from repro.serving.engine import Request
    n = max(1, int(round(tr["rate_per_s"] * seconds)))
    tseed = tr["table_seed"]
    table = np.random.default_rng(tseed if part == WINDOW else [tseed, part])
    plens = lognormal_sizes(table, tr["prompt"], n)
    outs = lognormal_sizes(table, tr["output"], n)
    gaps = table.exponential(1.0, n)
    gaps *= seconds * n / (n + 1) / gaps.sum()
    rng = np.random.default_rng([int(seed), 11] if part == WINDOW
                                else [int(seed), 11, part])
    order = rng.permutation(n)
    due = start + np.cumsum(gaps[rng.permutation(n)])
    return [Timed(float(due[i]), Request(
        rid=part * 10 ** 6 + i,
        prompt=rng.integers(0, vocab, int(plens[k])).tolist(),
        max_new=int(outs[k]), temperature=0.0))
        for i, k in enumerate(order)]


def schedule(tr: dict, seed: int, seconds: float, vocab: int):
    """The ramp's, the window's and the after-window's requests, due in
    [0, ramp), [ramp, ramp + seconds) and the ``drain_s`` after that."""
    ramp = tr["ramp_s"]
    return (stretch(tr, seed, RAMP, 0.0, ramp, vocab),
            stretch(tr, seed, WINDOW, ramp, seconds, vocab),
            stretch(tr, seed, AFTER, ramp + seconds, tr["drain_s"], vocab))


def build_engine(c: dict, tr: dict, seed: int, devices):
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import serve_topology
    from repro.models.params import param_structs
    from repro.models.serving import make_serve_plan
    from repro.serving.engine import ServeEngine
    cfg = weights.program_config(c)
    topo = serve_topology(cfg, devices=devices)
    plan = make_serve_plan(cfg, topo, S_ctx=tr["s_ctx"],
                           global_batch=tr["lanes"])
    shard = jax.tree.map(lambda s: s.sharding, param_structs(cfg, topo))
    params = weights.make_program_weights(seed, c, jnp.bfloat16, shard)
    return ServeEngine(cfg, topo, plan, params, page_size=tr["page_size"],
                       seed=seed & 0xFFFFFFFF)


def warm(engine, tr: dict) -> None:
    """Every lane admits, prefills, decodes and retires once: the step
    program and the per-step collective program are compiled."""
    from repro.serving.engine import Request
    for i in range(tr["lanes"]):
        engine.submit(Request(rid=-1 - i, prompt=[1, 2, 3], max_new=2))
    while engine.queue or engine.active_h.any():
        engine.step()
    engine.reset_metrics()


class Loop:
    """Drives an engine through an arrival schedule on the wall clock.
    A request's last token reaches the host with the engine's next step,
    so the loop steps while any submitted request is unfinished."""

    def __init__(self, engine, c: dict, arrivals: list[Timed]):
        self.engine, self.c = engine, c
        self.arrivals = sorted(arrivals, key=lambda t: t.due)
        self.next = 0
        self.t0 = harness.now()
        self.live: list[Timed] = []
        self.busy: list[int] = []       # lanes in use after each step
        self.steps: list[tuple[float, float]] = []   # (flops, bytes) each
        self.record_steps = False

    def _after_step(self, t_step: float) -> None:
        eng, now = self.engine, harness.now()
        still = []
        for t in self.live:
            r = t.req
            if math.isnan(t.admitted) and r.admitted_step >= 0:
                t.admitted = t_step
            n = len(r.out_tokens)
            if n and math.isnan(t.first):
                t.first = now
            if n >= r.max_new:
                t.done = now
            else:
                still.append(t)
        self.live = still
        self.busy.append(int(eng.active_h.sum()))
        if self.record_steps:
            lens = [int(p) for p, a in zip(eng.pos_h, eng.active_h) if a]
            self.steps.append((flops.decode_step_flops(self.c, lens),
                               flops.decode_step_bytes(self.c, lens)))

    def run(self, until: float, done=lambda: False) -> None:
        """Step until ``until`` seconds after the loop's start, or until
        ``done()``, submitting every request when it falls due."""
        eng, arr = self.engine, self.arrivals
        while not done():
            t = harness.now() - self.t0
            if t >= until:
                break
            while self.next < len(arr) and arr[self.next].due <= t:
                a = arr[self.next]
                a.submitted = harness.now()
                eng.submit(a.req)
                self.live.append(a)
                self.next += 1
            if eng.queue or eng.active_h.any() or self.live:
                ts = harness.now()
                with harness.annotate("bench.engine_step"):
                    eng.step()
                self._after_step(ts)
            elif self.next < len(arr):
                with harness.annotate("bench.wait_arrival"):
                    time.sleep(max(0.0, min(arr[self.next].due - t, 0.01)))
            else:
                break


DECODE_MODULE = "jit_step_shard"     # the engine's jitted decode step


def quantile(xs, q: float) -> float:
    """The smallest value with at least a share ``q`` of ``xs`` at or
    under it."""
    xs = sorted(xs)
    return xs[min(len(xs) - 1, max(0, int(math.ceil(q * len(xs))) - 1))]


def run(ctx) -> dict:
    c, tr, seed = ctx.cell.config, ctx.cell.traffic, ctx.seed
    with harness.annotate("bench.setup.engine"):
        engine = build_engine(c, tr, seed, ctx.devices)
    ctx.phase("engine")
    with harness.annotate("bench.setup.warm"):
        warm(engine, tr)
    ctx.phase("warm")
    ramp, window, after = schedule(tr, seed, ctx.seconds, c["vocab_size"])
    loop = Loop(engine, c, ramp + window + after)
    close = tr["ramp_s"] + ctx.seconds
    with harness.annotate("bench.setup.ramp"):
        loop.run(tr["ramp_s"])
    ctx.phase("ramp")

    engine.reset_metrics()
    k0 = len(loop.busy)
    ctx.start_window()
    loop.run(close)
    hist = engine.metrics.histogram("serve.step_seconds")
    n_steps, step_mean = hist.count, hist.sum / max(hist.count, 1)
    ctx.end_window()
    busy = loop.busy[k0:]

    trace = {}
    if ctx.trace:
        loop.record_steps = True
        with harness.traced(trace):
            loop.run(close + tr["trace_seconds"])
        loop.record_steps = False
    loop.run(close + tr["drain_s"],
             done=lambda: all(not math.isnan(t.done) for t in window))
    peak = harness.peak_bytes(ctx.devices)

    t0 = loop.t0
    ok = [t for t in window if not math.isnan(t.done)]
    ttft = [t.first - (t0 + t.due) if not math.isnan(t.first) else math.inf
            for t in window]
    tpot = [(t.done - t.first) / (t.req.max_new - 1) for t in ok
            if t.req.max_new > 1]
    late = [t.submitted - (t0 + t.due) for t in window]
    queue = [t.admitted - (t0 + t.due) for t in window
             if not math.isnan(t.admitted)]
    lanes = {"at_open": loop.busy[k0 - 1] if k0 else 0,
             "mean": statistics.fmean(busy) if busy else 0.0,
             "of": tr["lanes"]}
    ctx.note(f"{len(window)} requests due, {len(ok)} finished; generator "
             f"late p50 {statistics.median(late)} max {max(late)} s; "
             f"engine steps {n_steps}; lanes in use {lanes}; ttft p95 "
             f"{quantile(ttft, 0.95)} s, tpot p95 "
             f"{1e3 * quantile(tpot, 0.95) if tpot else math.inf} ms")

    # the check: a seeded sample of the finished requests, longest first
    rng = np.random.default_rng([int(seed), 13])
    pick = sorted(ok, key=lambda t: -t.req.max_new)[:1]
    rest = [ok[i] for i in rng.permutation(len(ok)) if ok[i] not in pick]
    while rest and sum(t.req.max_new for t in pick) < tr["check_tokens"]:
        pick.append(rest.pop())
    rows = [(list(t.req.prompt), list(t.req.out_tokens)) for t in pick]
    traced_steps = loop.steps
    del engine, loop
    gc.collect()
    gap = check(ctx.cell.reference, c, seed, rows, tr["s_ctx"])
    return {
        "attempted": len(window), "failed": len(window) - len(ok),
        "peak_bytes": peak,
        "values": {"ttft_p50": statistics.median(ttft),
                   "tpot_p50": 1e3 * statistics.median(tpot)
                   if tpot else math.inf},
        "rec": {"trace": trace,
                "engine_step_s_mean": step_mean if n_steps else None,
                "queue_s_mean": statistics.fmean(queue) if queue else None,
                "trace_steps": traced_steps,
                "decode_module": DECODE_MODULE},
        "numbers": {"logit_gap": gap},
        "extra": {"generator_late_s": {"p50": statistics.median(late),
                                       "max": max(late)},
                  "lanes_in_use": lanes},
    }


def tokens_and_first(rows, s_ctx: int):
    """Each request's prompt and served tokens, padded to ``s_ctx``, with
    the first and last positions whose next token was served."""
    toks = np.zeros((len(rows), s_ctx), np.int32)
    first, last = [], []
    for n, (prompt, out) in enumerate(rows):
        seq = prompt + out
        toks[n, :len(seq)] = seq
        first.append(len(prompt) - 1)
        last.append(len(seq) - 2)
    return toks, first, last


def check(ref, c: dict, seed: int, rows, s_ctx: int) -> float:
    """The widest gap by which a served token's logit lies below the
    reference's largest, over every served position of ``rows``."""
    if not rows:
        return math.inf
    toks, first, last = tokens_and_first(rows, s_ctx)
    got = ref.teacher_forced(c, seed, toks)
    gap = 0.0
    for n in range(len(rows)):
        z = got[n, first[n]:last[n] + 1]
        gap = max(gap, float(np.max(z[:, 0] - z[:, 2])))
    return gap
