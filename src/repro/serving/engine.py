"""Continuous-batching decode engine over program-scheduled collectives.

One engine step serves every in-flight request at once and costs exactly:

  * **one recorded CommProgram** of rooted collectives -- the host->PE
    broadcasts of the step's control state (page table, admit/evict masks,
    prompt buffer, sampling temperatures, rng key) plus the PE->host gather
    of the *previous* step's sampled tokens.  The program is re-recorded
    every step (constants change) but its structure never does, so the
    PR 5 structural-fingerprint lower cache serves every step after the
    first (``LOWER_STATS["cache_hits"]`` grows by one per step) -- per-token
    collectives are planned once and overlap-scheduled under any installed
    profile;
  * **one jitted shard_map step** (:func:`make_step`) wrapping the paged
    flash-decode cell (:class:`repro.serving.pages.PagedServer`: the
    ``Server.decode_shard`` cell reading each layer's keys from the page
    pools inside the layer scan, in place through the paged decode kernel
    on TPU, with one row per lane written back after it) plus
    device-side sampling, so no logits ever cross to the host.

Scheduling is continuous batching with slot reuse: requests admit from the
arrival queue into free batch lanes, prefill runs *through the decode cell*
(chunk-1 chunked prefill: each step teacher-forces the next prompt token
while building the paged KV cache -- "prefill-then-decode" as phases of one
request, not separate kernels), decode samples on-device (greedy or
temperature via a sharded-vocab collective argmax), and completed requests
evict the next step, returning their pages to the pools.

Host bookkeeping is deterministic without token values (completion is
length-based: ``plen + max_new``), which is what lets sampled tokens flow
back with a one-step lag through the next program's gather instead of a
blocking per-step device round-trip.

Admission policies:
  * ``"reserve"`` (default): admit only when every shard can cover the
    request's full eventual page footprint net of pages already promised
    to in-flight requests -- allocation can then never fail mid-decode;
  * ``"lazy"``: admit optimistically as soon as a lane is free and the
    request's first block fits; if a shard's pool later runs dry, the
    youngest other request is **preempted** -- its pages are
    swapped to the host via the rooted gather
    (:func:`repro.serving.pages.extract_slot_pages`), freed, and the
    request re-queued; re-admission scatters the saved pages back
    (:func:`~repro.serving.pages.inject_slot_pages`).  Swap traffic is the
    only host-mediated cache motion and happens outside the per-step
    program, only on preemption events.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.models.config import ModelConfig
from repro.models.params import param_specs
from repro.models.serving import ServePlan, Server
from repro.models.topology import Topology
from repro.serving import pages as pages_mod
from repro.serving.pages import (
    PagedServer, PagePlan, PageTable, extract_slot_pages, init_paged_cache,
    inject_slot_pages, make_page_plan, paged_cache_specs)
from repro.telemetry import spans as _spans
from repro.telemetry.metrics import MetricsRegistry

Array = jax.Array
_I32MAX = np.int32(np.iinfo(np.int32).max)
SHARE_BUCKETS = (0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0)


@dataclasses.dataclass
class Request:
    """One decode request.  ``arrival`` is in engine steps (the bench maps a
    Poisson arrival trace onto it); ``temperature == 0`` samples greedily."""
    rid: int
    prompt: list[int]
    max_new: int
    temperature: float = 0.0
    arrival: int = 0
    # filled by the engine
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    submitted_at: float = math.nan     # time.perf_counter() at submit
    admitted_step: int = -1
    finished_step: int = -1
    preemptions: int = 0

    @property
    def plen(self) -> int:
        return len(self.prompt)

    @property
    def limit(self) -> int:
        """One past the last decoded position (= plen + max_new - 1)."""
        return self.plen + self.max_new - 1


def make_step(cfg: ModelConfig, topo: Topology, plan: ServePlan,
              pplan: PagePlan):
    """The engine's jitted step over the page pools (donated): paged decode
    of every lane, on-device sampling, and the lanes' advance.  Returns
    (sampled, toks, pos, active, prompts, new pools)."""
    paged = PagedServer(Server(cfg, topo, plan), pplan)
    P_max = plan.S_ctx
    vocab = cfg.vocab_size

    def step_shard(params, pcache, table, toks, pos, active, prompts,
                   admit, admit_tok, admit_pos, admit_prompts, plen,
                   evict, temps, key):
        tpc = topo.comm(topo.tp)
        # merge this step's schedule into the carried lane state
        active = (active & ~evict) | admit
        toks = jnp.where(admit, admit_tok, toks)
        pos = jnp.where(admit, admit_pos, pos)
        prompts = jnp.where(admit[:, None], admit_prompts, prompts)

        logits, pcache = paged.decode_shard(params, pcache, table,
                                            toks, pos)
        # ---- on-device sampling over the vocab-sharded logits
        V_loc = logits.shape[-1]
        me = compat.axis_index(topo.tp)
        gid = me * V_loc + jnp.arange(V_loc, dtype=jnp.int32)
        neg = jnp.finfo(jnp.float32).min
        logits = jnp.where(gid[None, :] < vocab, logits, neg)
        k = jax.random.fold_in(key, me)
        g = jax.random.gumbel(k, logits.shape, jnp.float32)
        warm = logits / jnp.maximum(temps, 1e-6)[:, None] + g
        eff = jnp.where(temps[:, None] > 0.0, warm, logits)
        # collective argmax: max over shards, then min global id
        # among the (bitwise-equal on the owner) maximizers
        m_loc = eff.max(axis=-1)
        m_all = tpc.all_reduce(m_loc, op="max")
        cand = jnp.where(eff == m_all[:, None], gid[None, :],
                         jnp.int32(_I32MAX)).min(axis=-1)
        sampled = tpc.all_reduce(cand, op="min")
        # ---- teacher-force prefill, advance the lanes
        nxt_p = jnp.take_along_axis(
            prompts, jnp.clip(pos + 1, 0, P_max - 1)[:, None],
            axis=1)[:, 0]
        nxt = jnp.where(pos + 1 < plen, nxt_p, sampled)
        toks = jnp.where(active, nxt, toks)
        pos = jnp.where(active, pos + 1, pos)
        return sampled, toks, pos, active, prompts, pcache

    pspec = param_specs(cfg, topo)
    cspec = paged_cache_specs(cfg, topo, plan, pplan)
    rep = P()
    fn = compat.shard_map(
        step_shard, mesh=topo.cube.mesh,
        in_specs=(pspec, cspec) + (rep,) * 13,
        out_specs=(rep, rep, rep, rep, rep, cspec),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(1,))


class ServeEngine:
    """Continuous-batching decode server on the serve topology."""

    def __init__(self, cfg: ModelConfig, topo: Topology, plan: ServePlan,
                 params, *, page_size: int = 4,
                 pages_per_shard: int | None = None,
                 admission: str = "reserve", seed: int = 0):
        if plan.batch_axes:
            raise NotImplementedError(
                "ServeEngine runs single-pod serve plans (batch replicated); "
                f"got batch_axes={plan.batch_axes}")
        if cfg.is_encoder_decoder:
            raise NotImplementedError(
                "encoder-decoder serving needs a cross-cache prefill path")
        if admission not in ("reserve", "lazy"):
            raise ValueError(f"unknown admission policy {admission!r}")
        self.cfg, self.topo, self.plan = cfg, topo, plan
        self.params = params
        self.admission = admission
        self.seed = seed
        self.pplan = make_page_plan(plan, topo, page_size=page_size,
                                    pages_per_shard=pages_per_shard)
        self.B = plan.global_batch
        self.P_max = plan.S_ctx
        self.rolling = plan.S_cache < plan.S_ctx

        self.table = PageTable(self.pplan, self.B)
        self.pcache = init_paged_cache(cfg, topo, plan, self.pplan)

        # host mirrors (deterministic: no token values needed)
        self.slot_req: list[Request | None] = [None] * self.B
        self.pos_h = np.zeros(self.B, np.int32)
        self.active_h = np.zeros(self.B, bool)
        self.plen_h = np.zeros(self.B, np.int32)
        self.limit_h = np.zeros(self.B, np.int32)
        self.temp_h = np.zeros(self.B, np.float32)
        self._admit_order = np.zeros(self.B, np.int64)  # admission stamp
        self._slot_commit = np.zeros((self.B, self.pplan.n_shards), np.int64)
        self._committed = np.zeros(self.pplan.n_shards, np.int64)

        # device-carried state
        self._toks = jnp.zeros(self.B, jnp.int32)
        self._pos = jnp.zeros(self.B, jnp.int32)
        self._active = jnp.zeros(self.B, bool)
        self._prompts = jnp.zeros((self.B, self.P_max), jnp.int32)
        self._sampled = jnp.zeros(self.B, jnp.int32)
        # lanes whose previous-step sample is a generated token:
        # (slot, request, generated-token index)
        self._meta: list[tuple[int, Request, int]] = []

        self.queue: list[Request] = []
        self.step_idx = 0
        self.programs_recorded = 0
        self.last_program = None   # most recent per-step CommProgram
        self.finished: list[Request] = []

        # Per-engine metrics registry (always on -- it replaces the old
        # step_wall/token_wall list bookkeeping and is the single source
        # run() and benchmarks/serving.py read latency/throughput from).
        self.metrics = MetricsRegistry()
        self._lower_hits = 0
        self._lower_lookups = 0

        self._step_fn = make_step(cfg, topo, plan, self.pplan)
        self._paged_kernel = float(any(
            pages_mod.reads_in_place(topo, plan, d["k"])
            for d in self.pcache.values() if "k" in d))

    # ------------------------------------------------------------- admission
    def submit(self, req: Request) -> None:
        if not req.prompt:
            raise ValueError(f"request {req.rid} has an empty prompt")
        if req.max_new < 1:
            raise ValueError(f"request {req.rid} asks for no tokens")
        if req.limit > self.plan.S_ctx:
            raise ValueError(
                f"request {req.rid} needs {req.limit} positions, over the "
                f"serve plan's S_ctx={self.plan.S_ctx}")
        need = self._need(req)
        if any(n > self.pplan.pages_per_shard for n in need):
            raise ValueError(
                f"request {req.rid} needs {max(need)} pages on one shard "
                f"but the pools hold {self.pplan.pages_per_shard} -- it "
                "could never run even alone")
        if math.isnan(req.submitted_at):
            req.submitted_at = time.perf_counter()
        self.queue.append(req)
        self.queue.sort(key=lambda r: r.arrival)

    def _need(self, req_or_state) -> list[int]:
        limit = (req_or_state["req"].limit
                 if isinstance(req_or_state, dict) else req_or_state.limit)
        return self.table.blocks_needed(min(limit, self.plan.S_cache))

    def _can_admit(self, entry) -> bool:
        if isinstance(entry, dict):        # resumed: exact saved footprint
            need = np.zeros(self.pplan.n_shards, np.int64)
            for j in np.nonzero(entry["valid"])[0]:
                need[self.pplan.owner(int(j))] += 1
            free = np.asarray(self.table.free_per_shard(), np.int64)
            return bool((free >= need).all())
        free = np.asarray(self.table.free_per_shard(), np.int64)
        if self.admission == "reserve":
            need = np.asarray(self._need(entry), np.int64)
            return bool((free - self._committed >= need).all())
        # lazy: optimistic -- only the request's first block must fit now;
        # a shard running dry later preempts (feasibility of the full
        # footprint against the pool size was checked at submit)
        need = np.asarray(self.table.blocks_needed(1), np.int64)
        return bool((free >= need).all())

    def _admit_into(self, slot: int, entry, admit, admit_tok, admit_pos,
                    admit_prompts) -> None:
        saved = entry if isinstance(entry, dict) else None
        req: Request = saved["req"] if saved else entry
        start = int(saved["pos"]) if saved else 0
        self.metrics.counter("serve.admitted").inc()
        self.slot_req[slot] = req
        self.pos_h[slot] = start
        self.active_h[slot] = True
        self.plen_h[slot] = req.plen
        self.limit_h[slot] = req.limit
        self.temp_h[slot] = req.temperature
        self._admit_order[slot] = self._stamp = getattr(
            self, "_stamp", 0) + 1
        if req.admitted_step < 0:              # first admission only
            req.admitted_step = self.step_idx
            self.metrics.histogram("serve.queue_seconds").observe(
                time.perf_counter() - req.submitted_at)
        need = np.asarray(self._need(req), np.int64)
        self._slot_commit[slot] = need
        self._committed += need
        admit[slot] = True
        admit_pos[slot] = start
        if start < req.plen:
            admit_tok[slot] = req.prompt[start]
        else:                               # resumed mid-decode
            admit_tok[slot] = req.out_tokens[start - req.plen]
        admit_prompts[slot, :req.plen] = np.asarray(req.prompt, np.int32)
        if saved:
            # re-allocate exactly the saved blocks, then scatter pages back
            req.preemptions += 1
            for j in np.nonzero(saved["valid"])[0]:
                assert self._ensure(slot, int(j) * self.pplan.page_size)
            self.pcache = inject_slot_pages(
                self.pcache, saved, self.table.table[slot], slot,
                self.pplan, self.topo, self.plan)

    def _ensure(self, slot: int, cache_pos: int) -> bool:
        j = self.table.block_of(cache_pos)
        fresh = self.table.table[slot, j] < 0
        if not self.table.ensure(slot, cache_pos):
            return False
        if fresh:
            sh = self.pplan.owner(j)
            if self._slot_commit[slot, sh] > 0:
                self._slot_commit[slot, sh] -= 1
                self._committed[sh] -= 1
        return True

    def _release(self, slot: int) -> None:
        self.table.free_slot(slot)
        self._committed -= self._slot_commit[slot]
        self._slot_commit[slot] = 0
        self.slot_req[slot] = None
        self.active_h[slot] = False

    def _preempt_for(self, slot: int, shard: int) -> bool:
        """Swap out the youngest other active request holding pages on
        ``shard``; returns False when no victim exists."""
        cands = [b for b in range(self.B)
                 if b != slot and self.active_h[b] and any(
                     self.table.table[b, j] >= 0
                     for j in range(self.pplan.n_blocks)
                     if self.pplan.owner(j) == shard)]
        if not cands:
            return False
        victim = max(cands, key=lambda b: self._admit_order[b])
        self._drain()                       # bank pending sampled tokens
        req = self.slot_req[victim]
        saved = extract_slot_pages(self.pcache, self.table.table[victim],
                                   victim, self.pplan, self.topo, self.plan)
        saved["req"] = req
        saved["pos"] = int(self.pos_h[victim])
        self._release(victim)
        self._evict_next[victim] = True     # device lane off next program
        self.queue.insert(0, saved)
        self.metrics.counter("serve.preempted").inc()
        return True

    # ------------------------------------------------------------- stepping
    def _drain(self) -> None:
        """Apply pending generated-token bookkeeping from the device copy
        (used before swaps and at end of run; normally the next step's
        program gather does this without blocking)."""
        if not self._meta:
            return
        vals = np.asarray(jax.device_get(self._sampled))
        self._apply_meta(vals)

    def _apply_meta(self, sampled: np.ndarray) -> None:
        for slot, req, gi in self._meta:
            tok = int(sampled[slot])
            if gi == len(req.out_tokens):
                req.out_tokens.append(tok)
        self._meta = []

    def step(self) -> None:
        """One engine step: evict / admit / record-and-run the step program
        / run the jitted paged-decode + sampling cell.  Spans: ``serve.step``
        around ``serve.schedule``, ``serve.program``, ``serve.decode``,
        ``serve.wait`` and ``serve.mirror``."""
        with _spans.maybe_span("serve.step", step=self.step_idx):
            self._step_inner()

    def _step_inner(self) -> None:
        t0 = time.perf_counter()
        B = self.B
        with _spans.maybe_span("serve.schedule"):
            admit, admit_tok, admit_pos, admit_prompts = self._schedule()
        evict = self._evict_next
        key = np.array([np.uint32(self.seed), np.uint32(self.step_idx)],
                       np.uint32)

        # -- ONE recorded CommProgram per decode step: the rooted host->PE
        #    broadcasts of control state + the PE->host gather of the
        #    previous step's sampled tokens.  Structure is step-invariant,
        #    so lowering is a structural-fingerprint cache hit from step 1.
        with _spans.maybe_span("serve.program"):
            kvc = self.topo.comm(self.plan.kv_axes)
            prog = self.topo.cube.program(name="serve-step")
            with prog:
                prev = prog.input(jax.ShapeDtypeStruct((B,), jnp.int32))
                outs = [kvc.broadcast(self.table.array()),
                        kvc.broadcast(admit), kvc.broadcast(admit_tok),
                        kvc.broadcast(admit_pos),
                        kvc.broadcast(admit_prompts),
                        kvc.broadcast(self.plen_h.copy()),
                        kvc.broadcast(evict),
                        kvc.broadcast(self.temp_h.copy()),
                        kvc.broadcast(key), kvc.gather(prev)]
                prog.output(*outs)
            from repro.core.program import LOWER_STATS
            hits0, low0 = LOWER_STATS["cache_hits"], LOWER_STATS["lowered"]
            (table_d, admit_d, atok_d, apos_d, aprm_d, plen_d, evict_d,
             temp_d, key_d, prev_host) = prog.execute(self._sampled)
            self._lower_hits += LOWER_STATS["cache_hits"] - hits0
            self._lower_lookups += (LOWER_STATS["cache_hits"] - hits0
                                    + LOWER_STATS["lowered"] - low0)
            if self._lower_lookups:
                self.metrics.gauge("serve.lower_cache_hit_ratio").set(
                    self._lower_hits / self._lower_lookups)
            self.programs_recorded += 1
            self.last_program = prog
            self._apply_meta(np.asarray(prev_host))

        # -- the fused paged-decode + on-device-sampling step
        with _spans.maybe_span("serve.decode"):
            (self._sampled, self._toks, self._pos, self._active,
             self._prompts, self.pcache) = self._step_fn(
                self.params, self.pcache, table_d, self._toks, self._pos,
                self._active, self._prompts, admit_d, atok_d, apos_d,
                aprm_d, plen_d, evict_d, temp_d, key_d)
        with _spans.maybe_span("serve.wait"):
            jax.block_until_ready(self._sampled)

        with _spans.maybe_span("serve.mirror"):
            self._mirror(t0)

    def _schedule(self):
        """Evict finished lanes, admit from the queue into free lanes and
        allocate this step's write blocks; returns the admit arrays."""
        B, pplan = self.B, self.pplan
        self._evict_next = np.zeros(B, bool)

        # -- evict lanes that finished last step (their final token arrives
        #    through this step's gather, recorded in _meta)
        for b in range(B):
            if self.active_h[b] and self.pos_h[b] >= self.limit_h[b]:
                req = self.slot_req[b]
                req.finished_step = self.step_idx
                self.finished.append(req)
                self._release(b)
                self._evict_next[b] = True
                self.metrics.counter("serve.evicted").inc()

        # -- admit from the arrival queue into free lanes
        admit = np.zeros(B, bool)
        admit_tok = np.zeros(B, np.int32)
        admit_pos = np.zeros(B, np.int32)
        admit_prompts = np.zeros((B, self.P_max), np.int32)
        while self.queue:
            head = self.queue[0]
            arr = (head["req"].arrival if isinstance(head, dict)
                   else head.arrival)
            if arr > self.step_idx:
                break
            free = [b for b in range(B) if not self.active_h[b]]
            if not free or not self._can_admit(head):
                break
            self.queue.pop(0)
            self._admit_into(free[0], head, admit, admit_tok, admit_pos,
                             admit_prompts)

        # -- allocate this step's write blocks (deterministic on host);
        #    under lazy admission a dry shard triggers preemption
        for b in range(B):
            if not self.active_h[b]:
                continue
            wp = int(self.pos_h[b]) % self.plan.S_cache
            while not self._ensure(b, wp):
                sh = pplan.owner(self.table.block_of(wp))
                if not self._preempt_for(b, sh):
                    raise RuntimeError(
                        f"page pools exhausted on shard {sh} and no "
                        "preemptible request holds pages there")

        free = np.asarray(self.table.free_per_shard(), np.int64)
        total_pages = self.pplan.n_shards * self.pplan.pages_per_shard
        self.metrics.gauge("serve.page_occupancy").set(
            1.0 - float(free.sum()) / total_pages if total_pages else 0.0)
        self.metrics.gauge("serve.paged_kernel").set(self._paged_kernel)
        self.metrics.histogram(
            "serve.kv_read_share", buckets=SHARE_BUCKETS).observe(
            self.table.read_share(self.pos_h))
        return admit, admit_tok, admit_pos, admit_prompts

    def _mirror(self, t0: float) -> None:
        """Advance the host mirrors deterministically, note which lanes
        just produced a *generated* (post-prefill) token, and count the
        step that started at ``t0`` (``time.perf_counter``)."""
        gen_this_step = 0
        for b in range(self.B):
            if not self.active_h[b]:
                continue
            p = int(self.pos_h[b])
            if p + 1 >= self.plen_h[b]:
                req = self.slot_req[b]
                self._meta.append((b, req, p + 1 - int(self.plen_h[b])))
                gen_this_step += 1
            self.pos_h[b] = p + 1
        self.step_idx += 1
        dt = time.perf_counter() - t0
        self.metrics.counter("serve.steps").inc()
        self.metrics.histogram("serve.step_seconds").observe(dt)
        if gen_this_step:
            self.metrics.counter("serve.generated_tokens").inc(
                gen_this_step)
            tok_hist = self.metrics.histogram("serve.token_seconds")
            for _ in range(gen_this_step):
                tok_hist.observe(dt)

    # ------------------------------------------------------------------ run
    def run(self, requests: list[Request] | None = None, *,
            max_steps: int = 10_000) -> dict[str, Any]:
        """Drive the arrival trace to completion; returns throughput and
        per-token latency metrics plus the finished requests."""
        for r in requests or []:
            self.submit(r)
        t0 = time.perf_counter()
        while (self.queue or self.active_h.any()):
            if self.step_idx >= max_steps:
                raise RuntimeError(f"no convergence in {max_steps} steps")
            self.step()
        self._drain()
        wall = time.perf_counter() - t0
        # Single measurement path: throughput and per-token percentiles
        # come from the engine's metrics registry (the token_seconds
        # histogram retains raw samples, so quantile() reproduces the
        # historical sorted-array formula exactly).
        n_tok = int(self.metrics.value("serve.generated_tokens"))
        tps = n_tok / wall if wall > 0 else 0.0
        self.metrics.gauge("serve.tokens_per_s").set(tps)
        return {
            "steps": self.step_idx,
            "wall_s": wall,
            "generated_tokens": n_tok,
            "tokens_per_s": tps,
            "p50_token_s": self.metrics.quantile("serve.token_seconds",
                                                 0.50),
            "p99_token_s": self.metrics.quantile("serve.token_seconds",
                                                 0.99),
            "programs_recorded": self.programs_recorded,
            "preemptions": sum(r.preemptions for r in self.finished),
            "finished": list(self.finished),
        }

    def reset_metrics(self) -> None:
        """Zero the registry and run-scoped bookkeeping (warmup boundary
        for benchmarks); in-flight request state is untouched."""
        self.metrics.reset()
        self._lower_hits = 0
        self._lower_lookups = 0
        self.programs_recorded = 0
        self.finished.clear()


def poisson_trace(n_requests: int, *, rate: float, plen_range=(4, 16),
                  max_new_range=(4, 12), temperature: float = 0.0,
                  vocab: int = 256, seed: int = 0) -> list[Request]:
    """A Poisson arrival trace (``rate`` = mean arrivals per engine step)
    with mixed prompt/output lengths -- the bench and example workload."""
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / max(rate, 1e-9), n_requests)
    arrivals = np.floor(np.cumsum(gaps)).astype(int)
    reqs = []
    for i in range(n_requests):
        plen = int(rng.randint(plen_range[0], plen_range[1] + 1))
        reqs.append(Request(
            rid=i,
            prompt=rng.randint(0, vocab, plen).astype(int).tolist(),
            max_new=int(rng.randint(max_new_range[0],
                                    max_new_range[1] + 1)),
            temperature=temperature,
            arrival=int(arrivals[i])))
    return reqs


__all__ = ["Request", "ServeEngine", "make_step", "poisson_trace"]
