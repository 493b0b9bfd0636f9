"""Communicator-centric collective API (PID-Comm §IV, Table II, §IX-A).

This module is the single choke point through which every collective in the
repo is planned, dispatched and observed:

  ``cube.comm(dims)``
      binds a :class:`~repro.core.hypercube.Hypercube` and a resolved dim
      selection into a :class:`Communicator` handle, caching the group size,
      the fast/slow (ICI/DCN) split and the instance count once, and exposes
      the eight PID-Comm primitives as methods.

  algorithm registry
      every executable flow is a registered algorithm --
      ``@register_algorithm("all_to_all", "im")``.  The paper's Table II
      ablation stages (``naive``/``pr``/``im``/``cm``) are registered per
      primitive, and the applicability table is *derived from the registry*
      rather than maintained by hand.  First-class non-stage algorithms ride
      the same rails: the §IX-A ``hierarchical`` split, the §V-C int8
      ``compressed`` DCN flow, and the Fig. 23(a) ``ring`` / ``tree``
      topology comparators.

  plan-driven dispatch
      ``algorithm="auto"`` (the default) consults the analytic planner at
      trace time -- payload shapes are static under jit -- so the executed
      flow (direct vs hierarchical vs naive) is the cost model's pick.  This
      unifies :mod:`repro.core.planner` with the runtime: what the planner
      estimates is what the communicator lowers.

  instrumentation
      every dispatch appends a :class:`CommEvent` (primitive, bitmap, chosen
      flow/stage, estimated ICI/DCN bytes and seconds) to any active
      :class:`CommTrace` context.  ``launch/dryrun.py`` and the benchmark
      harness consume the trace for their ``derived`` columns.  Every
      dispatch also runs its body under the planner's key
      (:func:`scope_name`), so each device op of the collective carries,
      in its HLO ``op_name``, the key that priced it;
      :func:`scope_estimate` prices a key again.

The legacy :class:`repro.core.collectives.Collectives` class survives as a
thin deprecated shim delegating here, so the conformance matrix runs
bit-identically through either surface.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.xla_metadata import set_xla_metadata
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import planner
from repro.core.hypercube import Hypercube
from repro.telemetry import metrics as _telemetry

Array = jax.Array

# Canonical Table II stage ladder, weakest to strongest.
STAGE_ORDER = ("naive", "pr", "im", "cm")

PRIMITIVES = ("all_to_all", "reduce_scatter", "all_reduce", "all_gather",
              "scatter", "gather", "reduce", "broadcast")

_REDUCERS = {
    "add": (lax.psum, jnp.sum, jnp.add),
    "max": (lax.pmax, jnp.max, jnp.maximum),
    "min": (lax.pmin, jnp.min, jnp.minimum),
}


# ============================================================ the registry
@dataclasses.dataclass(frozen=True)
class AlgorithmSpec:
    """One registered collective flow."""
    primitive: str
    name: str            # registry key ("im", "hierarchical", "ring", ...)
    stage: str           # the Table II stage this flow maps onto
    table_ii: bool       # counts toward the derived applicability table
    fn: Callable         # body: fn(comm, x, **kwargs) -> Array


_REGISTRY: dict[str, dict[str, AlgorithmSpec]] = {p: {} for p in PRIMITIVES}
_APPLICABILITY_CACHE: dict[str, tuple[str, ...]] | None = None


def register_algorithm(primitive: str, name: str, *, stage: str | None = None,
                       table_ii: bool | None = None):
    """Decorator registering a collective algorithm body.

    ``stage`` defaults to ``name`` when the name is a Table II stage;
    ``table_ii`` defaults to True exactly for stage names, so extras
    (``hierarchical``, ``compressed``, ``ring``, ``tree``) do not widen the
    paper's applicability table.
    """
    if primitive not in _REGISTRY:
        raise ValueError(f"unknown primitive {primitive!r}")
    is_stage = name in STAGE_ORDER
    if stage is None:
        if not is_stage:
            raise ValueError(f"algorithm {name!r} needs an explicit stage=")
        stage = name
    if table_ii is None:
        table_ii = is_stage

    def deco(fn):
        global _APPLICABILITY_CACHE
        if name in _REGISTRY[primitive]:
            raise ValueError(
                f"algorithm {name!r} already registered for {primitive!r}")
        _REGISTRY[primitive][name] = AlgorithmSpec(
            primitive=primitive, name=name, stage=stage,
            table_ii=table_ii, fn=fn)
        _APPLICABILITY_CACHE = None
        return fn

    return deco


def get_algorithm(primitive: str, name: str) -> AlgorithmSpec:
    try:
        return _REGISTRY[primitive][name]
    except KeyError:
        raise ValueError(
            f"no algorithm {name!r} registered for {primitive!r}; have "
            f"{sorted(_REGISTRY.get(primitive, ()))}") from None


def registered_algorithms(primitive: str) -> tuple[str, ...]:
    return tuple(_REGISTRY[primitive])


def applicability() -> dict[str, tuple[str, ...]]:
    """Paper Table II, derived from the registry: the ordered tuple of
    optimization stages registered (as ``table_ii``) per primitive.  Cached
    until the next registration (resolve_stage consults it per dispatch)."""
    global _APPLICABILITY_CACHE
    if _APPLICABILITY_CACHE is None:
        out = {}
        for prim, algs in _REGISTRY.items():
            stages = {a.name for a in algs.values() if a.table_ii}
            out[prim] = tuple(s for s in STAGE_ORDER if s in stages)
        _APPLICABILITY_CACHE = out
    return _APPLICABILITY_CACHE


def resolve_stage(primitive: str, algorithm: str) -> str:
    """Resolve an algorithm request against Table II: ``pidcomm`` means the
    strongest applicable stage; an inapplicable request falls back to the
    strongest applicable stage at or below it."""
    stages = applicability()[primitive]
    if algorithm == "pidcomm":
        return stages[-1]
    if algorithm not in STAGE_ORDER:
        raise ValueError(f"unknown algorithm {algorithm!r}")
    req = STAGE_ORDER.index(algorithm)
    best = stages[0]
    for s in stages:
        if STAGE_ORDER.index(s) <= req:
            best = s
    return best


# ppermute ladders get HLO-quadratic beyond this group size; the dispatcher
# falls through to the fused native collective there (the schedules coincide
# anyway).  Tunable: monkeypatch ``comm._LADDER_MAX`` (the legacy shim
# re-exposes it read-only as ``collectives._LADDER_MAX``).
_LADDER_MAX = 32


# ======================================================== instrumentation
@dataclasses.dataclass(frozen=True)
class CommEvent:
    """One dispatched collective, recorded at trace time."""
    primitive: str
    bitmap: str                  # dim selection in paper bitmap form
    dims: tuple[str, ...]
    algorithm: str               # what the caller requested ("auto", ...)
    flow: str                    # the registry algorithm actually executed
    stage: str                   # Table II stage of that flow
    group_size: int
    num_instances: int
    payload_bytes: int           # per-device payload
    ici_bytes: float             # planner estimate, per device
    dcn_bytes: float
    seconds: float
    # deferred-program provenance (repro.core.program): the CommProgram this
    # dispatch executed under, and the recorded op ids a fused/coalesced op
    # was rewritten from.  Empty for eager dispatches.
    program_id: str | None = None
    fused_from: tuple[int, ...] = ()
    # estimate provenance: "analytic" (hardcoded v5e constants) or
    # "measured" (an installed repro.tuning CommProfile priced this flow).
    est_source: str = "analytic"


_TRACES: list["CommTrace"] = []


class CommTrace:
    """Context manager collecting :class:`CommEvent` s from every dispatch.

    Dispatch happens at trace time (shapes are static under jit), so one
    traced program records each textual collective call site once -- the
    trace is the *planned schedule*, not an execution count.
    """

    def __init__(self):
        self.events: list[CommEvent] = []

    def __enter__(self) -> "CommTrace":
        _TRACES.append(self)
        return self

    def __exit__(self, *exc):
        _TRACES.remove(self)
        return False

    def record(self, event: CommEvent) -> None:
        self.events.append(event)

    def total_bytes(self) -> tuple[float, float]:
        return (sum(e.ici_bytes for e in self.events),
                sum(e.dcn_bytes for e in self.events))

    def summary(self) -> dict:
        """JSON-serializable per-(primitive, flow) aggregate."""
        by: dict[str, dict] = {}
        for e in self.events:
            d = by.setdefault(f"{e.primitive}/{e.flow}", {
                "count": 0, "stage": e.stage, "payload_bytes": 0,
                "ici_bytes": 0.0, "dcn_bytes": 0.0, "est_seconds": 0.0,
                "est_source": e.est_source})
            d["count"] += 1
            d["payload_bytes"] += e.payload_bytes
            d["ici_bytes"] += e.ici_bytes
            d["dcn_bytes"] += e.dcn_bytes
            d["est_seconds"] += e.seconds
            if d["est_source"] != e.est_source:
                d["est_source"] = "mixed"
        ici, dcn = self.total_bytes()
        fused = [e for e in self.events if e.fused_from]
        sources: dict[str, int] = {}
        for e in self.events:
            sources[e.est_source] = sources.get(e.est_source, 0) + 1
        return {"events": len(self.events), "ici_bytes": ici,
                "dcn_bytes": dcn, "by_flow": by,
                "est_sources": sources,
                "fused_events": len(fused),
                "fused_from_ops": sum(len(e.fused_from) for e in fused),
                "programs": sorted({e.program_id for e in self.events
                                    if e.program_id})}


def _emit(event: CommEvent) -> None:
    for t in _TRACES:
        t.record(event)


# ========================================================== communicator
def _payload_bytes(x) -> int:
    """Per-device payload bytes of ``x`` -- static at trace time."""
    size = int(getattr(x, "size", 1))
    dtype = getattr(x, "dtype", None)
    return size * (dtype.itemsize if dtype is not None else 4)


# planner algorithm each executed flow corresponds to, for the estimates
# attached to CommEvents.
_FLOW_TO_PLANNER = {
    "naive": "naive",
    "hierarchical": "pidcomm",
    "compressed": "compressed",
    "ring_fused": "ring_fused",
    "ag_prologue": "ag_prologue",
    "rs_epilogue": "rs_epilogue",
}

# compute-fused flows (repro.kernels.collective) the auto planner may pick
# when a measured profile prices them cheaper than the unfused stages
_FUSED_FLOWS = frozenset(("ring_fused", "ag_prologue", "rs_epilogue"))


def scope_name(primitive: str, bitmap: str, flow: str,
               payload_bytes: int) -> str:
    """The named scope of one dispatch's device ops:
    ``comm.<primitive>.<bitmap>.<flow>.<payload bytes per device>``."""
    return f"comm.{primitive}.{bitmap}.{flow}.{payload_bytes}"


@contextlib.contextmanager
def _scoped(scope: str):
    """Name the device ops made inside by ``scope``: in their ``op_name``
    (``jax.named_scope``) and in a ``comm_scope`` frontend attribute.  The
    attribute is part of the program's persistent-cache key, which leaves
    names out, so an executable compiled without the scope never stands
    in for one with it."""
    with jax.named_scope(scope), set_xla_metadata(comm_scope=scope):
        yield


def scope_estimate(cube: Hypercube, scope: str) -> planner.CommEstimate:
    """The planner's estimate of the dispatch that ran under ``scope`` (a
    :func:`scope_name`), priced as the dispatch priced it."""
    _, primitive, bitmap, flow, payload = scope.split(".")
    return planner.estimate(cube, primitive, bitmap, int(payload),
                            algorithm=_FLOW_TO_PLANNER.get(flow, "direct"))


def program_mod():
    """Deferred import of :mod:`repro.core.program` (cycle: program records
    through Communicator dispatch)."""
    from repro.core import program
    return program


class Communicator:
    """The eight PID-Comm primitives bound to one (cube, dim selection).

    Built via :meth:`repro.core.hypercube.Hypercube.comm`.  PE<->PE
    primitives (all_to_all / reduce_scatter / all_reduce / all_gather) are
    per-shard functions usable only inside ``shard_map`` over ``cube.mesh``;
    rooted primitives (scatter / gather / reduce / broadcast) operate at the
    jit boundary with the host as root (paper §IV-B3).

    ``algorithm`` per call (or ``default_algorithm`` at construction) is one
    of ``"auto"`` (planner-driven), ``"pidcomm"``, a Table II stage name, or
    a first-class registered algorithm (``"hierarchical"``, ``"compressed"``,
    ``"ring"``, ``"tree"``).
    """

    def __init__(self, cube: Hypercube, dims, *,
                 default_algorithm: str = "auto"):
        self.cube = cube
        self.dims: tuple[str, ...] = cube.resolve_dims(dims)
        self.bitmap = "".join(
            "1" if d in self.dims else "0" for d in cube.dim_names)
        self.group_size: int = cube.group_size(self.dims)
        self.num_instances: int = cube.num_instances(self.dims)
        self.fast_dims, self.slow_dims = cube.split_fast_slow(self.dims)
        self.crosses_dcn: bool = bool(self.slow_dims)
        self.default_algorithm = default_algorithm

    # ------------------------------------------------------------- helpers
    @property
    def ax(self) -> tuple[str, ...]:
        """The lax axis-name tuple of this group."""
        return self.dims

    def axis_index(self):
        """Linearized index of this shard within its group (shard_map)."""
        return lax.axis_index(self.dims)

    def describe(self) -> str:
        return (f"Communicator[{self.cube.describe()} dims={self.bitmap} "
                f"g={self.group_size} inst={self.num_instances} "
                f"slow={self.slow_dims or '()'}]")

    def program(self, *, name: str = ""):
        """Open a deferred :class:`repro.core.program.CommProgram` recording
        scope over this communicator's cube (any communicator of the same
        cube may record into it -- multi-communicator mixes included)."""
        return program_mod().CommProgram(self.cube, name=name)

    # ------------------------------------------------------------ dispatch
    def _resolve_flow(self, primitive: str, algorithm: str,
                      payload_bytes: int, op: str = "add"):
        """Map an algorithm request onto a registry flow name.  Returns
        (flow_name, planner_estimate_or_None)."""
        if algorithm == "auto":
            est = planner.plan(self.cube, primitive, self.dims, payload_bytes)
            if est.algorithm == "naive":
                return "naive", est
            if (est.algorithm == "hierarchical" and primitive == "all_reduce"
                    and op == "add"):
                return "hierarchical", est
            if (est.algorithm in _FUSED_FLOWS
                    and est.algorithm in _REGISTRY[primitive]):
                # a measured profile priced a compute-fused ring flow
                # cheaper than the unfused stages; run it as-is (without a
                # consumer/tile_fn the bodies are plain ring collectives)
                return est.algorithm, est
            if est.algorithm != "direct":
                # the planner's pick is not executable here (e.g. a
                # hierarchical split for a non-additive op); drop its
                # estimate so the trace reflects the flow actually run
                est = None
            return self._escalate(primitive,
                                  resolve_stage(primitive, "pidcomm"),
                                  op), est
        if algorithm == "pidcomm" or algorithm in STAGE_ORDER:
            return self._escalate(primitive,
                                  resolve_stage(primitive, algorithm),
                                  op), None
        if algorithm in _REGISTRY[primitive]:
            return algorithm, None
        raise ValueError(
            f"unknown algorithm {algorithm!r} for {primitive!r}; expected "
            f"'auto', 'pidcomm', a stage {STAGE_ORDER}, or one of "
            f"{sorted(_REGISTRY[primitive])}")

    def _escalate(self, primitive: str, stage: str, op: str) -> str:
        """Stage-level escalations that depend on the bound group:
        * all_to_all ``im`` ladders get HLO-quadratic beyond ``_LADDER_MAX``
          (or on multi-dim groups) and fall through to the fused ``cm``;
        * a DCN-crossing additive ``im`` all_reduce takes the §IX-A
          hierarchical split."""
        if (primitive == "all_to_all" and stage == "im"
                and (self.group_size > _LADDER_MAX or len(self.dims) > 1)):
            return "cm"
        if (primitive == "all_reduce" and stage == "im" and op == "add"
                and self.fast_dims and self.slow_dims):
            return "hierarchical"
        return stage

    def _dispatch(self, primitive: str, x, *, algorithm: str | None,
                  op: str = "add", _meta: tuple | None = None, **kwargs):
        alg = self.default_algorithm if algorithm is None else algorithm
        rec = program_mod().active_program()
        if rec is not None:
            # deferred mode: append a CommOp to the recording program
            # instead of dispatching; execution re-enters here with
            # recording suspended and ``_meta`` carrying provenance.
            return rec.record_op(self, primitive, x, algorithm=alg, op=op,
                                 kwargs=kwargs)
        payload = _payload_bytes(x)
        flow, est = self._resolve_flow(primitive, alg, payload, op)
        spec = get_algorithm(primitive, flow)
        if _TRACES or _telemetry.enabled():
            if est is None:
                est = planner.estimate(
                    self.cube, primitive, self.dims, payload,
                    algorithm=_FLOW_TO_PLANNER.get(flow, "direct"))
            _telemetry.inc("comm.dispatches")
            _telemetry.inc(f"comm.est_source.{est.est_source}")
        if _TRACES:
            program_id, fused_from = _meta if _meta else (None, ())
            _emit(CommEvent(
                primitive=primitive, bitmap=self.bitmap, dims=self.dims,
                algorithm=alg, flow=flow, stage=spec.stage,
                group_size=self.group_size,
                num_instances=self.num_instances, payload_bytes=payload,
                ici_bytes=est.ici_bytes, dcn_bytes=est.dcn_bytes,
                seconds=est.seconds, program_id=program_id,
                fused_from=tuple(fused_from), est_source=est.est_source))
        if primitive in ("all_reduce", "reduce_scatter", "reduce"):
            kwargs["op"] = op
        with _scoped(scope_name(primitive, self.bitmap, flow, payload)):
            return spec.fn(self, x, **kwargs)

    # ---------------------------------------------------- PE<->PE primitives
    def all_to_all(self, x: Array, *, split_axis: int, concat_axis: int,
                   algorithm: str | None = None) -> Array:
        if self.group_size == 1:
            return x
        return self._dispatch("all_to_all", x, algorithm=algorithm,
                              split_axis=split_axis, concat_axis=concat_axis)

    def reduce_scatter(self, x: Array, *, axis: int, op: str = "add",
                       algorithm: str | None = None) -> Array:
        if self.group_size == 1:
            return x
        return self._dispatch("reduce_scatter", x, algorithm=algorithm,
                              op=op, axis=axis)

    def all_gather(self, x: Array, *, axis: int,
                   algorithm: str | None = None) -> Array:
        if self.group_size == 1:
            return x
        return self._dispatch("all_gather", x, algorithm=algorithm, axis=axis)

    def all_reduce(self, x: Array, *, op: str = "add",
                   algorithm: str | None = None) -> Array:
        if self.group_size == 1:
            # identity on values, but typed invariant over the group like
            # the multi-member flow, so replicated consumers (loss totals,
            # softmax normalizers) see the same varying axes at any size
            if compat.vma_of(x) & set(self.ax):
                return _REDUCERS[op][0](x, self.ax)
            return x
        return self._dispatch("all_reduce", x, algorithm=algorithm, op=op)

    # ------------------------------------------------- rooted (host) four
    def scatter(self, host_value, *, axis: int | None = None,
                spec: tuple | None = None,
                algorithm: str | None = None):
        """Host -> PEs: partition ``host_value`` along ``axis`` over the
        bound dims, or — when ``spec`` is given instead — place it under a
        full PartitionSpec-shaped tuple (entries ``None`` / dim name / tuple
        of dim names per array axis).  The ``spec`` form is what elastic
        checkpoint restore records: one rooted scatter per leaf carrying the
        leaf's complete target sharding."""
        if (axis is None) == (spec is None):
            raise ValueError("scatter takes exactly one of axis= or spec=")
        if spec is not None:
            return self._dispatch("scatter", host_value, algorithm=algorithm,
                                  spec=tuple(spec))
        return self._dispatch("scatter", host_value, algorithm=algorithm,
                              axis=axis)

    def broadcast(self, host_value, *, algorithm: str | None = None):
        """Host -> PEs: replicate to every node of the cube."""
        return self._dispatch("broadcast", host_value, algorithm=algorithm)

    def gather(self, x, *, algorithm: str | None = None):
        """PEs -> host: materialize the global array in host memory."""
        return self._dispatch("gather", x, algorithm=algorithm)

    def reduce(self, x, *, op: str = "add", axis: int = 0,
               algorithm: str | None = None):
        """PEs -> host: reduction over the sharded axis, result on host."""
        return self._dispatch("reduce", x, algorithm=algorithm, op=op,
                              axis=axis)


# ===================================================== algorithm bodies
# Block-layout helpers shared by the bodies.
def _split_axis_to_front(x: Array, axis: int, groups: int) -> Array:
    """(..., G*b, ...) -> (G, ..., b, ...)."""
    shape = x.shape
    if shape[axis] % groups:
        raise ValueError(f"axis {axis} of {shape} not divisible by {groups}")
    b = shape[axis] // groups
    new = shape[:axis] + (groups, b) + shape[axis + 1:]
    return jnp.moveaxis(x.reshape(new), axis, 0)


def _merge_front_blocks(x: Array, axis: int) -> Array:
    """Inverse of `_split_axis_to_front`: (G, ..., b, ...) -> (..., G*b, ...)."""
    x = jnp.moveaxis(x, 0, axis)
    shape = x.shape
    return x.reshape(shape[:axis] + (shape[axis] * shape[axis + 1],)
                     + shape[axis + 2:])


# ----------------------------------------------------------- all_to_all
@register_algorithm("all_to_all", "naive")
def _aa_naive(comm, x, *, split_axis, concat_axis):
    # replicated intermediate over the group ("host buffer"), then per-word
    # modulation -- data-dependent gather over the flattened buffer (the
    # host rearranging word by word).
    g, ax = comm.group_size, comm.ax
    blocks = _split_axis_to_front(x, split_axis, g)            # (G, ..., b, ..)
    gathered = compat.all_gather(blocks, ax, axis=0, tiled=False)  # (G, G, ..)
    me = lax.axis_index(ax)
    idx = jnp.arange(g) * g + me
    flat = gathered.reshape((g * g,) + gathered.shape[2:])
    mine = jnp.take(flat, idx, axis=0)
    return _merge_front_blocks(mine, concat_axis)


@register_algorithm("all_to_all", "pr")
def _aa_pr(comm, x, *, split_axis, concat_axis):
    # PE-assisted reordering: sources pre-arranged their blocks so the
    # mediator extracts one column with a single dynamic slice.
    g, ax = comm.group_size, comm.ax
    blocks = _split_axis_to_front(x, split_axis, g)
    gathered = compat.all_gather(blocks, ax, axis=0, tiled=False)
    me = lax.axis_index(ax)
    mine = lax.dynamic_index_in_dim(
        jnp.swapaxes(gathered, 0, 1), me, axis=0, keepdims=False)
    return _merge_front_blocks(mine, concat_axis)


@register_algorithm("all_to_all", "im")
def _aa_ladder(comm, x, *, split_axis, concat_axis):
    """(G-1)-step ppermute ladder: one destination block per step, no
    replicated intermediate (in-register modulation analogue)."""
    g, ax = comm.group_size, comm.ax
    blocks = _split_axis_to_front(x, split_axis, g)
    me = lax.axis_index(ax)
    received = [lax.dynamic_index_in_dim(blocks, me, axis=0)]  # own block
    for step in range(1, g):
        # i sends its block destined for (i - step); it lands on (i - step)
        perm = [(i, (i - step) % g) for i in range(g)]
        send = lax.dynamic_index_in_dim(blocks, (me - step) % g, axis=0)
        received.append(lax.ppermute(send, ax, perm))
    stacked = jnp.concatenate(received, axis=0)  # slot s <- source (me+s)%g
    idx = (jnp.arange(g) - me) % g               # out[j] = slot (j-me)%g
    mine = jnp.take(stacked, idx, axis=0)
    return _merge_front_blocks(mine, concat_axis)


@register_algorithm("all_to_all", "cm")
def _aa_fused(comm, x, *, split_axis, concat_axis):
    # single fused native collective: the layout change happens inside the
    # transfer (cross-domain modulation).
    return lax.all_to_all(x, comm.ax, split_axis, concat_axis, tiled=True)


# ------------------------------------------------------- reduce_scatter
@register_algorithm("reduce_scatter", "naive")
def _rs_naive(comm, x, *, axis, op):
    g, ax = comm.group_size, comm.ax
    blocks = _split_axis_to_front(x, axis, g)                  # (G, ..., b, ..)
    gathered = compat.all_gather(blocks, ax, axis=0, tiled=False)
    me = lax.axis_index(ax)
    col = lax.dynamic_index_in_dim(gathered, me, axis=1, keepdims=False)
    # naive: horizontal, source-by-source sequential reduction.
    comb = _REDUCERS[op][2]
    acc = col[0]
    for s in range(1, g):
        acc = comb(acc, col[s])
    return acc


@register_algorithm("reduce_scatter", "pr")
def _rs_pr(comm, x, *, axis, op):
    g, ax = comm.group_size, comm.ax
    blocks = _split_axis_to_front(x, axis, g)
    gathered = compat.all_gather(blocks, ax, axis=0, tiled=False)
    me = lax.axis_index(ax)
    col = lax.dynamic_index_in_dim(gathered, me, axis=1, keepdims=False)
    # vertical (vectorized) reduction over the stacked source axis -- the
    # paper's one-SIMD-op-per-register argument.
    return _REDUCERS[op][1](col, axis=0)


@register_algorithm("reduce_scatter", "im")
def _rs_stream(comm, x, *, axis, op):
    g, ax = comm.group_size, comm.ax
    if op == "add":
        return compat.psum_scatter(x, ax, scatter_dimension=axis)
    red = _REDUCERS[op][0](x, ax)
    blocks = _split_axis_to_front(red, axis, g)
    me = lax.axis_index(ax)
    return lax.dynamic_index_in_dim(blocks, me, axis=0, keepdims=False)


# ----------------------------------------------------------- all_gather
@register_algorithm("all_gather", "naive")
def _ag_naive(comm, x, *, axis):
    # naive: root collects then broadcasts full copies -- emulated by a
    # masked psum carrying G full-size buffers over the bus.
    g, ax = comm.group_size, comm.ax
    me = lax.axis_index(ax)
    stacked = jnp.zeros((g,) + x.shape, x.dtype)
    stacked = lax.dynamic_update_index_in_dim(stacked, x, me, axis=0)
    full = lax.psum(stacked, ax)
    return _merge_front_blocks(full, axis)


@register_algorithm("all_gather", "pr")
def _ag_pr(comm, x, *, axis):
    gathered = compat.all_gather(x, comm.ax, axis=0, tiled=False)
    return _merge_front_blocks(gathered, axis)


@register_algorithm("all_gather", "im")
def _ag_stream(comm, x, *, axis):
    # direct tiled gather; with CM the consumer additionally reads the
    # gathered layout in place (no post-reorder op survives fusion), so the
    # same body serves both stages.
    return compat.all_gather(x, comm.ax, axis=axis)


register_algorithm("all_gather", "cm")(_ag_stream)


# ----------------------------------------------------------- all_reduce
@register_algorithm("all_reduce", "naive")
def _ar_naive(comm, x, *, op):
    g, ax = comm.group_size, comm.ax
    gathered = compat.all_gather(x, ax, axis=0, tiled=False)
    comb = _REDUCERS[op][2]
    acc = gathered[0]
    for s in range(1, g):
        acc = comb(acc, gathered[s])
    return acc


@register_algorithm("all_reduce", "pr")
def _ar_pr(comm, x, *, op):
    gathered = compat.all_gather(x, comm.ax, axis=0, tiled=False)
    return _REDUCERS[op][1](gathered, axis=0)


@register_algorithm("all_reduce", "im")
def _ar_direct(comm, x, *, op):
    # the runtime's fused native collective (data streams through the
    # reduction); DCN-crossing additive groups are escalated to
    # "hierarchical" by the dispatcher before reaching this body.
    return _REDUCERS[op][0](x, comm.ax)


@register_algorithm("all_reduce", "hierarchical", stage="im", table_ii=False)
def _ar_hierarchical(comm, x, *, op):
    """§IX-A: ICI reduce-scatter, DCN all-reduce of the 1/|ICI| shard, ICI
    all-gather.  DCN bytes drop |ICI|x.  Falls back to the direct flow when
    the group does not span both domains or the op is not additive."""
    fast, slow = comm.fast_dims, comm.slow_dims
    if not (fast and slow) or op != "add":
        return _REDUCERS[op][0](x, comm.ax)
    gf = comm.cube.group_size(fast)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % gf
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = compat.psum_scatter(flat, fast, scatter_dimension=0)
    shard = lax.psum(shard, slow)
    full = compat.all_gather(shard, fast, axis=0)
    if pad:
        full = full[:-pad]
    return full.reshape(x.shape)


@register_algorithm("all_reduce", "compressed", stage="cm", table_ii=False)
def _ar_compressed(comm, x, *, op):
    """§V-C: hierarchical all-reduce whose DCN hop carries blockwise-absmax
    int8 payloads (8-bit cross-domain modulation), under a custom_vjp so the
    flow is usable inside differentiated code (straight-through quantizer)."""
    from repro.core import compress
    if op != "add":
        raise ValueError("compressed all_reduce supports op='add' only")
    if not comm.slow_dims:
        raise ValueError(
            "compressed all_reduce needs a DCN-crossing group; "
            f"{comm.dims} is entirely intra-pod")
    return compress.compressed_all_reduce(x, comm.cube, comm.dims)


@register_algorithm("all_reduce", "ring", stage="im", table_ii=False)
def _ar_ring(comm, x, *, op):
    """Bandwidth-optimal ring (Fig. 23a comparator): (G-1) reduce-scatter
    steps + (G-1) all-gather steps of 1/G-size chunks, via ppermute."""
    if op != "add":
        raise ValueError("ring all_reduce supports op='add' only")
    if len(comm.dims) != 1:
        raise ValueError("ring all_reduce runs on a single dim")
    g, ax = comm.group_size, comm.ax
    me = lax.axis_index(ax)
    orig_len = x.shape[0]
    pad = (-orig_len) % g
    xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if pad else x
    chunks = jnp.stack(jnp.split(xp, g, axis=0), axis=0)   # (G, n/G, ...)
    fwd = [(i, (i + 1) % g) for i in range(g)]
    # reduce-scatter phase: after g-1 hops, i holds reduced chunk (i+1)%g.
    cur = lax.dynamic_index_in_dim(chunks, me, axis=0, keepdims=False)
    for step in range(g - 1):
        got = lax.ppermute(cur, ax, fwd)
        idx = (me - 1 - step) % g
        cur = got + lax.dynamic_index_in_dim(chunks, idx, axis=0,
                                             keepdims=False)
    red_idx = (me + 1) % g
    # all-gather phase: h_s = (me + 1 - s) % g after s hops.
    out = jnp.zeros_like(chunks)
    out = lax.dynamic_update_index_in_dim(out, cur, red_idx, axis=0)
    for s in range(1, g):
        cur = lax.ppermute(cur, ax, fwd)
        out = lax.dynamic_update_index_in_dim(out, cur, (me + 1 - s) % g,
                                              axis=0)
    full = out.reshape((-1,) + x.shape[1:])
    return full[:orig_len] if pad else full


@register_algorithm("all_reduce", "tree", stage="im", table_ii=False)
def _ar_tree(comm, x, *, op):
    """Recursive-doubling (hypercube-exchange) all-reduce: log2(G) steps of
    full-payload XOR-partner exchanges -- latency-optimal, bandwidth-
    suboptimal; stands in for the two-tree comparison of Fig 23(a)."""
    if op != "add":
        raise ValueError("tree all_reduce supports op='add' only")
    g, ax = comm.group_size, comm.ax
    if g & (g - 1):
        raise ValueError("tree_all_reduce needs a power-of-two group")
    acc = x
    level = 1
    while level < g:
        perm = [(i, i ^ level) for i in range(g)]
        got = lax.ppermute(acc, ax, perm)
        acc = acc + got
        level <<= 1
    return acc


# --------------------------------------------------- rooted (host) four
# The host is always the root (paper §IV-B3).  These run at the jit boundary
# on global arrays; one buffer per cube slice, like the paper's per-group
# host buffers.  The device path is stage-invariant: at the jit boundary the
# runtime's native host<->device transfer *is* the in-register path, so
# naive/pr only differ in the emulated host flow the paper ablates, not in
# bytes placed on devices -- one body serves every registered stage.
def _rooted_scatter(comm, host_value, *, axis=None, spec=None):
    if spec is None:
        ax = comm.dims
        spec = [None] * host_value.ndim
        spec[axis] = ax if len(ax) > 1 else ax[0]
    return jax.device_put(host_value, comm.cube.sharding(P(*spec)))


def _rooted_broadcast(comm, host_value):
    return jax.device_put(host_value, comm.cube.sharding(P()))


def _rooted_gather(comm, x):
    return jax.device_get(x)


def _rooted_reduce(comm, x, *, op, axis):
    reducer = {"add": jnp.sum, "max": jnp.max, "min": jnp.min}[op]
    return jax.device_get(reducer(x, axis=axis))


for _stage_name in ("naive", "im"):
    register_algorithm("scatter", _stage_name)(_rooted_scatter)
    register_algorithm("gather", _stage_name)(_rooted_gather)
for _stage_name in ("naive", "pr", "im"):
    register_algorithm("reduce", _stage_name)(_rooted_reduce)
register_algorithm("broadcast", "naive")(_rooted_broadcast)
del _stage_name


__all__ = [
    "AlgorithmSpec", "CommEvent", "CommTrace", "Communicator",
    "PRIMITIVES", "STAGE_ORDER", "applicability", "get_algorithm",
    "register_algorithm", "registered_algorithms", "resolve_stage",
]

# registration side effect: the compute-fused ring flows
# (ring_fused / ag_prologue / rs_epilogue) live with their kernels in
# repro.kernels.collective but must exist in the registry whenever comm is
# importable -- auto dispatch, microbench sweeps, and conformance
# accounting all resolve them by name.  Importing at the bottom keeps the
# cycle safe: every name the kernel module pulls from here is defined by
# now.
import repro.kernels.collective  # noqa: E402,F401  (registers fused flows)
