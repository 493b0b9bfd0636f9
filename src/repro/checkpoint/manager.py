"""Topology-bound checkpoint manager: async save, elastic restore.

The redesigned surface binds placement once at construction::

    mgr = CheckpointManager(root, topo=topo, specs=TrainState(params=pspecs,
                                                              opt=ospecs))
    mgr.save(step, TrainState(params=params, opt=opt_state))
    state = mgr.restore(step)                       # onto mgr's topology
    params = mgr.restore_params(step, serve_topo=stopo, specs=sspecs)

and the state tree is a single :class:`TrainState` instead of parallel
``params``/``opt_state`` arguments.  The pre-redesign positional
signatures — ``save(step, params, opt_state)``, ``restore(step,
params_like, opt_like, topo=..., param_specs=..., opt_specs=...)`` and
``restore_params(step, params_like, topo=..., param_specs=...)`` — keep
working as deprecated shims (``DeprecationWarning``, same pattern as
``core.collectives.Collectives``).

Data movement is collective programs (:mod:`repro.checkpoint.reshard`):
save records one rooted-gather CommProgram per section, restore one
rooted-scatter program per section planned under the installed
CommProfile, with ``program_id`` provenance on every CommEvent.

**Async save** splits along the donation boundary: the gather programs
execute at ``save()`` dispatch — the train step donates its params/opt
buffers, so the device→host copy must complete before the next step runs —
while serialization and disk writes run on a bounded background executor
(``checkpoint.write.{section}`` spans, ``ckpt.*`` metrics).  Worker failures are
captured and re-raised at ``wait()`` or the next ``save()``, never
swallowed in the thread.  The manifest is written and the ``.tmp``
directory renamed only after every section landed, so a killed-mid-write
checkpoint is invisible to ``all_steps()``/``restore()`` and simply
overwritten by the retry.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import time
import warnings
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import layout, reshard
from repro.telemetry import metrics as _telemetry
from repro.telemetry import spans as _spans

Array = jax.Array


@dataclasses.dataclass
class TrainState:
    """The checkpointed unit: model params plus optimizer state, one tree."""
    params: Any
    opt: Any = None


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.params, s.opt), None),
    lambda _, children: TrainState(*children),
)


def _deprecated(old: str, new: str) -> None:
    warnings.warn(
        f"{old} is deprecated; use {new} (topology-bound CheckpointManager "
        "surface)", DeprecationWarning, stacklevel=3)


class CheckpointManager:
    """Sharded, atomic, async-capable checkpointing with elastic restore.

    Parameters
    ----------
    root:
        Checkpoint directory (one ``step_<n>`` subdirectory per step).
    topo:
        The topology (or bare Hypercube) whose cube save gathers from and
        restore scatters onto.  ``None`` falls back to a plain host loop
        (``device_get`` / ``jnp.asarray``) with no recorded programs.
    specs:
        TrainState-shaped tree of PartitionSpecs for restore placement
        (also accepted as ``{"params": ..., "opt": ...}``).
    keep_last:
        GC horizon: completed checkpoints beyond the newest ``keep_last``
        are deleted after each successful save.  The step currently being
        written is never collected.
    max_workers:
        Bound on the background write executor.
    """

    def __init__(self, root: str, *, topo=None, specs=None,
                 async_save: bool = True, keep_last: int = 3,
                 max_workers: int = 2):
        self.root = root
        self.topo = topo
        self.specs = specs
        self.async_save = async_save
        self.keep_last = keep_last
        self.max_workers = max(1, int(max_workers))
        self._executor: ThreadPoolExecutor | None = None
        self._pending: list[Future] = []
        self._writing: set[int] = set()
        os.makedirs(root, exist_ok=True)

    # ------------------------------------------------------------------ io
    def _dir(self, step: int) -> str:
        return layout.step_dir(self.root, step)

    def _ensure_executor(self) -> ThreadPoolExecutor:
        if self._executor is None:
            self._executor = ThreadPoolExecutor(
                max_workers=self.max_workers,
                thread_name_prefix="ckpt-write")
        return self._executor

    def _specs_sections(self) -> dict | None:
        return _sections_of(self.specs) if self.specs is not None else None

    # ---------------------------------------------------------------- save
    def save(self, step: int, state, opt_state=None, *,
             extra: dict | None = None) -> None:
        """Write ``state`` (a :class:`TrainState`) as checkpoint ``step``.

        Gathers to host via one rooted-gather program per section at
        dispatch, then (``async_save``) hands serialization and the atomic
        rename to the background executor.  The deprecated form
        ``save(step, params, opt_state)`` still works.
        """
        if opt_state is not None or not isinstance(state, TrainState):
            _deprecated("save(step, params, opt_state)",
                        "save(step, TrainState(params=..., opt=...))")
            state = TrainState(params=state, opt=opt_state)
        self.wait()  # one save in flight; re-raises captured write errors
        t0 = time.monotonic()
        _telemetry.inc("ckpt.saves")

        tree = {"opt": state.opt, "params": state.params}
        leaves, _ = jax.tree.flatten(tree)
        n_opt = len(jax.tree.leaves(state.opt))
        records = layout.leaf_records(tree)
        manifest = layout.build_manifest(
            step, records, n_opt=n_opt, cube_dims=self._cube_dims(),
            extra=extra)

        # device -> host: one recorded rooted-gather program per section.
        # The program's structural fingerprint is step-invariant, so this
        # lowers once and then hits the cube's lower cache every save.
        # Runs at dispatch because the train step donates these buffers.
        sections = {"opt": (0, n_opt), "params": (n_opt, len(leaves))}
        host: list[np.ndarray] = [None] * len(leaves)  # type: ignore
        for name, (lo, hi) in sections.items():
            if hi == lo:
                continue
            with _spans.maybe_span(f"checkpoint.gather.{name}", cat="wall",
                                   step=step, leaves=hi - lo):
                if self.topo is not None:
                    host[lo:hi] = reshard.gather_to_host(
                        self.topo, leaves[lo:hi],
                        name=f"ckpt-gather-{name}")
                else:
                    host[lo:hi] = [np.asarray(jax.device_get(l))
                                   for l in leaves[lo:hi]]

        tmp = self._dir(step) + ".tmp"
        if os.path.exists(tmp):  # debris from a killed writer: retry wins
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        self._writing.add(step)

        def write_section(name: str, lo: int, hi: int) -> int:
            with _spans.maybe_span(f"checkpoint.write.{name}", cat="wall",
                                   step=step, leaves=hi - lo):
                nbytes = 0
                for i in range(lo, hi):
                    np.save(os.path.join(tmp, f"arr_{i}.npy"), host[i])
                    nbytes += host[i].nbytes
            return nbytes

        def finalize(section_bytes: list[int]) -> None:
            try:
                layout.write_manifest(tmp, manifest)
                layout.atomic_finalize(tmp, self._dir(step))
                total = int(sum(section_bytes))
                _telemetry.set_gauge("ckpt.saved_bytes", total)
                _telemetry.observe("ckpt.save_seconds",
                                   time.monotonic() - t0)
                _spans.maybe_instant("checkpoint.durable", step=step,
                                     bytes=total)
            finally:
                self._writing.discard(step)
            self._gc(protect={step})

        spans = sections.items()
        if self.async_save:
            ex = self._ensure_executor()
            futs = [ex.submit(write_section, name, lo, hi)
                    for name, (lo, hi) in spans if hi > lo]

            def run_finalize(section_futs=tuple(futs)):
                # FIFO executor: the sections queued above finish (or fail)
                # before this task runs its .result() calls, so this never
                # blocks a worker on a task behind it in the queue
                finalize([f.result() for f in section_futs])

            self._pending = futs + [ex.submit(run_finalize)]
        else:
            try:
                finalize([write_section(name, lo, hi)
                          for name, (lo, hi) in spans if hi > lo])
            finally:
                self._writing.discard(step)

    def wait(self) -> None:
        """Block until the in-flight save is durable; re-raise the first
        captured write error (each error is surfaced exactly once)."""
        pending, self._pending = self._pending, []
        errors: list[BaseException] = []
        for f in pending:
            try:
                f.result()
            except BaseException as e:  # noqa: BLE001 - re-raised below
                if all(e is not seen for seen in errors):
                    errors.append(e)
        if errors:
            _telemetry.inc("ckpt.write_errors", len(errors))
            raise errors[0]

    def _gc(self, *, protect: set[int] = frozenset()) -> None:
        steps = self.all_steps()
        keep = set(steps[-self.keep_last:]) if self.keep_last > 0 \
            else set(steps)
        for s in steps:
            if s in keep or s in protect or s in self._writing:
                continue
            shutil.rmtree(self._dir(s), ignore_errors=True)

    def all_steps(self) -> list[int]:
        return layout.list_steps(self.root)

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def _cube_dims(self) -> dict | None:
        cube = getattr(self.topo, "cube", self.topo)
        if cube is None or not hasattr(cube, "dim_names"):
            return None
        return dict(zip(cube.dim_names, cube.dim_sizes))

    # ------------------------------------------------------------- restore
    def restore(self, step: int, params_like=None, opt_like=None, *,
                topo=None, param_specs=None, opt_specs=None):
        """Restore checkpoint ``step``.

        New surface: ``restore(step)`` returns a :class:`TrainState` placed
        on the manager's bound topology under its bound specs (structure
        from the specs tree, falling back to the manifest's leaf records).

        Deprecated shim: ``restore(step, params_like, opt_like, ...)``
        returns the old ``(params, opt)`` tuple.
        """
        if params_like is not None:
            _deprecated("restore(step, params_like, opt_like)",
                        "restore(step)")
            like = {"opt": opt_like, "params": params_like}
            specs = None
            if topo is not None and param_specs is not None:
                specs = {"opt": opt_specs, "params": param_specs}
            state = self._restore_state(step, like=like, specs=specs,
                                        topo=topo)
            return state.params, state.opt
        return self._restore_state(step, like=None,
                                   specs=self._specs_sections(),
                                   topo=self.topo)

    def restore_params(self, step: int, params_like=None, *,
                       serve_topo=None, specs=None, topo=None,
                       param_specs=None):
        """Restore **params only** — the restore-for-serving path.

        New surface: ``restore_params(step, serve_topo=stopo, specs=sspecs)``
        places the params section onto the serve topology (defaults to the
        manager's bound topology/specs when omitted).  Elastic: the serve
        cube may have different dims than the cube that saved.

        Deprecated shim: ``restore_params(step, params_like, topo=...,
        param_specs=...)``.
        """
        if params_like is not None:
            _deprecated("restore_params(step, params_like)",
                        "restore_params(step, serve_topo=..., specs=...)")
            serve_topo, specs = topo, param_specs
            like = params_like
        else:
            like = None
            if serve_topo is None:
                serve_topo = self.topo
            if specs is None:
                bound = self._specs_sections()
                specs = bound["params"] if bound else None
        return self._restore_section(step, "params", like=like,
                                     specs=specs, topo=serve_topo)

    # ------------------------------------------------------ restore internals
    def _load_manifest(self, step: int) -> dict:
        d = self._dir(step)
        if not os.path.isdir(d):
            raise FileNotFoundError(
                f"no checkpoint for step {step} under {self.root} "
                f"(have steps {self.all_steps()})")
        return layout.read_manifest(d)

    def _restore_state(self, step: int, *, like, specs, topo) -> TrainState:
        self.wait()
        t0 = time.monotonic()
        manifest = self._load_manifest(step)
        n_leaves = int(manifest["n_leaves"])
        n_opt = int(manifest["sections"]["opt"])
        records = manifest.get("leaves")

        if like is not None:
            flat_like, treedef = jax.tree.flatten(like)
            if records is not None:
                layout.validate_records(records, layout.leaf_records(like),
                                        section="state", step=step)
            elif len(flat_like) != n_leaves:
                raise ValueError(
                    f"checkpoint step {step} holds {n_leaves} state leaves "
                    f"but the target structure has {len(flat_like)} -- "
                    "architecture mismatch between save and restore")
            n = len(flat_like)
        elif specs is not None:
            treedef, n = _spec_treedef(specs)
            if n != n_leaves:
                raise ValueError(
                    f"checkpoint step {step} holds {n_leaves} state leaves "
                    f"but the bound specs tree has {n} -- architecture "
                    "mismatch between save and restore")
        elif records is not None:
            tree = layout.tree_from_records(
                records, list(range(n_leaves)))
            flat, treedef = jax.tree.flatten(tree)
            if flat != list(range(n_leaves)):
                raise ValueError(
                    "manifest leaf records do not reconstruct a stable "
                    "flat order; pass specs= to CheckpointManager")
            n = n_leaves
        else:
            raise ValueError(
                "checkpoint manifest predates leaf records; pass specs= to "
                "CheckpointManager or use the deprecated "
                "restore(step, params_like, opt_like) form")

        d = self._dir(step)
        host = [np.load(os.path.join(d, f"arr_{i}.npy"))
                for i in range(n_leaves)]
        placed: list[Any] = [None] * n_leaves
        for name, lo, hi in (("opt", 0, n_opt),
                             ("params", n_opt, n_leaves)):
            if hi == lo:
                continue
            sec_specs = _section_spec_leaves(specs, name, hi - lo)
            placed[lo:hi] = self._place(host[lo:hi], sec_specs, topo,
                                        section=name)
        tree = jax.tree.unflatten(treedef, placed)
        sections = _sections_of(tree)
        state = TrainState(params=sections["params"], opt=sections["opt"])
        _telemetry.inc("ckpt.restores")
        _telemetry.set_gauge("ckpt.restored_bytes",
                             int(sum(a.nbytes for a in host)))
        _telemetry.observe("ckpt.restore_seconds", time.monotonic() - t0)
        return state

    def _restore_section(self, step: int, section: str, *, like, specs,
                         topo):
        self.wait()
        t0 = time.monotonic()
        manifest = self._load_manifest(step)
        n_leaves = int(manifest["n_leaves"])
        sections = manifest.get("sections")
        records = manifest.get("leaves")

        if like is not None:
            flat_like, treedef = jax.tree.flatten(like)
            n = len(flat_like)
        elif specs is not None:
            treedef, n = _spec_treedef(specs)
        elif records is not None:
            n = sections[section]
            offset0 = n_leaves - sections["params"] \
                if section == "params" else 0
            # record paths are rooted at the full state tree; drop the
            # leading section key so the rebuilt tree is the bare section
            sec_records = [
                {**records[offset0 + i],
                 "path": list(records[offset0 + i]["path"])[1:]}
                for i in range(n)]
            tree = layout.tree_from_records(sec_records, list(range(n)))
            flat, treedef = jax.tree.flatten(tree)
            if flat != list(range(n)):
                raise ValueError(
                    "manifest leaf records do not reconstruct a stable "
                    "flat order; pass specs=")
        else:
            raise ValueError(
                "checkpoint manifest predates leaf records; pass specs= or "
                "the deprecated params_like skeleton")

        n_section = sections[section] if sections else n
        if n_section != n:
            raise ValueError(
                f"checkpoint step {step} holds {n_section} {section} leaves "
                f"but the target structure has {n} -- architecture "
                "mismatch between save and restore")
        # params leaves are the trailing section of the flat order
        # ("params" sorts after "opt" in the save-time flatten)
        offset = (n_leaves - n_section) if section == "params" else 0
        if records is not None and like is not None:
            # saved record paths are rooted at the full state tree; the
            # ``like`` skeleton is the bare section
            sec = [{**r, "path": list(r["path"])[1:]}
                   for r in records[offset:offset + n_section]]
            layout.validate_records(sec, layout.leaf_records(like),
                                    section=section, step=step)

        d = self._dir(step)
        host = [np.load(os.path.join(d, f"arr_{offset + i}.npy"))
                for i in range(n_section)]
        spec_leaves = reshard.flatten_specs(specs, host) \
            if specs is not None else None
        out = self._place(host, spec_leaves, topo, section=section)
        _telemetry.inc("ckpt.restores")
        _telemetry.set_gauge("ckpt.restored_bytes",
                             int(sum(a.nbytes for a in host)))
        _telemetry.observe("ckpt.restore_seconds", time.monotonic() - t0)
        return jax.tree.unflatten(treedef, out)

    def _place(self, host: list[np.ndarray], spec_leaves, topo, *,
               section: str) -> list:
        """Host arrays -> live arrays: one rooted-scatter program per
        section when placement is known, plain ``jnp.asarray`` otherwise."""
        if topo is not None and spec_leaves is not None:
            with _spans.maybe_span(f"checkpoint.restore.{section}",
                                   cat="wall", leaves=len(host)):
                return reshard.scatter_to_cube(
                    topo, host, spec_leaves,
                    name=f"ckpt-restore-{section}")
        return [jnp.asarray(a) for a in host]


def _sections_of(tree) -> dict:
    """Normalize a TrainState / {"params", "opt"} dict into sections."""
    if isinstance(tree, TrainState):
        return {"opt": tree.opt, "params": tree.params}
    if isinstance(tree, dict) and "params" in tree \
            and set(tree) <= {"opt", "params"}:
        return {"opt": tree.get("opt"), "params": tree["params"]}
    raise TypeError(
        "expected a TrainState or a {'params': ..., 'opt': ...} dict, got "
        f"{type(tree).__name__}")


def _is_spec_leaf(x) -> bool:
    # PartitionSpec is a tuple subclass; a None node stays a jax empty
    # subtree so a spec tree for ``opt=None`` flattens like the state did
    # at save time (use P() for an explicitly replicated leaf)
    return isinstance(x, tuple)


def _spec_treedef(specs):
    """(treedef, n_leaves) of a spec tree, treating PartitionSpecs (tuple
    subclass) and Nones as leaves."""
    flat, treedef = jax.tree.flatten(specs, is_leaf=_is_spec_leaf)
    return treedef, len(flat)


def _section_spec_leaves(specs, section: str, n: int):
    """Flat spec leaves of one section of a sections-dict spec tree, or
    None when no specs are bound."""
    if specs is None:
        return None
    sec = specs.get(section) if isinstance(specs, dict) else None
    if sec is None:
        return None
    flat, _ = jax.tree.flatten(sec, is_leaf=_is_spec_leaf)
    if len(flat) != n:
        raise ValueError(
            f"{section} spec tree has {len(flat)} leaves, checkpoint "
            f"section has {n}")
    return [() if s is None else tuple(s) for s in flat]


__all__ = ["CheckpointManager", "TrainState"]
