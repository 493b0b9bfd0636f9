"""One import site for the jax APIs the repo builds on (jax 0.9).

Everything in the repo that needs ``shard_map``, mesh construction, or the
varying-axes (vma) machinery goes through this module, so a future move of
any of them is a one-file change.

Exports:
  shard_map(f, *, mesh, in_specs, out_specs, check_vma=True)
      ``jax.shard_map``.
  make_mesh(shape, axes, *, devices=None)
      ``jax.make_mesh`` with explicit Auto axis types.
  vma_of(x) / pvary(x, axes)
      Read / extend an array's varying-axes set.
  replicated_psum(x, axes)
      ``lax.psum`` for terminal reductions (loss totals, normalizers).
  psum_scatter / all_gather
      Keyword-stable wrappers over the ``jax.lax`` collectives.
  axis_index / dynamic_update_slice / dynamic_slice / fori_loop
      Re-exports of the non-collective lax helpers the app layer uses, so
      application code never imports ``jax.lax`` directly (grep-enforced).

Importing it also gives the program's spans
(``repro.telemetry.spans.maybe_span``) the JAX profiler as a sink: while
a profile records, each span is a ``jax.profiler.TraceAnnotation`` on the
device trace's clock.
"""
from __future__ import annotations

import jax
from jax import lax
# With check_vma (the default) the varying-axes machinery both checks
# out_specs replication and lets autodiff insert the gradient psums for
# replicated leaves.
from jax import shard_map  # noqa: F401  (re-export)
from jax.profiler import TraceAnnotation
from jax.sharding import AxisType

from repro.telemetry import spans as _spans

_spans.install_profiler_sink(TraceAnnotation.is_enabled, TraceAnnotation)


def make_mesh(shape, axes, *, devices=None):
    """Device mesh of ``shape`` over ``axes`` (on ``devices``, default
    every visible device), Auto-typed."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


# ---------------------------------------------------------- varying axes
def vma_of(x) -> frozenset:
    """The varying-axes set of ``x`` (empty outside shard_map)."""
    return jax.typeof(x).vma


def pvary(x, axes):
    """Mark ``x`` varying over ``axes``."""
    axes = tuple(axes)
    if not axes:
        return x
    return lax.pcast(x, axes, to="varying")


# -------------------------------------------------------- lax collectives
def replicated_psum(x, axes):
    """psum for *terminal* reductions: ones whose output is consumed only by
    group-replicated compute (loss totals, logsumexp/normalizer denominators).
    The varying-axes autodiff transposes it to the identity-shaped pvary,
    which is exact. Models call this instead of ``lax.psum`` (which the CI
    import grep forbids there) to mark the reduction as terminal."""
    return lax.psum(x, axes)


def psum_scatter(x, axis_name, *, scatter_dimension: int = 0,
                 tiled: bool = True):
    return lax.psum_scatter(x, axis_name,
                            scatter_dimension=scatter_dimension, tiled=tiled)


def all_gather(x, axis_name, *, axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


# ----------------------------------------------- lax index/update helpers
# Stable re-exports of the non-collective ``jax.lax`` helpers application
# code needs (shard index, windowed updates, loops), so the app layer's
# "import through repro.compat, never jax directly" rule is grep-enforceable
# (CI greps src/repro/apps for raw ``jax.lax`` / ``from jax import lax``).
# Collectives are NOT re-exported here: those must go through
# ``cube.comm(...)`` / ``topo.comm(...)``.
axis_index = lax.axis_index
dynamic_update_slice = lax.dynamic_update_slice
dynamic_slice = lax.dynamic_slice
fori_loop = lax.fori_loop
