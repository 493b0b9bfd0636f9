"""Physical mesh construction for the production deployment.

The production target is TPU v5e: one pod = a 16x16 ICI-connected slice
(256 chips), two pods connected over DCN for the multi-pod configuration.
``make_production_mesh`` is a function (never a module-level constant) so that
importing this module never touches jax device state.

Mesh construction (explicit ``AxisType``) lives in :mod:`repro.compat`;
this module re-exports it so all launch-path callers keep their import
site.
"""
from __future__ import annotations

from repro.compat import make_mesh

__all__ = ["make_mesh", "make_production_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """The deployment mesh: 16x16 chips per pod; 2 pods over DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
