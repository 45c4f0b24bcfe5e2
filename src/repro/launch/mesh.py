"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the 512 placeholder host
devices exist only when ``dryrun.py`` set ``XLA_FLAGS`` before any jax
import.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis auto-partitioned."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod (TPU v5e pod slice); 2 pods when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh():
    """1×1 mesh on the real local device — used by tests to exercise the
    sharding-rule code paths without placeholder devices."""
    return make_mesh((1, 1), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Mesh axes used for batch/data parallelism (pod folds into data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
