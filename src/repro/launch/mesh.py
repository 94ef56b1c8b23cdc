"""Production meshes (DESIGN.md §5).

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device state.  The single-pod mesh is a
(data=16, model=16) grid of one v5e pod (256 chips); multi-pod adds a
leading "pod" axis (2 pods = 512 chips) used purely for data parallelism —
only the gradient all-reduce crosses the pod boundary.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def data_axes(multi_pod: bool) -> tuple[str, ...]:
    """Axes that carry batch/data parallelism."""
    return ("pod", "data") if multi_pod else ("data",)


def make_host_mesh(axis_name: str = "data"):
    """All local devices on one axis (tests / examples on CPU)."""
    import numpy as np

    devs = np.array(jax.devices())
    return jax.sharding.Mesh(devs, (axis_name,))
