"""Production mesh construction. A FUNCTION (not module-level state) so that
importing this module never touches jax device state.

Every mesh axis is ``Auto``: the model code places arrays with
``with_sharding_constraint`` and leaves the rest to GSPMD, which
``jax.make_mesh``'s default ``Explicit`` axes refuse."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

from repro.parallel.axisinfo import AxisInfo


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (one v5e pod, 256 chips) or 2×16×16 (two pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_axis_info(mesh) -> AxisInfo:
    names = mesh.axis_names
    if "pod" in names:
        return AxisInfo(mesh, batch_axes=("pod", "data"), model_axis="model")
    return AxisInfo(mesh, batch_axes=("data",), model_axis="model")


def make_mesh_for_devices(n_devices: Optional[int] = None, model_parallel: int = 1):
    """Elastic mesh for whatever devices exist (training launcher / tests)."""
    n = n_devices or len(jax.devices())
    if n % model_parallel:
        raise ValueError(f"{n} devices not divisible by model_parallel={model_parallel}")
    return _auto_mesh((n // model_parallel, model_parallel), ("data", "model"))
