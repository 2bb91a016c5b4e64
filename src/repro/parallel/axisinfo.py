"""Mesh axis bookkeeping shared by model code and the launcher."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class AxisInfo:
    """Which mesh axes play which role.

    ``batch_axes`` shard the batch (pure DP): ``('data',)`` single-pod or
    ``('pod', 'data')`` multi-pod. ``model_axis`` is the TP/EP axis. The KV
    page pool is sharded over *all* axes (``page_axes``) — the TPU analogue of
    the paper's page striping across every data provider.
    """

    mesh: Mesh
    batch_axes: Tuple[str, ...]
    model_axis: str = "model"

    @property
    def page_axes(self) -> Tuple[str, ...]:
        return self.batch_axes + (self.model_axis,)

    @property
    def n_batch_shards(self) -> int:
        return int(jax.numpy.prod(jax.numpy.array([self.mesh.shape[a] for a in self.batch_axes])))

    @property
    def n_page_shards(self) -> int:
        n = 1
        for a in self.page_axes:
            n *= self.mesh.shape[a]
        return n

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))


def single_device_axis_info() -> Optional["AxisInfo"]:
    """None — model code treats None as 'run the local path directly'."""
    return None


def constrain(x, axis_info: Optional[AxisInfo], *spec):
    """with_sharding_constraint that is a no-op without an AxisInfo.

    Model code sprinkles these at block boundaries so GSPMD never loses batch
    sharding (a single gather from a sharded table can otherwise poison the
    whole graph into replication).
    """
    if axis_info is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(axis_info.mesh, P(*spec)))


def constrain_batch(x, axis_info: Optional[AxisInfo]):
    """Shard dim 0 (batch) over the DP axes; everything else unconstrained."""
    if axis_info is None:
        return x
    batch = x.shape[0]
    n = 1
    for a in axis_info.batch_axes:
        n *= axis_info.mesh.shape[a]
    if batch % n:
        return x
    spec = (axis_info.batch_axes,) + (None,) * (x.ndim - 1)
    return jax.lax.with_sharding_constraint(x, NamedSharding(axis_info.mesh, P(*spec)))


def page_offset_in_shard(axis_names: Tuple[str, ...], pages_local: int):
    """Inside shard_map: first global page id owned by this rank."""
    idx = 0
    for name in axis_names:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx * pages_local
