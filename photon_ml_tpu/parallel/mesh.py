"""Device-mesh construction helpers.

The reference's execution substrate is a Spark cluster (driver + executors);
ours is a :class:`jax.sharding.Mesh` over TPU chips. Axis vocabulary used
throughout the framework (SURVEY.md §2.10):

- ``"data"`` — sample sharding for the fixed effect (replaces RDD partitions
  + ``treeAggregate``),
- ``"entity"`` — random-effect entity sharding (replaces the
  ``RandomEffectDatasetPartitioner`` hash sharding),
- ``"feature"`` — optional coefficient-dimension sharding for very wide
  fixed-effect models (no reference equivalent; breeze held the full vector
  on the driver).

Multi-host: pass the global device list; the same axis names ride ICI within
a slice and DCN across slices (mesh construction orders devices so the
fastest-varying axis maps to ICI neighbours, which `jax.make_mesh` handles).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"
ENTITY_AXIS = "entity"
FEATURE_AXIS = "feature"


def make_mesh(
    axis_sizes: Optional[dict[str, int]] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a mesh; default is all devices on one ``"data"`` axis."""
    devices = list(devices) if devices is not None else jax.devices()
    if not axis_sizes:
        axis_sizes = {DATA_AXIS: len(devices)}
    names = tuple(axis_sizes)
    shape = tuple(axis_sizes[n] for n in names)
    n_needed = 1
    for s in shape:
        n_needed *= s
    if n_needed > len(devices):
        raise ValueError(f"mesh {axis_sizes} needs {n_needed} devices, have {len(devices)}")
    # Auto axis types: GSPMD propagates shardings; shard_map enters Manual
    # mode explicitly where we want hand-placed psums (jax.make_mesh
    # defaults to Explicit mode, which demands a global set_mesh context
    # instead).
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(names),
                         devices=devices[:n_needed])


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def data_sharded(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Leading-dim sharding over ``axis``."""
    return NamedSharding(mesh, PartitionSpec(axis))
