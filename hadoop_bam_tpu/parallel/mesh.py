"""Device mesh construction.

The framework's parallelism is data parallelism over record-aligned spans
(SURVEY.md section 2.9): the mesh's ``data`` axis is the analog of the map
task pool.  Meshes are 1D by default; multi-axis shapes are accepted for
embedding this pipeline inside a larger training mesh (decode sharded along
one axis, the consumer model sharded along others).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax import shard_map  # noqa: F401 — every step builder imports it here
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape: Optional[Tuple[int, ...]] = None,
              axis_names: Sequence[str] = ("data",),
              devices=None) -> Mesh:
    if devices is None:
        devices = jax.devices()
    if shape is None:
        shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(f"mesh shape {shape} needs {n} devices, "
                         f"have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def data_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Sharding that splits the leading array dim across the data axis."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
