"""Device-mesh helpers.

The scale-out story of this framework (SURVEY.md §2.3): data parallelism over
the 'data' axis (psum gradient all-reduce over ICI — replacing the
reference's dead tensorpack parameter-server trainer), and 'spatial'
parallelism over image rows for the high-resolution correlation (the
sequence/context-parallel analog of the (HW)^2 volume, SURVEY.md §5).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
SPATIAL_AXIS = "spatial"


def make_mesh(axes: Sequence[str] = (DATA_AXIS,),
              shape: Optional[Tuple[int, ...]] = None,
              devices=None) -> Mesh:
    """Mesh over the given logical axes; default: all devices on 'data'."""
    devices = list(jax.devices()) if devices is None else list(devices)
    if shape is None:
        shape = (len(devices),) + (1,) * (len(axes) - 1)
    assert int(np.prod(shape)) == len(devices), (shape, len(devices))
    return Mesh(np.asarray(devices).reshape(shape), axes)


def compat_shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (``check_vma=False``):
    this stack's specs replicate params explicitly and the check rejects
    some valid psum patterns.  One wrapper so every shard_map call site in
    parallel/ sets the knob the same way."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def batch_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Leading-dim sharding for input batches."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, batch, axis: str = DATA_AXIS):
    """Place a host batch onto the mesh, leading dim sharded over ``axis``."""
    sharding = batch_sharding(mesh, axis)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)
