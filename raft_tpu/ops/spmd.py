"""Trace-time SPMD context for spatially-sharded (sequence-parallel) runs.

The whole-model distributed path runs the *unchanged* model code inside
``shard_map`` with activations row-sharded on the image H axis.  Rather than
threading an axis name through every op call, the ops layer consults this
context: while :func:`spatial_sharding` is active (statically, during
tracing),

* ``conv2d`` halo-exchanges boundary rows and convolves VALID in H,
* ``instance_norm``/``group_norm`` reduce their statistics with psums,
* convex upsampling and align-corners resize fetch their one-row halos and
  build shard-offset interpolation weights.

This is the sequence-parallel analog for the reference's (HW)^2 correlation
workload (SURVEY.md §5): "sequence length" is image rows, collectives ride
the ICI ring.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

import jax
import jax.numpy as jnp

_axis: Optional[str] = None


@contextmanager
def spatial_sharding(axis_name: str):
    """Enable row-sharded semantics for ops traced inside this block."""
    global _axis
    prev = _axis
    _axis = axis_name
    try:
        yield
    finally:
        _axis = prev


def spatial_axis() -> Optional[str]:
    return _axis


def axis_size(axis_name: str) -> int:
    """Static size of a mapped axis as a plain int, usable in static
    control flow."""
    return jax.lax.axis_size(axis_name)


def halo_exchange(x: jax.Array, halo: int, axis_name: Optional[str] = None) -> jax.Array:
    """Pad the H axis (axis 1 of [B, H, W, C]) of a row-sharded block with
    ``halo`` rows from the neighboring shards; zeros at the outer edges (the
    image boundary, matching torch zero padding).  Returns
    [B, H + 2*halo, W, C]."""
    if halo == 0:
        return x
    axis_name = _axis if axis_name is None else axis_name
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    Hl = x.shape[1]
    if halo > Hl:
        # halo wider than the slab (tiny maps): neighbor exchange can't
        # supply enough rows, so gather the full H axis and cut the padded
        # window — correct and cheap exactly when maps are tiny.
        full = jax.lax.all_gather(x, axis_name, axis=1, tiled=True)
        full = jnp.pad(full, ((0, 0), (halo, halo), (0, 0), (0, 0)))
        return jax.lax.dynamic_slice_in_dim(full, idx * Hl, Hl + 2 * halo,
                                            axis=1)
    top = x[:, :halo]          # my top rows -> previous device's bottom halo
    bot = x[:, -halo:]         # my bottom rows -> next device's top halo
    # from next device: its top rows become my bottom halo
    from_next = jax.lax.ppermute(top, axis_name,
                                 [(i, (i - 1) % n) for i in range(n)])
    # from previous device: its bottom rows become my top halo
    from_prev = jax.lax.ppermute(bot, axis_name,
                                 [(i, (i + 1) % n) for i in range(n)])
    zeros = jnp.zeros_like(top)
    top_halo = jnp.where(idx == 0, zeros, from_prev)
    bot_halo = jnp.where(idx == n - 1, zeros, from_next)
    return jnp.concatenate([top_halo, x, bot_halo], axis=1)
