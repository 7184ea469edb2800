"""Spatial (sequence/context-parallel analog) sharding.

The reference's (HW)^2 correlation volume is structurally long-context
attention (SURVEY.md §5): Q = fmap1 rows, K = fmap2, memory O((HW)^2).  For
high resolutions the TPU answer is to shard the *query* rows across devices:
each device computes correlation and windowed lookup for its row-block of
queries against the (all-gathered) fmap2 — distributed blockwise correlation,
collectives riding ICI.  Plus a halo-exchange primitive so convolutions can
run on row-sharded activations inside shard_map.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import RAFTConfig
from ..ops import spmd as _spmd
from ..ops.corr import (build_pyramid, dense_corr, fmap2_pyramid,
                        lookup_dense, lookup_partial_onehot)
from .mesh import SPATIAL_AXIS, compat_shard_map


def required_h_multiple(config: RAFTConfig, n_devices: int) -> int:
    """Smallest multiple the input H must divide into for whole-model
    row-sharded inference over ``n_devices``: the /8 feature stem times the
    per-shard pyramid-pooling constraint of the ring lookup (local H/8 slab
    divisible by 2^(corr_levels-1) — see make_ring_lookup_local).  The single
    source of truth for callers validating sizes (e.g. the CLI)."""
    return 8 * n_devices * 2 ** (config.corr_levels - 1)


def halo_exchange(x: jax.Array, halo: int, axis_name: str = SPATIAL_AXIS) -> jax.Array:
    """Neighbor-row halo padding of a row-sharded block; the single
    implementation lives in ops.spmd (re-exported here with the spatial-axis
    default for shard_map users)."""
    return _spmd.halo_exchange(x, halo, axis_name)


def conv2d_row_sharded(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
                       stride: int = 1, axis_name: str = SPATIAL_AXIS) -> jax.Array:
    """conv2d on row-sharded activations: halo-exchange in H, torch-symmetric
    padding in W, VALID in H after the halo."""
    kh, kw = w.shape[0], w.shape[1]
    x = halo_exchange(x, kh // 2, axis_name)
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=((0, 0), (kw // 2, kw // 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if b is not None:
        out = out + b
    return out


def make_spatial_corr_lookup(mesh: Mesh, num_levels: int, radius: int,
                             axis: str = SPATIAL_AXIS):
    """Distributed blockwise correlation: fmap1/coords row-sharded over
    ``axis``, fmap2 row-sharded then all-gathered level-wise inside.

    Returns jitted (fmap1, fmap2, coords) -> corr features, output sharded
    like the queries.  Device memory: O(HW/n * HW) instead of O((HW)^2)."""

    def inner(f1_local, f2_local, coords_local):
        f2_full = jax.lax.all_gather(f2_local, axis, axis=1, tiled=True)
        pyramid = build_pyramid(f1_local, f2_full, num_levels)
        return lookup_dense(pyramid, coords_local, radius)

    f = compat_shard_map(inner, mesh=mesh,
                      in_specs=(P(None, axis), P(None, axis), P(None, axis)),
                      out_specs=P(None, axis))
    return jax.jit(f)


def make_ring_lookup_local(f1_local: jax.Array, f2_local: jax.Array,
                           num_levels: int, radius: int, axis: str,
                           precision=None, kernel: str = "onehot",
                           pallas_opts: Optional[dict] = None):
    """Build a per-iteration ring-pass correlation lookup closure for use
    INSIDE an existing shard_map over ``axis`` (fmap1/fmap2/coords all
    row-sharded slabs, coords in global pixel units).

    Each call runs the ring: correlate the local queries against one fmap2
    row-slab at a time ([Q/n, HW/n] tile on the MXU), accumulate that slab's
    window contributions via the one-hot partial lookup (zero outside the
    slab, so partials sum exactly), and ``ppermute`` the slab pyramid to the
    next neighbor — n-1 rotations, compute overlapping the ICI transfer,
    peak memory O((HW)^2/n^2) per device.

    ``kernel``: 'onehot' computes each slab's partial via dense_corr + the
    XLA one-hot lookup; 'pallas' runs the fused kernel per slab — shifting
    the global query coords down by the slab's start row makes the
    unchanged kernel produce exactly that slab's partial at EVERY pyramid
    level at once (the shift scales with the level like the coords do, and
    out-of-slab windows one-hot-match nothing = zeros).  ``pallas_opts``
    forwards q_blk/p_blk_target; each slab's launch
    takes the band schedule by the kernel's own plan
    (``kernel_plans.CorrLevelPlan.banded``: a map of more than one step's
    positions), like every other caller.

    ``precision=None`` means backend-default MXU precision (bf16 inputs) on
    BOTH branches: dense_corr passes it through, and the pallas branch maps
    it to ``jax.lax.Precision.DEFAULT``.
    """
    if kernel not in ("onehot", "pallas"):
        raise ValueError(f"kernel must be 'onehot' or 'pallas', "
                         f"got {kernel!r}")
    if kernel == "pallas":
        # public custom_vjp entry point: the ring path stays differentiable
        # (backward rides the XLA twin); hoisted out of the per-slab closure
        from ..ops.corr_pallas import fused_lookup
        pl_opts = {"q_blk": 128, "p_blk_target": 4096, **(pallas_opts or {})}
        # precision=None means backend default — same resolution the onehot
        # branch's dense_corr applies
        pl_prec = (precision if precision is not None
                   else jax.lax.Precision.DEFAULT)
    n_dev = _spmd.axis_size(axis)
    my = jax.lax.axis_index(axis)
    B, Hl, W, C = f1_local.shape
    if Hl % (2 ** (num_levels - 1)) != 0:
        raise ValueError(
            f"local H/8 slab {Hl} must be divisible by 2^{num_levels - 1} "
            f"so pyramid pooling stays shard-local; use fewer devices or "
            f"pad H (H/8 divisible by n_dev * 2^(levels-1)).")
    Q = Hl * W
    levels0 = fmap2_pyramid(f2_local, num_levels)     # shard-local pooling
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def lookup(coords: jax.Array) -> jax.Array:
        flat = coords.reshape(B, Q, 2)

        def contrib(levels, src):
            if kernel == "pallas":
                # global -> slab-local coords: subtract the slab's start row
                # (src * Hl full-res fmap rows); the kernel's own 1/2^i
                # scaling then lands on the right slab row at every level
                shifted = coords.at[..., 1].add(
                    -(src * Hl).astype(coords.dtype))
                # float32 out (fused_lookup's default out_dtype): the
                # slabs' partial windows are added up below
                out = fused_lookup(f1_local, tuple(levels), shifted, radius,
                                   pl_prec, pl_opts["q_blk"],
                                   pl_opts["p_blk_target"])
                return out.reshape(B, Q, -1)
            outs = []
            for i, f2l in enumerate(levels):
                H2l = f2l.shape[1]
                outs.append(lookup_partial_onehot(
                    dense_corr(f1_local, f2l, precision=precision), flat,
                    radius, i, row_offset=src * H2l))
            return jnp.concatenate(outs, axis=-1)

        def step(carry, _):
            levels, src, acc = carry
            acc = acc + contrib(levels, src)
            # rotate the fmap2 slab pyramid to the next device in the ring
            # (overlaps with the next step's correlation compute)
            levels = [jax.lax.ppermute(f2l, axis, perm) for f2l in levels]
            return (levels, (src - 1) % n_dev, acc), None

        acc0 = jnp.zeros((B, Q, num_levels * (2 * radius + 1) ** 2),
                         jnp.float32)
        # n_dev - 1 rotations: the last slab needs no ppermute
        (levels, src, acc), _ = jax.lax.scan(step, (levels0, my, acc0), None,
                                             length=n_dev - 1)
        acc = acc + contrib(levels, src)
        return acc.reshape(B, Hl, W, -1)

    return lookup


def make_ring_corr_lookup(mesh: Mesh, num_levels: int, radius: int,
                          axis: str = SPATIAL_AXIS, precision=None,
                          kernel: str = "onehot",
                          pallas_opts: Optional[dict] = None):
    """Standalone jitted ring-pass correlation lookup — the ring-attention
    analog (see :func:`make_ring_lookup_local`): (fmap1, fmap2, coords) ->
    [B, H, W, L*(2r+1)^2], all arrays row-sharded over ``axis``.

    ``precision`` / ``kernel`` / ``pallas_opts`` forward to
    :func:`make_ring_lookup_local` with the same semantics, so the standalone
    entry point exposes the full option surface of the in-model ring path."""

    def inner(f1_local, f2_local, coords_local):
        lookup = make_ring_lookup_local(f1_local, f2_local, num_levels,
                                        radius, axis, precision=precision,
                                        kernel=kernel,
                                        pallas_opts=pallas_opts)
        return lookup(coords_local)

    f = compat_shard_map(inner, mesh=mesh,
                      in_specs=(P(None, axis), P(None, axis), P(None, axis)),
                      out_specs=P(None, axis))
    return jax.jit(f)


def make_shard_inference_fn(config: RAFTConfig, mesh: Mesh,
                            iters: Optional[int] = None,
                            axis: str = SPATIAL_AXIS):
    """Whole-model row-sharded inference via shard_map — the full
    sequence-parallel path, explicit-collectives edition of
    :func:`make_spatial_inference_fn`.

    The unchanged model code runs under ``ops.spmd.spatial_sharding``:
    convolutions halo-exchange boundary rows, instance norms psum their
    statistics, upsampling fetches one-row halos, and the correlation runs
    the ring pass (``make_ring_lookup_local``) — no (HW)^2/n volume, no
    fmap2 all-gather.  Constraints: H divisible by
    8 * n_devices * 2^(corr_levels-1).

    Returns jitted (params, image1, image2) -> flow, images/flow row-sharded
    over ``axis``.
    """
    from ..models.raft import raft_forward
    from ..ops import spmd

    def fwd(params, image1, image2):
        with spmd.spatial_sharding(axis):
            out, _ = raft_forward(params, image1, image2, config,
                                  iters=iters, train=False, all_flows=False)
        return out.flow

    f = compat_shard_map(fwd, mesh=mesh,
                      in_specs=(P(), P(None, axis), P(None, axis)),
                      out_specs=P(None, axis))
    return jax.jit(f)


def make_spatial_inference_fn(config: RAFTConfig, mesh: Mesh,
                              iters: Optional[int] = None,
                              axis: str = SPATIAL_AXIS):
    """Whole-model inference with images row-sharded over ``axis`` via jit
    sharding annotations: XLA's SPMD partitioner inserts the halo exchanges
    for the convolutions and the collectives for the correlation
    automatically — the pjit path, complementing the explicit shard_map path
    above."""
    from ..models.raft import make_inference_fn

    fn = make_inference_fn(config, iters=iters)
    img_sharding = NamedSharding(mesh, P(None, axis))
    rep = NamedSharding(mesh, P())

    return jax.jit(fn, in_shardings=(rep, img_sharding, img_sharding),
                   out_shardings=img_sharding)
