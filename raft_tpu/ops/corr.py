"""All-pairs correlation pyramid and windowed lookup — the TPU answer to the
reference's never-written CUDA correlation extension (reference readme.md:12).

Reference semantics being matched (reference networks/model_utils.py:199-249):
  corr[b, q, p] = <fmap1[b, q], fmap2[b, p]> / sqrt(C), pyramid by 2x2
  average-pooling over the p-plane, then per-query bilinear sampling of a
  (2r+1)^2 window centered at coords/2^level, channels ordered
  (level, x-offset, y-offset) — the x-offset-major order both the reference
  and official RAFT produce.

TPU-first design, not a translation:

* Pyramid by linearity: avg-pooling the (HW)^2 volume over the p-plane equals
  correlating against an avg-pooled fmap2, so level i is computed directly as
  ``fmap1 @ pool_i(fmap2)^T`` — the reference's 191 MB level-0 volume is never
  pooled, and levels 1..3 cost a fraction of the reference's AvgPooling chain.
* Shared-fraction window lookup: all (2r+1)^2 sample points of one query share
  a single fractional offset, so the bilinear sample of the whole window is
  4 shifted views of one (2r+2)^2 integer window — two ``take_along_axis``
  gathers per level per query instead of 4 gathers x (2r+1)^2 points.
* On-demand (blockwise) mode: gathers the fmap2 feature window and contracts
  with fmap1 per query chunk — O(HW * (2r+2)^2 * C) per iteration, never
  materializing any (HW)^2 volume.  This is the flash-attention-style answer
  to the reference's memory blow-up, and the correctness reference for the
  fused Pallas kernel in ``corr_pallas.py``.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..lint.contracts import contract
from ..telemetry.trace import stage
from .conv import avg_pool2d


def as_precision(corr_precision) -> jax.lax.Precision:
    """``RAFTConfig.corr_precision`` ('highest' / 'default') or a
    ``jax.lax.Precision`` -> the ``jax.lax.Precision``."""
    if isinstance(corr_precision, jax.lax.Precision):
        return corr_precision
    return (jax.lax.Precision.HIGHEST if corr_precision == "highest"
            else jax.lax.Precision.DEFAULT)


def corr_terms(f1_dtype, f2_dtype, precision) -> Tuple[int, int]:
    """How many bfloat16 terms ``(n1, n2)`` the fused kernel's correlation
    matmul (``ops/corr_pallas.py``) multiplies its operands as — the ONE
    place that decides the kernel's MXU passes
    (:func:`corr_mxu_passes`), from what it can observe of its input.

    A bfloat16 value is one term.  A float32 value is three (hi + mid + lo,
    8 + 8 + 8 significand bits, exact).  With a bfloat16 ``f1`` the kernel is
    handed exactly the terms ``f2`` needs (``corr_pallas.f2_terms``) and sums
    one single-pass dot per term: every product of the float32 ``HIGHEST``
    matmul that is not zero.  A float32 ``f1`` keeps that ``HIGHEST`` matmul
    (the MXU's own split, ``(3, 3)``: six passes); ``DEFAULT`` keeps meaning
    one pass over operands the MXU rounds itself.
    """
    if as_precision(precision) != jax.lax.Precision.HIGHEST:
        return 1, 1
    if jnp.dtype(f1_dtype) != jnp.bfloat16:
        return 3, 3
    return 1, (1 if jnp.dtype(f2_dtype) == jnp.bfloat16 else 3)


def corr_mxu_passes(n1: int, n2: int) -> int:
    """MXU passes of one correlation tile: a dot per term of ``f2`` against a
    one-term ``f1``; the float32 ``HIGHEST`` matmul (3, 3) runs six."""
    return 6 if (n1, n2) == (3, 3) else n1 * n2


def level_mxu_passes(map_dtype, num_levels: int,
                     corr_precision) -> Tuple[int, ...]:
    """MXU passes of each pyramid level's correlation tile for encoder maps
    of ``map_dtype``: level 0 is the map itself, the pooled levels are
    float32 (1/3/3/3 for bfloat16 maps at 'highest', 6/6/6/6 for float32).
    What the serving engine logs and exports per level."""
    return tuple(
        corr_mxu_passes(*corr_terms(
            map_dtype, map_dtype if level == 0 else jnp.float32,
            corr_precision))
        for level in range(num_levels))


def fmap2_pyramid(fmap2: jax.Array, num_levels: int = 4) -> List[jax.Array]:
    """[B, H, W, C] -> list of ``num_levels`` pooled maps (level 0 = input)."""
    levels = [fmap2]
    for _ in range(num_levels - 1):
        levels.append(avg_pool2d(levels[-1], 2, 2))
    return levels


def mask_ragged_rows(x: jax.Array, sizes: jax.Array) -> jax.Array:
    """Zero everything outside each item's live crop of a shared max box.

    x: [B, H, W, ...] with every item corner-anchored at (0, 0);
    sizes: [B, 2] int32 per-item (h, w) live extents.  Dtype-preserving, so
    it composes with bf16 feature maps and int coordinate planes alike.
    """
    B, H, W = x.shape[:3]
    sizes = sizes.astype(jnp.int32)
    iy = jax.lax.broadcasted_iota(jnp.int32, (B, H, W), 1)
    ix = jax.lax.broadcasted_iota(jnp.int32, (B, H, W), 2)
    live = (iy < sizes[:, 0, None, None]) & (ix < sizes[:, 1, None, None])
    live = live.reshape(live.shape + (1,) * (x.ndim - 3))
    return jnp.where(live, x, jnp.zeros((), x.dtype))


def ragged_pyramid(fmap2: jax.Array, sizes: jax.Array,
                   num_levels: int = 4) -> List[jax.Array]:
    """Ragged twin of :func:`fmap2_pyramid`: every item is a corner-anchored
    ``sizes[b] = (h_b, w_b)`` crop living in one shared ``[B, Hm, Wm, C]``
    max box, and each pyramid level re-masks the dead region to zero with the
    floor-halved extents ``sizes // 2^level``.

    Why the per-level re-mask makes this EXACT (not just approximate) w.r.t.
    each crop's own pyramid: ``avg_pool2d`` is window-2/stride-2/VALID, so a
    level-l map keeps rows ``[0, h // 2^l)``.  Every kept window at level
    l+1 covers rows ``2p, 2p+1 < 2*(h_l // 2)  <= h_l`` — entirely inside the
    live region — so kept values equal the solo crop's pooled values.  At an
    ODD live extent the boundary window would mix one live row with one dead
    (zero) row and emit half the true average, but that window's index is
    exactly ``h_l // 2``, the first index the next mask kills.  Masking
    level 0 first, then pool+mask per level, therefore reproduces each
    crop's standalone pyramid embedded in the max box with zeros outside —
    the zeros-padding lookup semantics fall out for free.
    """
    sizes = sizes.astype(jnp.int32)
    levels = [mask_ragged_rows(fmap2, sizes)]
    for _ in range(num_levels - 1):
        sizes = sizes // 2
        levels.append(mask_ragged_rows(avg_pool2d(levels[-1], 2, 2), sizes))
    return levels


@contract(fmap1="*[B,H,W,C]", fmap2_l="*[B,H2,W2,C]",
          _returns="f32[B,Q,H2,W2]")
def dense_corr(fmap1: jax.Array, fmap2_l: jax.Array,
               precision=None) -> jax.Array:
    """[B, H1, W1, C] x [B, H2, W2, C] -> [B, H1*W1, H2, W2] scaled corr."""
    B, H1, W1, C = fmap1.shape
    _, H2, W2, _ = fmap2_l.shape
    f1 = fmap1.reshape(B, H1 * W1, C)
    f2 = fmap2_l.reshape(B, H2 * W2, C)
    corr = jnp.einsum("bqc,bpc->bqp", f1, f2, precision=precision,
                      preferred_element_type=jnp.float32)
    corr = corr / jnp.sqrt(jnp.asarray(C, jnp.float32))
    return corr.reshape(B, H1 * W1, H2, W2)


def build_pyramid(fmap1: jax.Array, fmap2: jax.Array, num_levels: int = 4,
                  precision=None) -> List[jax.Array]:
    """Dense correlation pyramid: list of [B, Q, H2/2^i, W2/2^i]."""
    with stage("corr/pyramid"):
        return [dense_corr(fmap1, f2, precision=precision)
                for f2 in fmap2_pyramid(fmap2, num_levels)]


def _window_gather_2d(vol: jax.Array, ix0: jax.Array, iy0: jax.Array, win: int) -> jax.Array:
    """Gather aligned integer windows with zeros padding.

    vol: [B, Q, H, W]; ix0, iy0: int32 [B, Q] top-left window corner.
    Returns [B, Q, win(y), win(x)].
    """
    B, Q, H, W = vol.shape
    offs = jnp.arange(win, dtype=jnp.int32)
    iy = iy0[..., None] + offs          # [B, Q, win]
    ix = ix0[..., None] + offs
    valid_y = (iy >= 0) & (iy < H)
    valid_x = (ix >= 0) & (ix < W)
    iyc = jnp.clip(iy, 0, H - 1)
    ixc = jnp.clip(ix, 0, W - 1)
    # rows: [B, Q, H, W] -> [B, Q, win, W]
    rows = jnp.take_along_axis(vol, iyc[..., None], axis=2)
    rows = jnp.where(valid_y[..., None], rows, 0.0)
    # cols: [B, Q, win, W] -> [B, Q, win, win]
    winv = jnp.take_along_axis(rows, ixc[:, :, None, :], axis=3)
    winv = jnp.where(valid_x[:, :, None, :], winv, 0.0)
    return winv


def _bilinear_window(winv: jax.Array, fx: jax.Array, fy: jax.Array, r: int) -> jax.Array:
    """Combine a (2r+2)^2 integer window into the (2r+1)^2 bilinear samples.

    winv: [B, Q, 2r+2(y), 2r+2(x)]; fx, fy: [B, Q] fractional offsets.
    Returns [B, Q, (2r+1)^2] in x-offset-major order.
    """
    n = 2 * r + 1
    v00 = winv[:, :, :n, :n]       # (y+0, x+0)
    v01 = winv[:, :, :n, 1:]       # (y+0, x+1)
    v10 = winv[:, :, 1:, :n]       # (y+1, x+0)
    v11 = winv[:, :, 1:, 1:]       # (y+1, x+1)
    fx = fx[..., None, None]
    fy = fy[..., None, None]
    out = ((1 - fx) * (1 - fy) * v00 + fx * (1 - fy) * v01
           + (1 - fx) * fy * v10 + fx * fy * v11)      # [B, Q, ny, nx]
    return out.transpose(0, 1, 3, 2).reshape(*out.shape[:2], n * n)


@stage("corr/lookup_dense")
@contract(coords="*[B,H,W,2]", _returns="f32[B,H,W,N]")
def lookup_dense(pyramid: Sequence[jax.Array], coords: jax.Array, radius: int) -> jax.Array:
    """Sample the dense pyramid at ``coords`` [B, H, W, 2] (x, y).

    Returns [B, H, W, L*(2r+1)^2], levels concatenated in order.
    """
    B, H, W, _ = coords.shape
    Q = H * W
    flat = coords.reshape(B, Q, 2)
    outs = []
    for i, corr in enumerate(pyramid):
        c = flat / (2.0 ** i)
        cx, cy = c[..., 0], c[..., 1]
        cx0 = jnp.floor(cx)
        cy0 = jnp.floor(cy)
        ix0 = cx0.astype(jnp.int32) - radius
        iy0 = cy0.astype(jnp.int32) - radius
        winv = _window_gather_2d(corr, ix0, iy0, 2 * radius + 2)
        outs.append(_bilinear_window(winv, cx - cx0, cy - cy0, radius))
    return jnp.concatenate(outs, axis=-1).reshape(B, H, W, -1)


def _onehot_interp(idx0: jax.Array, frac: jax.Array, n: int, size: int,
                   offset: int | jax.Array = 0) -> jax.Array:
    """Separable bilinear selection matrix A [B, Q, n, size]:
    ``A[b,q,j,p] = (1-frac)*[p+offset == idx0+j] + frac*[p+offset == idx0+j+1]``.

    Out-of-range indices simply never match — zeros padding for free.  The
    ``offset`` shifts the p-plane (used by ring/partial lookups where only a
    row-slab of the correlation plane is present).
    """
    B, Q = idx0.shape
    ids = jnp.arange(size, dtype=jnp.int32)[None, None, None, :] + offset
    tgt = idx0[:, :, None, None] + jnp.arange(n, dtype=jnp.int32)[None, None, :, None]
    f = frac[:, :, None, None]
    return (jnp.where(ids == tgt, 1.0 - f, 0.0)
            + jnp.where(ids == tgt + 1, f, 0.0))


@contract(corr3="f32[B,Q,HB,W]", coords="*[B,Q,2]", _returns="f32[B,Q,N]")
def lookup_partial_onehot(corr3: jax.Array, coords: jax.Array, radius: int,
                          level: int, row_offset: int | jax.Array = 0) -> jax.Array:
    """Window lookup on a (possibly row-partial) correlation plane, as two
    one-hot interpolation matmuls (the MXU formulation of bilinear window
    sampling — same math as the fused Pallas kernel, in plain XLA).

    corr3: [B, Q, Hblk, W2] correlation against rows
    [row_offset, row_offset + Hblk) of the level-``level`` p-plane;
    coords: [B, Q, 2] full-resolution (x, y) query coords.
    Returns [B, Q, (2r+1)^2] in x-offset-major order; contributions from
    window rows outside the slab are zero, so partial results over a row
    partition of the plane sum to the full lookup.
    """
    B, Q, Hblk, W2 = corr3.shape
    n = 2 * radius + 1
    c = coords / (2.0 ** level)
    cx, cy = c[..., 0], c[..., 1]
    cx0 = jnp.floor(cx)
    cy0 = jnp.floor(cy)
    a_y = _onehot_interp(cy0.astype(jnp.int32) - radius, cy - cy0, n, Hblk,
                         offset=row_offset)                    # [B,Q,n,Hblk]
    a_x = _onehot_interp(cx0.astype(jnp.int32) - radius, cx - cx0, n, W2)
    win_y = jnp.einsum("bqjh,bqhw->bqjw", a_y, corr3,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)     # [B,Q,n(y),W2]
    win = jnp.einsum("bqiw,bqjw->bqij", a_x, win_y,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)       # [B,Q,n(x),n(y)]
    return win.reshape(B, Q, n * n)


@stage("corr/lookup_dense_onehot")
@contract(coords="*[B,H,W,2]", _returns="f32[B,H,W,N]")
def lookup_dense_onehot(pyramid: Sequence[jax.Array], coords: jax.Array,
                        radius: int) -> jax.Array:
    """Drop-in alternative to ``lookup_dense`` using the one-hot matmul
    formulation instead of gathers (TPU: MXU work beats take_along_axis)."""
    B, H, W, _ = coords.shape
    flat = coords.reshape(B, H * W, 2)
    outs = [lookup_partial_onehot(corr, flat, radius, i)
            for i, corr in enumerate(pyramid)]
    return jnp.concatenate(outs, axis=-1).reshape(B, H, W, -1)


def _gather_feature_windows(fmap: jax.Array, ix0: jax.Array, iy0: jax.Array, win: int) -> jax.Array:
    """fmap: [B, H, W, C]; ix0/iy0: [B, T] -> [B, T, win(y), win(x), C], zeros OOB.

    One flat gather over the H*W plane of exactly the T*win^2 window points.
    An earlier two-stage version (row gather then column gather)
    materialized a [B, T*win, W, C] intermediate — ~W/win x larger than the
    output, hundreds of MB at chunk 1024 — which made the gather-lookup
    blockwise path the one degenerate config of a CPU sweep (3.7x slower
    than its one-hot sibling).
    """
    B, H, W, C = fmap.shape
    offs = jnp.arange(win, dtype=jnp.int32)
    iy = iy0[..., None] + offs                       # [B, T, win]
    ix = ix0[..., None] + offs
    valid = ((iy >= 0) & (iy < H))[..., :, None] & \
            ((ix >= 0) & (ix < W))[..., None, :]     # [B, T, win(y), win(x)]
    flat = (jnp.clip(iy, 0, H - 1)[..., :, None] * W
            + jnp.clip(ix, 0, W - 1)[..., None, :])  # [B, T, win, win]
    T = iy.shape[1]
    pts = jnp.take_along_axis(fmap.reshape(B, H * W, C),
                              flat.reshape(B, T * win * win, 1), axis=1)
    return jnp.where(valid[..., None], pts.reshape(B, T, win, win, C), 0.0)


@stage("corr/lookup_ondemand")
@contract(fmap1="*[B,H,W,C]", coords="*[B,H,W,2]", _returns="f32[B,H,W,N]")
def lookup_ondemand(fmap1: jax.Array, fmap2_levels: Sequence[jax.Array],
                    coords: jax.Array, radius: int,
                    chunk: Optional[int] = None,
                    precision=None) -> jax.Array:
    """Blockwise correlation lookup without any (HW)^2 volume.

    For each query chunk and level: gather the (2r+2)^2 fmap2 feature window,
    contract with the query's fmap1 vector on the MXU, combine bilinearly.

    ``chunk`` (queries per ``lax.map`` step) defaults to a cache-budgeted
    size: the live window buffer is B * chunk * (2r+2)^2 * C floats, and a
    round-6 CPU sweep showed time tracking that buffer, not the chunk count
    — ~7-13 MB is the sweet spot at the bench shapes while the old fixed
    chunk=1024 ran buffers of 100-400 MB for a 3-5x slowdown on the CPU.
    The path stays gather-BOUND by construction either
    way — it is the reference SampleCorr semantics twin and the fused
    kernel's backward-gradient oracle, not a fast path;
    ``lookup_blockwise_onehot`` replaces the gathers with matmuls and is
    the shipping blockwise default.
    """
    B, H, W, C = fmap1.shape
    Q = H * W
    n = 2 * radius + 1
    win = 2 * radius + 2
    if chunk is None:
        budget = 8 * 2 ** 20                     # ~8 MB window buffer
        chunk = max(32, min(1024, budget // max(1, B * win * win * C * 4)))
        chunk = 1 << (chunk.bit_length() - 1)    # pow2 so padding stays small
    f1 = fmap1.reshape(B, Q, C)
    flat = coords.reshape(B, Q, 2)
    scale = 1.0 / jnp.sqrt(jnp.asarray(C, jnp.float32))

    # pad Q to a multiple of chunk so lax.map sees uniform chunks
    pad = (-Q) % chunk
    if pad:
        f1 = jnp.pad(f1, ((0, 0), (0, pad), (0, 0)))
        flat = jnp.pad(flat, ((0, 0), (0, pad), (0, 0)))
    nchunks = (Q + pad) // chunk
    f1 = f1.reshape(B, nchunks, chunk, C).transpose(1, 0, 2, 3)
    flat = flat.reshape(B, nchunks, chunk, 2).transpose(1, 0, 2, 3)

    def one_chunk(args):
        f1_c, coords_c = args          # [B, T, C], [B, T, 2]
        outs = []
        for i, f2 in enumerate(fmap2_levels):
            c = coords_c / (2.0 ** i)
            cx, cy = c[..., 0], c[..., 1]
            cx0 = jnp.floor(cx)
            cy0 = jnp.floor(cy)
            ix0 = cx0.astype(jnp.int32) - radius
            iy0 = cy0.astype(jnp.int32) - radius
            winf = _gather_feature_windows(f2, ix0, iy0, win)      # [B,T,win,win,C]
            winv = jnp.einsum("btyxc,btc->btyx", winf, f1_c, precision=precision,
                              preferred_element_type=jnp.float32) * scale
            outs.append(_bilinear_window(winv, cx - cx0, cy - cy0, radius))
        return jnp.concatenate(outs, axis=-1)      # [B, T, L*n*n]

    out = jax.lax.map(one_chunk, (f1, flat))       # [nchunks, B, T, L*n*n]
    out = out.transpose(1, 0, 2, 3).reshape(B, Q + pad, -1)
    if pad:
        out = out[:, :Q]
    return out.reshape(B, H, W, -1)


@stage("corr/lookup_blockwise_onehot")
@contract(fmap1="*[B,H,W,C]", coords="*[B,H,W,2]", _returns="f32[B,H,W,N]")
def lookup_blockwise_onehot(fmap1: jax.Array, f2_levels: Sequence[jax.Array],
                            coords: jax.Array, radius: int,
                            chunk: int = 512, precision=None) -> jax.Array:
    """Blockwise correlation lookup, matmul-only (no gathers, no (HW)^2
    volume): per query chunk and level, one [T, P] correlation tile on the
    MXU followed by the separable one-hot window lookup — the XLA twin of
    the fused Pallas kernel (ops/corr_pallas.py), fully differentiable, so
    it also serves as that kernel's backward delegate."""
    B, H, W, C = fmap1.shape
    Q = H * W
    f1 = fmap1.reshape(B, Q, C)
    flat = coords.reshape(B, Q, 2)
    scale = 1.0 / jnp.sqrt(jnp.asarray(C, jnp.float32))

    pad = (-Q) % chunk
    if pad:
        f1 = jnp.pad(f1, ((0, 0), (0, pad), (0, 0)))
        flat = jnp.pad(flat, ((0, 0), (0, pad), (0, 0)))
    nchunks = (Q + pad) // chunk
    f1 = f1.reshape(B, nchunks, chunk, C).transpose(1, 0, 2, 3)
    flat = flat.reshape(B, nchunks, chunk, 2).transpose(1, 0, 2, 3)

    def one_chunk(args):
        f1_c, coords_c = args          # [B, T, C], [B, T, 2]
        outs = []
        for i, f2 in enumerate(f2_levels):
            _, H2, W2, _ = f2.shape
            corr = jnp.einsum("btc,bpc->btp", f1_c,
                              f2.reshape(B, H2 * W2, C), precision=precision,
                              preferred_element_type=jnp.float32) * scale
            outs.append(lookup_partial_onehot(
                corr.reshape(B, chunk, H2, W2), coords_c, radius, i))
        return jnp.concatenate(outs, axis=-1)   # [B, T, L*n*n]

    out = jax.lax.map(one_chunk, (f1, flat))    # [nchunks, B, T, L*n*n]
    out = out.transpose(1, 0, 2, 3).reshape(B, Q + pad, -1)
    if pad:
        out = out[:, :Q]
    return out.reshape(B, H, W, -1)


def naive_corr_lookup(fmap1: jax.Array, fmap2: jax.Array, coords: jax.Array,
                      num_levels: int, radius: int) -> jax.Array:
    """Straightforward per-point implementation mirroring the reference's
    SampleCorr semantics (model_utils.py:224-249) — test oracle only."""
    from .grid_sample import grid_sample
    B, H, W, C = fmap1.shape
    pyramid = build_pyramid(fmap1, fmap2, num_levels)
    n = 2 * radius + 1
    d = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    # x-offset-major window points, matching reference/official ordering
    delta = jnp.stack(jnp.meshgrid(d, d, indexing="ij"), axis=-1)  # [nx, ny, 2]=(dx,dy)
    outs = []
    for i, corr in enumerate(pyramid):
        _, Q, H2, W2 = corr.shape
        vol = corr.reshape(B * Q, H2, W2, 1)
        centroid = coords.reshape(B * Q, 1, 1, 2) / (2.0 ** i)
        pts = centroid + delta.reshape(1, n, n, 2)
        sampled = grid_sample(vol, pts, padding_mode="zeros")       # [BQ, n, n, 1]
        outs.append(sampled.reshape(B, H, W, n * n))
    return jnp.concatenate(outs, axis=-1)
