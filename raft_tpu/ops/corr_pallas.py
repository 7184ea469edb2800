"""Fused blockwise correlation + windowed lookup as a Pallas TPU kernel.

This is the framework's stand-in for the reference's never-written CUDA
correlation extension (reference readme.md:12): the reference materializes the
full (HW)^2 volume in device memory (reference networks/model_utils.py:206-215,
~191 MB at 432x1024) and then bilinear-samples 81 points per query from it
(model_utils.py:224-249). Here the volume never exists in HBM at all.

Design (flash-attention-style: the matmul on the MXU, the window on the
vector and cross-lane units):

* Grid ``(B, Q-blocks, key steps)``. Each program computes one correlation
  tile ``f1_block @ f2_rows^T`` on the MXU — at any instant only a ``[T,
  rows x lanes]`` tile lives in VMEM: 128 queries in the sublanes, the
  positions of some consecutive key rows in the lanes.
* WHICH rows is the query tile's own choice wherever the map is more than
  one step's positions (``kernel_plans.corr_level_plan``): a tile's 128
  queries are consecutive in raster order, so their windows touch a band of
  ``2r + 2`` rows and a few more, and the launch's schedule names the first
  row of that band (a multiple of the granule ``g`` under the tile's first
  window row).  A grid step is handed the ``R`` rows from there as ``R / g``
  blocks of ``g`` rows — the same planes passed ``R / g`` times under plain
  blocked indexing, block ``S + i`` — so tiles that name the same band (the
  next tile of a query row, a repeated entry) refetch nothing and a tile
  multiplies and selects over the rows it needs, not over one or two fixed
  row-blocks of 32 (PRs 26-35; TUNING.md, PR 36).  A tile whose windows span
  more than ``R`` rows takes further bands, disjoint and in order, up to the
  whole map: exact for any flow.  The launch takes as many key steps a tile
  as the tile of THIS lookup that needs most of them (a dynamic grid
  dimension, :func:`schedule_steps`: one on served flow), not as many as the
  level has bands: a step that visits nothing still cost 0.2-0.3 us of the
  4-6 a tile (TUNING.md, PR 38).
* WHERE a row lies in the lanes is the level's width
  (``kernel_plans.corr_row_lanes``): a row of 64, 32 or 16 columns or fewer
  is stored that many lanes wide, so 2, 4 or 8 map rows share one 128-lane
  row of the planes and of the tile (a contiguous ``[H2, 64, C]`` map IS
  ``[H2 / 2, 128, C]``: :func:`pad_planes` pads, nothing is relaid).  The
  MXU multiplies, and the selection walks, a pooled level's columns and not
  the zeros that padded each row to a vector register: half, three quarters
  and seven eighths of levels 2 and 3 at 1080x1920 and of levels 1-3 at
  440x1024 until PR 43 (TUNING.md).
* A query's (2r+1)^2 bilinear window needs the (2r+2)^2 integer taps around
  it.  A visited block SELECTS the taps that lie in it, exactly
  (:func:`_window_taps`): each 128-lane chunk of the tile (a map row, a
  half of one, or 2, 4 or 8 narrow ones) is gathered along its lanes at the
  query's own columns (``tpu.dynamic_gather``, a different rotation in
  every sublane), eight map rows are packed into one 128-lane tile, and a
  second gather picks the query's own rows.  Lanes move; nothing is
  multiplied, so a tap is the float32 correlation sum bit for bit, a tap in
  other rows or off the map is an exact zero (zeros padding for free),
  and windows that reach past a step's rows add up across the k grid
  dimension in a float32 VMEM scratch of ``[T, 256]``.
* The bilinear BLEND runs once a query tile, on its last grid step
  (:func:`_write_windows`): four products and three adds a value in float32
  from two lane rotations of the scratch, the only rounding after the
  correlation sums, then one lane-dense store.
* Until PR 32 the window was *interpolated* in every visited block by two
  per-query batched matmuls with weighted one-hot matrices, ``A_x[t] @
  (A_y[t] @ corr[t])^T`` at ``HIGHEST`` ("these dots are tiny next to the
  corr matmul").  They were two thirds of every launch: 128 slivers of
  ``[9, rows] x [rows, lanes]`` a step cost a fixed 5 us (10-12 us at 256
  lanes) whatever the block held, where the correlation dot itself takes
  0.3-4 us (TUNING.md).  A broadcast-multiply-reduce form on the VPU, the
  other value of the option that chose between them, was 1.5-3.7 x slower
  still; the option went with both.
* Backward delegates to the differentiable, matmul-only XLA twin
  (``ops.corr.lookup_blockwise_onehot``) via ``custom_vjp``: the forward
  rides the kernel, gradients ride XLA matmul fusions with no gathers.
  (``coords`` is ``stop_gradient``'d upstream anyway — models/raft.py
  step(), mirroring reference RAFT.py:93.)

Numerics: correlation products exact, accumulated in float32 (outputs match
``ops.corr.lookup_dense`` on the same values to float32 round-off).  At
``corr_precision='highest'`` the MXU multiplies bfloat16 terms, and how many
terms an operand needs is a fact of its dtype (:func:`corr_terms`): a
bfloat16 map is one term, a float32 map pooled from it is three (hi + mid +
lo, split once where the pyramid is built), so bfloat16 maps cost one MXU
pass at level 0 and three at the pooled levels; float32 maps take the MXU's
own six-pass ``HIGHEST`` matmul.  Same products, same float32 sums — the
passes left out multiplied zeros.  Selection is exact, scaling, the sums over
key steps and the blend are float32 throughout; a launch
writes its windows once, rounded to the dtype its consumer states
(``out_dtype``) and lane-dense (``[B, Q, n*n]``): a ``[.., 9, 9]`` float32
array pads each query's 324 B to 8 KiB of HBM tiles, and converting and
reshaping that cost a fifth of the served program's run (PR 29).  Off-TPU
backends run the kernel in Pallas interpret mode so CPU tests exercise
identical code.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..kernel_plans import (LANE, TAP_LANES, VMEM_BYTES, corr_level_plan,
                            corr_tap_tiles)
from ..lint.contracts import contract
from ..telemetry.trace import stage
# corr_terms and its two readers live in ops/corr.py, which imports no
# Pallas: a server that loads its executables from the AOT cache asks for the
# pass counts without paying this module's import (1.0-1.2 s of set-up)
from .corr import (as_precision, corr_mxu_passes, corr_terms,  # noqa: F401
                   fmap2_pyramid, level_mxu_passes, lookup_blockwise_onehot,
                   mask_ragged_rows, ragged_pyramid)


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


# The scoped-VMEM limit every pallas_call here requests: the compiler's
# 16 MiB default refuses the default block plan inside the train step
# (kernel_plans.VMEM_BYTES has the figures); the static envelope is
# checked against the same number.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES)


def split_bf16_terms(x: jax.Array, n: int) -> jax.Array:
    """float32 ``x`` -> ``[n, *x.shape]`` bfloat16 planes, largest first, whose
    float32 sum is ``x`` bit for bit at ``n`` = 3 (a bfloat16-valued ``x``
    needs 1).  Each plane rounds what the planes before it left;
    ``reduce_precision`` does the rounding in float32, where no compiler
    pass may elide it as a convert pair."""
    planes, rest = [], x.astype(jnp.float32)
    for _ in range(n):
        term = jax.lax.reduce_precision(rest, exponent_bits=8,
                                        mantissa_bits=7)
        planes.append(term.astype(jnp.bfloat16))
        rest = rest - term
    return jnp.stack(planes)


def f2_terms(f1_dtype, f2_level: jax.Array, precision) -> jax.Array:
    """``[B, H2, W2, C]`` -> the ``[n, B, H2, W2, C]`` planes the kernel
    multiplies (:func:`corr_terms`): the bfloat16 terms of ``f2_level`` when
    ``f1`` is bfloat16 at ``HIGHEST``, else its one float32 plane."""
    n1, n2 = corr_terms(f1_dtype, f2_level.dtype, precision)
    if as_precision(precision) != jax.lax.Precision.HIGHEST or n1 != 1:
        return f2_level.astype(jnp.float32)[None]
    return f2_level[None] if n2 == 1 else split_bf16_terms(f2_level, n2)


def _kernel_operands(f1: jax.Array, f2_level: jax.Array, precision):
    """(f1, f2 planes, dot precision) as the kernel holds them.  A bfloat16
    stack IS the exact-terms form: ``f1`` stays bfloat16 and each dot is one
    pass.  Anything else is the float32 matmul at ``precision``."""
    if f2_level.ndim == 4:          # not split by the caller, outside its loop
        f2_level = f2_terms(f1.dtype, f2_level, precision)
    if f2_level.dtype == jnp.bfloat16:
        return f1, f2_level, jax.lax.Precision.DEFAULT
    return f1.astype(jnp.float32), f2_level, precision


def _corr_tile(f1_ref, f2_ref, corr_precision) -> jax.Array:
    """[T, Pblk] float32 correlation of the query block with the f2 block:
    one dot per term plane, summed smallest term first."""
    f1 = f1_ref[0]                                   # [T, C]
    corr = None
    for t in reversed(range(f2_ref.shape[0])):
        part = jax.lax.dot_general(
            f1, f2_ref[t, 0], (((1,), (1,)), ((), ())),   # [Pblk, C]
            precision=corr_precision,
            preferred_element_type=jnp.float32)
        corr = part if corr is None else corr + part
    return corr


#: Window rows (map rows, where a block is packed) that share one 128-lane
#: tile of the tap layout (``kernel_plans.corr_tap_tiles``), and the shifts
#: that divide by the two powers of two (the vector unit has no divide).
_ROWS_A_TILE = LANE // TAP_LANES
_TAP_SHIFT = TAP_LANES.bit_length() - 1
_ROW_SHIFT = _ROWS_A_TILE.bit_length() - 1
_LANE_SHIFT = LANE.bit_length() - 1


def _window_origin(coords_ref, level_scale: float, radius: int):
    """A query tile's windows: ``(ix0, iy0)`` int32 ``[T, 1]``, the first
    tap's column and row at this level, and ``(fx, fy)`` float32 ``[T, 1]``,
    how far the query lies past it."""
    c = coords_ref[0] * level_scale                  # [T, 2] (x, y)
    c0 = jnp.floor(c)
    i0 = c0.astype(jnp.int32) - radius
    frac = c - c0
    return (i0[:, 0:1], i0[:, 1:2]), (frac[:, 0:1], frac[:, 1:2])


def _tap_lanes(T: int):
    """int32 ``[T, 128]``: the window row (within its tile of eight) and the
    window column each lane of the tap layout holds."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (T, LANE), 1)
    return lane >> _TAP_SHIFT, lane & (TAP_LANES - 1)


def _take(x: jax.Array, lanes: jax.Array) -> jax.Array:
    """``out[t, l] = x[t, lanes[t, l]]``: a gather along the 128 lanes of
    each query's row (one ``tpu.dynamic_gather`` a vector register)."""
    return jnp.take_along_axis(x, lanes, axis=1, mode="promise_in_bounds")


def _window_taps(first_row, f1_ref, coords_ref, f2_refs, *,
                 level_scale: float, corr_scale: float, radius: int,
                 w2: int, corr_precision):
    """Shared program body, the part every visited step runs: the corr tile
    against the key rows the step was handed — consecutive map rows from
    ``first_row``, in one block or in the granule blocks ``f2_refs`` of a
    band, each ``[terms, 1, rows * w2, C]`` — and from it the ``(n+1) x
    (n+1)`` integer taps of each query's window that lie in these rows,
    SELECTED, not interpolated: ``[T, tiles * 128]`` float32, window row
    ``j`` and column ``i`` at lane ``j * 16 + i``, zero where a tap lies in
    other rows or outside the map.  Each tap is the float32 correlation
    sum itself, bit for bit: lanes are moved, nothing is multiplied.

    Queries lie in the sublanes and a block's positions in the lanes, so a
    query's window is a different set of lanes for every sublane: that is a
    lane gather (``tpu.dynamic_gather``), not a matmul.  Three moves:

    * columns: each 128-lane chunk of the tile is gathered at the query's
      columns ``ix0 + i``, the sixteen window columns repeated in every
      group of sixteen lanes.  Where a map row is stored ``w2`` >= 128 lanes
      wide a chunk is (a part of) one map row (two gathers and a select
      where a row takes 256 lanes); where it is stored 64, 32 or 16 lanes
      wide (``kernel_plans.corr_row_lanes``) a chunk is ``p`` = 2, 4 or 8
      map rows side by side and ONE gather serves them all: lane group
      ``g`` reads map row ``g % p`` of the chunk, at lanes ``(g % p) * w2 +
      (ix0 + i) % w2``;
    * pack: eight map rows share one 128-lane tile, row ``y`` keeping group
      ``y % 8`` (a select on a static lane mask: of one group a map row, of
      ``p`` groups a chunk, none where a chunk is a whole tile);
    * rows: a query's window rows ``iy0 + j`` lie in at most three such
      tiles; they are chosen per query and gathered once more, which rotates
      the groups so that window row ``j`` lands in group ``j % 8``.

    The cost goes with the 128-lane chunks of the step, and no bilinear
    weight enters here (TUNING.md has the us a step)."""
    T = f1_ref.shape[1]
    corrs = [_corr_tile(f1_ref, ref, corr_precision) for ref in f2_refs]
    rows_each = f2_refs[0].shape[2] // w2       # [T, rows_each * w2] each
    (ix0, iy0), _ = _window_origin(coords_ref, level_scale, radius)
    grp, col = _tap_lanes(T)
    zeros = jnp.zeros((T, LANE), jnp.float32)

    x = ix0 + col                               # the map column a lane wants
    chunks, p = max(1, w2 // LANE), max(1, LANE // w2)
    chunk_of = x >> _LANE_SHIFT
    src = x & (min(w2, LANE) - 1)
    keeps = grp             # the chunk, of a tile's 8 / p, a lane group keeps
    if p > 1:               # and the lanes of its map row within the chunk
        src = src | ((grp & (p - 1)) << (w2.bit_length() - 1))
        keeps = grp >> (p.bit_length() - 1)
    packed = []
    for y in range(0, rows_each * len(f2_refs), p):
        corr, row = corrs[y // rows_each], None
        for c in range(chunks):
            at = y % rows_each * w2 + c * LANE
            got = _take(corr[:, at:at + LANE], src)
            row = got if row is None else jnp.where(chunk_of == c, got, row)
        if p == _ROWS_A_TILE:                   # the chunk is a packed tile
            packed.append(row)
            continue
        if y % _ROWS_A_TILE == 0:
            packed.append(zeros)
        packed[-1] = jnp.where(keeps == y % _ROWS_A_TILE // p, row,
                               packed[-1])

    r0 = iy0 - first_row                # first window row, in the step's rows
    first_tile = r0 >> _ROW_SHIFT

    def tile_of(which):     # the packed tile each query wants, zeros past the block
        out = zeros
        for g, rows in enumerate(packed):
            out = jnp.where(which == g, rows, out)
        return out

    # window row j = 8a + jj sits in block row r0 + j: group (r0 + jj) % 8 of
    # tile first_tile + a, or of the next one once the groups wrap
    rot = (((r0 + grp) & (_ROWS_A_TILE - 1)) << _TAP_SHIFT) | col
    wraps = (r0 & (_ROWS_A_TILE - 1)) + grp >= _ROWS_A_TILE
    in_map = (x >= 0) & (x < w2)    # (columns W2 .. W2p hold zero correlation)
    tiles = corr_tap_tiles(2 * radius + 1)
    srcs = [_take(tile_of(first_tile + a), rot) for a in range(tiles + 1)]
    # the 1/sqrt(C) of the correlation, on the taps alone: the product of a
    # moved value is the moved product, and a tile has 32 x as many values
    taps = [jnp.where(in_map, jnp.where(wraps, srcs[a + 1], srcs[a])
                      * corr_scale, 0.0) for a in range(tiles)]
    return taps[0] if tiles == 1 else jnp.concatenate(taps, axis=1)


def _floordiv_lanes(lane: jax.Array, n: int) -> jax.Array:
    """``lane // n`` for lanes 0..127 as a multiply and a shift (the vector
    unit has no integer divide)."""
    mult, shift = -(-(1 << 12) // n), 12
    assert all((v * mult) >> shift == v // n for v in range(128)), n
    return (lane * mult) >> shift


def _write_windows(coords_ref, acc_ref, out_ref, *, level_scale: float,
                   radius: int):
    """Shared program body, the part a query tile runs once, on its last
    grid step: the bilinear blend of the summed taps ``acc_ref`` (the layout
    of :func:`_window_taps`) into the ``n x n`` windows, in float32 on the
    VPU, rounded to the output's dtype as it is stored — the only rounding
    after the correlation sums.  With ``t[j, i]`` the tap at window row
    ``j``, column ``i``:

        bx[j, i]  = (1-fx) * t[j, i] + fx * t[j, i+1]
        out[j, i] = (1-fy) * bx[j, i] + fy * bx[j+1, i]

    so x is blended first: four products and three adds a value, in that
    order.  The neighbours come from lane rotations (one lane for ``i+1``,
    sixteen for ``j+1``, carrying in from the next tile); a last gather
    with a static index puts ``out[j, i]`` at lane ``i * n + j``, a query's
    n*n values side by side (x-offset-major), and the row is stored once."""
    n = 2 * radius + 1
    T = acc_ref.shape[0]
    _, (fx, fy) = _window_origin(coords_ref, level_scale, radius)
    gx, gy = 1.0 - fx, 1.0 - fy
    lane = jax.lax.broadcasted_iota(jnp.int32, (T, LANE), 1)
    tiles = corr_tap_tiles(n)

    def ahead(v, k):                     # the value k lanes further on
        return pltpu.roll(v, LANE - k, axis=1)

    bx = []
    for a in range(tiles):
        t = acc_ref[:, a * LANE:(a + 1) * LANE]
        bx.append(gx * t + fx * ahead(t, 1))
    i = _floordiv_lanes(lane, n)
    j = lane - n * i
    perm = ((j & (_ROWS_A_TILE - 1)) << _TAP_SHIFT) | i
    perm = jnp.where(lane < n * n, perm, 0)
    out = None
    for a in range(tiles):
        below = ahead(bx[a], TAP_LANES)
        if a + 1 < tiles:
            below = jnp.where(lane < LANE - TAP_LANES, below,
                              ahead(bx[a + 1], TAP_LANES))
        got = _take(gy * bx[a] + fy * below, perm)
        out = got if out is None else jnp.where(j >> _ROW_SHIFT == a, got, out)
    out_ref[0] = out[:, :n * n].astype(out_ref.dtype)


def _accumulate(acc_ref, k, last, visit, taps, write):
    """The one way a lookup launch hands over its result.  Where ``visit``
    (None: every step) holds, grid step ``k`` adds its key row-block's
    ``taps()`` (float32, :func:`_window_taps`' layout) into the float32
    scratch ``acc_ref``; the step a query tile ends on (``last``), visited
    or not, calls ``write()`` once (:func:`_write_windows`)."""
    def add():
        part = taps()

        @pl.when(k == 0)
        def _():
            acc_ref[...] = part

        @pl.when(k > 0)
        def _():
            acc_ref[...] = acc_ref[...] + part

    if visit is None:
        add()
    else:
        pl.when(visit)(add)

    pl.when(last)(write)


def _sums_scratch(T: int, n: int) -> list:
    """``scratch_shapes`` of every lookup launch: :func:`_accumulate`'s
    float32 tap sums (``kernel_plans.corr_window_vmem`` prices them)."""
    return [pltpu.VMEM((T, corr_tap_tiles(n) * LANE), jnp.float32)]


def _level_body(C: int, level: int, radius: int, plan, corr_precision):
    """``(taps, write)`` of one level's launch, the two parts of the shared
    body with the level's constants bound."""
    scale = dict(level_scale=1.0 / (2.0 ** level), radius=radius)
    return (functools.partial(_window_taps, corr_scale=1.0 / (C ** 0.5),
                              w2=plan.w2p, corr_precision=corr_precision,
                              **scale),
            functools.partial(_write_windows, **scale))


def _level_kernel(f1_ref, coords_ref, f2_ref, out_ref, acc_ref, *, taps,
                  write, h2_blk):
    """One (batch, query-block, p-block) program: the k-th grid step visits
    f2 row-block k (full pass over the map)."""
    k = pl.program_id(2)
    _accumulate(acc_ref, k, k == pl.num_programs(2) - 1, None,
                lambda: taps(k * h2_blk, f1_ref, coords_ref, (f2_ref,)),
                lambda: write(coords_ref, acc_ref, out_ref))


def _band_kernel(S_ref, f1_ref, coords_ref, *refs, taps, write, granule,
                 stride):
    """Band-scheduled program: identical math to ``_level_kernel`` but the
    k-th grid step of a query tile visits the band of key rows that starts
    at row ``S[b, j*stride + k] * granule``, handed over as its granule
    blocks ``refs[:-2]`` (then the output and the scratch).  ``stride`` is
    the schedule's entries a tile (the plan's ``n_bands``), which the grid's
    third dimension (:func:`schedule_steps`) may be shorter than.  A tile
    that needs fewer bands than the grid has steps finds its last one
    repeated; a repeated index means the pipeline skips the DMA refetch and
    this body skips the compute, so only bands the tile's bilinear windows
    overlap do work."""
    *f2_refs, out_ref, acc_ref = refs
    b = pl.program_id(0)
    k = pl.program_id(2)
    at = pl.program_id(1) * stride + k
    sel = S_ref[b, at]
    prev = S_ref[b, at - jnp.minimum(k, 1)]      # step 0 has no previous
    _accumulate(acc_ref, k, k == pl.num_programs(2) - 1,
                (k == 0) | (sel != prev),
                lambda: taps(sel * granule, f1_ref, coords_ref, f2_refs),
                lambda: write(coords_ref, acc_ref, out_ref))


def _window_rows(coords: jax.Array, level_scale: float, radius: int, T: int):
    """Per (batch, query tile) the first and the last (inclusive) map row its
    windows touch at a level: int32 ``[B, Qp/T]`` each, not clipped."""
    B, Qp, _ = coords.shape
    cy = coords[..., 1] * level_scale                     # [B, Qp]
    iy0 = (jnp.floor(cy).astype(jnp.int32) - radius).reshape(B, Qp // T, T)
    return iy0.min(axis=2), iy0.max(axis=2) + 2 * radius + 1


def _band_schedule(coords: jax.Array, level_scale: float, radius: int,
                   T: int, plan) -> jax.Array:
    """Per (batch, query tile) the bands of key rows its bilinear windows
    touch, as a ``[B, Qb, K]`` schedule of band STARTS in granules: the
    first band starts on the granule of the tile's first window row, each
    further one ``R`` rows on (disjoint, in order), as many as reach its
    last window row.  Entries past the needed range repeat the last needed
    band (skip marker).  Windows wholly outside the map select nothing (no
    row matches their taps), so pointing them at row 0 is safe."""
    H2, g, n_g = plan.rows, plan.band_granule, plan.band_granules
    lo, hi = _window_rows(coords, level_scale, radius, T)
    any_rows = (hi >= 0) & (lo < H2)
    s_lo = jnp.where(any_rows, jnp.clip(lo, 0, H2 - 1) // g, 0)
    s_hi = jnp.where(any_rows, jnp.clip(hi, 0, H2 - 1) // g, 0)
    more = (s_hi - s_lo) // n_g                 # bands after the first
    ks = jnp.arange(plan.n_bands, dtype=jnp.int32)[None, None, :]
    return (s_lo[..., None]
            + n_g * jnp.minimum(ks, more[..., None])).astype(jnp.int32)


def _tile_bands(schedule: jax.Array, plan) -> jax.Array:
    """int32 ``[B, Qb]``: the bands each query tile visits under a
    ``[B, Qb, K]`` band schedule — its last entry less its first, in bands,
    plus one (entries run up from the first band and then repeat the
    last)."""
    return (schedule[..., -1] - schedule[..., 0]) // plan.band_granules + 1


def schedule_steps(schedule: jax.Array, plan) -> jax.Array:
    """int32 scalar, 1 .. ``plan.n_bands``: the key steps a query tile takes
    in the launch of this ``[B, Qb, K]`` band schedule — the bands of the
    tile that visits most.  The launch's third grid dimension: no tile needs
    a later entry of its schedule, so the steps left out are ones every tile
    would have skipped."""
    return jnp.max(_tile_bands(schedule, plan))


def _pad_queries(plan, f1: Optional[jax.Array], coords: jax.Array):
    """(f1, coords) padded to the plan's ``qp`` queries.  Coords are
    edge-padded (not zeros): padded queries' windows then stay inside the
    real queries' row range, so the window schedule of the tail block is not
    dragged down to row-block 0."""
    pad = plan.qp - coords.shape[1]
    if pad:
        if f1 is not None:
            f1 = jnp.pad(f1, ((0, 0), (0, pad), (0, 0)))
        coords = jnp.pad(coords, ((0, 0), (0, pad), (0, 0)), mode="edge")
    return f1, coords.astype(jnp.float32)


def level_schedule(coords: jax.Array, plan, level: int,
                   radius: int) -> jax.Array:
    """The ``[B, Qp/T, n_bands]`` band schedule of one banded level for
    ``coords`` [B, Q, 2], as :func:`_lookup_level` takes it."""
    if not plan.banded:
        raise ValueError(f"level {level}: a map of {plan.rows} rows is one "
                         f"block of this plan and takes no schedule")
    _, coords = _pad_queries(plan, None, coords)
    return _band_schedule(coords, 1.0 / (2.0 ** level), radius, plan.t, plan)


def level_shapes(f2_levels: Sequence[jax.Array]):
    """``[(H2, W2), ...]`` of f2 levels given as maps ``[B,H2,W2,C]``."""
    return [tuple(lvl.shape[-3:-1]) for lvl in f2_levels]


def level_plans(queries: int, grid_w: int, shapes, radius: int,
                q_blk: int = 128, p_blk_target: int = 4096) -> Tuple:
    """One block plan per pyramid level for ``queries`` queries of a grid
    ``grid_w`` wide; None where the map is pooled away to nothing (the
    kernel short-circuits those to zeros)."""
    return tuple(
        corr_level_plan(queries, h2, w2, q_blk=q_blk,
                        p_blk_target=p_blk_target, radius=radius,
                        grid_w=grid_w)
        if h2 > 0 and w2 > 0 else None for h2, w2 in shapes)


def lookup_schedules(coords: jax.Array, shapes, radius: int,
                     q_blk: int = 128, p_blk_target: int = 4096) -> Tuple:
    """Per level, the band schedule its launch runs under, or None where
    the map is one block: coords [B, H, W, 2], ``shapes`` the ``(H2, W2)``
    of each f2 level (:func:`level_shapes`).  Which levels get one is
    decided by each level's block plan (``CorrLevelPlan.banded``) and
    nowhere else: no flag selects it."""
    B, H, W, _ = coords.shape
    cf = coords.reshape(B, H * W, 2)
    return tuple(
        level_schedule(cf, plan, i, radius)
        if plan is not None and plan.banded else None
        for i, plan in enumerate(level_plans(H * W, W, shapes, radius, q_blk,
                                             p_blk_target)))


def schedule_keyblocks(schedules, batch: int, plans) -> jax.Array:
    """int32 ``[visited, possible, tiles, steps, stored, live]`` of one
    lookup of ``batch`` maps under ``plans`` (:func:`level_plans`): the
    (query tile, band) grid steps that did work, the steps a walk of every
    band of every level would take, the (query tile, level) pairs, and the
    grid steps the launches took, so that ``visited / tiles`` is the bands a
    tile visited a level (1.0: every tile's windows lay in one band) and
    ``steps / tiles`` the steps it was charged for (1.0: no launch took a
    step that did nothing); then the key positions those steps multiplied
    and selected over, per level the steps x the map rows of a step x the
    lanes a row is stored in (``stored``) and x the map's own columns
    (``live``): ``live / stored`` is the share of the MXU's and the
    selection's lanes that held a key.  (int32: eight 1080p maps are 16e6
    stored positions a lookup, 0.19e9 over a call's twelve iterations.)
    A banded level's counts are reduced from the schedule its kernel is
    given (:func:`_tile_bands`; its grid is :func:`schedule_steps` a tile);
    a level walked without one visits all the row-blocks it has, one where
    the map is one block."""
    visited = steps = stored = live = jnp.int32(0)
    possible = tiles = 0
    for plan, S in zip(plans, schedules):
        if plan is None:
            continue
        level_tiles = batch * (plan.qp // plan.t)
        tiles += level_tiles
        if S is None:
            rows = plan.h2_blk
            level_steps = level_tiles * plan.n_pblocks
            possible += level_steps
            visited = visited + level_steps
        else:
            rows = plan.band_rows
            level_steps = level_tiles * schedule_steps(S, plan)
            possible += level_tiles * plan.n_bands
            visited = visited + jnp.sum(_tile_bands(S, plan))
        steps = steps + level_steps
        stored = stored + level_steps * (rows * plan.w2p)
        live = live + level_steps * (rows * plan.w2)
    return jnp.stack([visited, jnp.int32(possible), jnp.int32(tiles), steps,
                      stored, live])


def pad_planes(f2: jax.Array, plan) -> jax.Array:
    """Term planes ``[n, B, H2, W2, C]`` of a level with the zero rows and
    lanes its launch reads (``plan``): the lanes to the width a row is
    stored in (``kernel_plans.corr_row_lanes``: a vector register or more,
    or the half, quarter or eighth of one that holds a pooled level's
    columns), the rows to what the last band (or row-block) reaches.  Zero
    keys correlate to zero: identical to zeros padding at the map's edge.
    Flattened to positions, as every launch takes them, 2, 4 or 8 narrow
    rows then lie side by side in each 128 lanes: the packed layout is this
    pad and no relayout.  A caller that looks up many times does this once
    (:class:`FusedLookup`); planes that already have the shape pass
    through."""
    rows = plan.band_rows_padded if plan.banded else plan.rows_padded
    H2, W2 = f2.shape[2:4]
    if (H2, W2) == (rows, plan.w2p):
        return f2
    return jnp.pad(f2, ((0, 0), (0, 0), (0, rows - H2), (0, plan.w2p - W2),
                        (0, 0)))


def _lookup_level(f1: jax.Array, f2_level: jax.Array, coords: jax.Array,
                  radius: int, level: int, *, q_blk: int,
                  p_blk_target: int, interpret: bool, grid_w: int,
                  shape: Optional[Tuple[int, int]] = None,
                  corr_precision=jax.lax.Precision.HIGHEST,
                  schedule: Optional[jax.Array] = None,
                  out_dtype=jnp.float32) -> jax.Array:
    """f1 [B,Q,C] (queries of a grid ``grid_w`` wide, in raster order),
    f2_level [B,H2,W2,C] (or its [n,B,H2,W2,C] term planes, :func:`f2_terms`,
    padded by :func:`pad_planes` or not: ``shape`` is the map's own ``(H2,
    W2)`` where they are), coords [B,Q,2] -> [B,Q,(2r+1)^2] in
    ``out_dtype``: the float32 sums, rounded once where the kernel writes
    them.

    ``schedule`` (:func:`level_schedule` of the same coords and plan): the
    bands of key rows each query tile visits; None walks every row-block of
    the map.  The values are the same either way, bit for bit: every tap
    lies in one band or block, rows left out added exact zeros, and the
    visited ones keep their order."""
    B, Q, C = f1.shape
    H2, W2 = shape or f2_level.shape[-3:-1]
    n = 2 * radius + 1
    if H2 == 0 or W2 == 0:
        # degenerate pyramid level (map pooled away to nothing): every window
        # is fully out of bounds -> zeros padding
        return jnp.zeros((B, Q, n * n), out_dtype)
    f1, f2, corr_precision = _kernel_operands(f1, f2_level, corr_precision)
    n_terms = f2.shape[0]

    # All padding/blocking arithmetic is the plan's (kernel_plans.py); the
    # static VMEM budget analyzer prices the very plan this call executes.
    plan = corr_level_plan(Q, H2, W2, q_blk=q_blk, p_blk_target=p_blk_target,
                           radius=radius, grid_w=grid_w)
    T, Qp, W2p = plan.t, plan.qp, plan.w2p
    f1, coords = _pad_queries(plan, f1, coords)
    if schedule is None and plan.banded:
        # the all-rows walk of a map of several row-blocks (what a banded
        # launch is held against): fixed blocks, their own row padding
        f2 = f2[:, :, :H2, :W2]
        plan = dataclasses.replace(plan, n_bands=0)
    # W2 is padded to the width a row is stored in and the rows to what the
    # last step reads; as positions, narrow rows share their 128 lanes
    f2 = pad_planes(f2, plan).reshape(n_terms, B, -1, C)
    taps, write = _level_body(C, level, radius, plan, corr_precision)
    out_shape = jax.ShapeDtypeStruct((B, Qp, n * n), out_dtype)
    acc = _sums_scratch(T, n)

    if schedule is not None:
        K, g, n_g = plan.n_bands, plan.band_granule, plan.band_granules
        if schedule.shape != (B, Qp // T, K):
            raise ValueError(f"level {level}: schedule {schedule.shape} is "
                             f"not this plan's {(B, Qp // T, K)}")
        # the key steps a tile: what the tile that needs most bands needs,
        # known on the device alone (one on served flow, K at most)
        grid = (B, Qp // T, schedule_steps(schedule, plan))
        # SMEM pads an array's last two dims to (8, 128) words: as [B, Qb, K]
        # eight pairs' schedule of 254 tiles x 3 blocks took the whole 1 MiB
        # (the chip compiler's refusal at 1080x1920, batch 8: PR 26), as
        # [B, Qb*K] it takes 73 KB
        S = schedule.reshape(B, -1)
        # the band as its n_g granule blocks: the same planes handed over
        # n_g times under plain blocked indexing, block S + i of g rows.
        # Tiles that name the same band (the next tile of a query row, a
        # repeated entry) refetch nothing.  K stays the schedule's stride
        # whatever the grid's third dimension is.
        def granule(i, b, j, k, S):
            return 0, b, S[b, j * K + k] + i, 0

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, T, C), lambda b, j, k, S: (b, j, 0)),
                pl.BlockSpec((1, T, 2), lambda b, j, k, S: (b, j, 0)),
                *[pl.BlockSpec((n_terms, 1, g * W2p, C),
                               functools.partial(granule, i))
                  for i in range(n_g)],
            ],
            out_specs=pl.BlockSpec((1, T, n * n),
                                   lambda b, j, k, S: (b, j, 0)),
            scratch_shapes=acc,
        )
        out = pl.pallas_call(
            functools.partial(_band_kernel, taps=taps, write=write,
                              granule=g, stride=K),
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
            compiler_params=_COMPILER_PARAMS,
        )(S, f1, coords, *[f2] * n_g)
    else:
        f2_block = (n_terms, 1, plan.h2_blk * W2p, C)   # one row-block
        out = pl.pallas_call(
            functools.partial(_level_kernel, taps=taps, write=write,
                              h2_blk=plan.h2_blk),
            grid=(B, Qp // T, plan.n_pblocks),
            in_specs=[
                pl.BlockSpec((1, T, C), lambda b, j, k: (b, j, 0)),
                pl.BlockSpec((1, T, 2), lambda b, j, k: (b, j, 0)),
                pl.BlockSpec(f2_block, lambda b, j, k: (0, b, k, 0)),
            ],
            out_specs=pl.BlockSpec((1, T, n * n), lambda b, j, k: (b, j, 0)),
            out_shape=out_shape,
            scratch_shapes=acc,
            interpret=interpret,
            compiler_params=_COMPILER_PARAMS,
        )(f1, coords, f2)
    return out[:, :Q] if Qp != Q else out


# Dtype audit (raftlint R4 / contracts): the maps enter in the dtype the
# encoder produced (bfloat16 or float32 — corr_terms reads it); coords, the
# corr tile (preferred_element_type), every scale factor (corr_scale,
# level_scale: weak-typed Python floats, so nothing promotes to f64 even
# under jax_enable_x64 on the CPU backend) and the accumulator are float32;
# the output is the float32 sum rounded once to the dtype its consumer
# states (``out_dtype``: the update block's compute dtype, or float32 for a
# caller that goes on adding, as the ring lookup does).  The contract pins
# that intent.
@contract(fmap1="f32|bf16[B,H,W,C]", coords="f32[B,H,W,2]",
          _returns="f32|bf16[B,H,W,N]")
def _fused_lookup_impl(fmap1: jax.Array, f2_levels: Sequence[jax.Array],
                       coords: jax.Array, radius: int,
                       q_blk: int = 128, p_blk_target: int = 4096,
                       interpret: Optional[bool] = None,
                       corr_precision=jax.lax.Precision.HIGHEST,
                       schedules: Optional[Tuple] = None,
                       out_dtype=jnp.float32,
                       shapes: Optional[Sequence] = None) -> jax.Array:
    """``f2_levels``: the maps, or their term planes, padded
    (:func:`pad_planes`) or not; ``shapes`` the maps' own ``(H2, W2)`` where
    the planes are padded."""
    B, H, W, C = fmap1.shape
    Q = H * W
    interp = _use_interpret() if interpret is None else interpret
    if shapes is None:
        shapes = level_shapes(f2_levels)
    if schedules is None:       # a caller that does not count key blocks
        schedules = lookup_schedules(coords, shapes, radius, q_blk=q_blk,
                                     p_blk_target=p_blk_target)
    f1 = fmap1.reshape(B, Q, C)
    cf = coords.reshape(B, Q, 2)
    outs = []
    for i, (f2l, shape, sched) in enumerate(zip(f2_levels, shapes,
                                                schedules)):
        # a scope per pyramid level (.../raft/corr_lookup/l<i>/...): each
        # level is one kernel launch of its own size, and a trace reader can
        # then tell them apart through the engine's instruction -> stage
        # map.  The compiler names a kernel's instruction after the
        # INNERMOST scope (%corr_lookup.<n>, which the benchmark's roofline
        # reader finds it by), so the scope ends as the enclosing one does.
        with stage(f"l{i}/corr_lookup"):
            outs.append(_lookup_level(
                f1, f2l, cf, radius, i, q_blk=q_blk,
                p_blk_target=p_blk_target, interpret=interp, grid_w=W,
                shape=shape, corr_precision=corr_precision, schedule=sched,
                out_dtype=out_dtype))
    return jnp.concatenate(outs, axis=-1).reshape(B, H, W, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 9))
def fused_lookup(fmap1: jax.Array, f2_levels: Tuple[jax.Array, ...],
                 coords: jax.Array, radius: int,
                 corr_precision=jax.lax.Precision.HIGHEST,
                 q_blk: int = 128, p_blk_target: int = 4096,
                 f2_planes: Optional[Tuple[jax.Array, ...]] = None,
                 schedules: Optional[Tuple] = None,
                 out_dtype=jnp.float32) -> jax.Array:
    """Pallas-fused correlation lookup.

    fmap1 [B,H,W,C], f2_levels tuple of [B,H/2^i,W/2^i,C], coords [B,H,W,2]
    -> [B, H, W, L*(2r+1)^2], matching ``ops.corr.lookup_dense`` exactly.

    ``out_dtype``: the dtype the caller consumes the windows in.  The
    kernel sums in float32 and rounds once, as it writes: bit for bit
    ``fused_lookup(...).astype(out_dtype)`` of the float32 result, without
    the float32 array.  A caller that adds results up keeps the default.

    ``f2_planes`` (optional): ``f2_levels`` as the kernel multiplies them
    (:func:`f2_terms` of each level, padded by :func:`pad_planes`), built
    once by a caller that looks up many times (:func:`make_fused_lookup`).
    The forward then reads only these; ``f2_levels`` stay the differentiable
    maps the backward uses, and give the maps' shapes.

    ``schedules`` (optional): :func:`lookup_schedules` of these coords, from
    a caller that also counts them (:class:`FusedLookup`); None computes the
    same here.  Either way the level's plan decides
    (``kernel_plans.CorrLevelPlan.banded``) whether a launch takes the map
    as one block or fetches, for each query tile, the band of key rows its
    windows touch.
    """
    return _fused_lookup_impl(
        fmap1, f2_levels if f2_planes is None else f2_planes, coords, radius,
        q_blk=q_blk, p_blk_target=p_blk_target,
        corr_precision=corr_precision, schedules=schedules,
        out_dtype=out_dtype, shapes=level_shapes(f2_levels))


def _fused_lookup_fwd(fmap1, f2_levels, coords, radius, corr_precision,
                      q_blk, p_blk_target, f2_planes, schedules, out_dtype):
    return fused_lookup(fmap1, f2_levels, coords, radius, corr_precision,
                        q_blk, p_blk_target, f2_planes, schedules,
                        out_dtype), (fmap1, f2_levels, coords, schedules)


def _twin_vjp(fmap1, f2_levels, coords, radius, corr_precision, g):
    """Cotangents of (fmap1, f2_levels, coords) through the matmul-only XLA
    twin (no gathers in the backward), on float32 maps whatever dtype the
    forward multiplied: the configured corr precision applies to the
    backward matmuls too — 'highest' must not silently degrade to bf16 MXU
    inputs in training.  ``g`` arrives in the forward's ``out_dtype`` and is
    raised to float32 like the primals; each cotangent comes back in its
    primal's dtype, which is where an ``astype(float32)`` before the lookup
    would put it."""
    primals = (fmap1, tuple(f2_levels), coords)
    _, vjp = jax.vjp(
        lambda a, b, c: lookup_blockwise_onehot(a, tuple(b), c, radius,
                                                precision=corr_precision),
        *jax.tree.map(lambda x: x.astype(jnp.float32), primals))
    return jax.tree.map(lambda ct, x: ct.astype(x.dtype),
                        vjp(g.astype(jnp.float32)), primals)


def _fused_lookup_bwd(radius, corr_precision, q_blk, p_blk_target,
                      out_dtype, residuals, g):
    # the planes are a function of f2_levels that the forward precomputed:
    # their cotangent is zero (None), f2_levels carry the gradient; the
    # schedules are integer metadata (float0, as the ragged sizes are)
    *primals, schedules = residuals
    return (*_twin_vjp(*primals, radius, corr_precision, g), None,
            jax.tree.map(lambda s: np.zeros(s.shape, jax.dtypes.float0),
                         schedules))


fused_lookup.defvjp(_fused_lookup_fwd, _fused_lookup_bwd)


class FusedLookup:
    """The per-iteration lookup of models/raft.py: ``lookup(coords)``.

    Pools the fmap2 pyramid once (in float32), splits it once into the
    planes the kernel multiplies (:func:`f2_terms`: level 0 is ``fmap2``
    itself in the dtype it came in) and pads them once with the zero rows
    and lanes the launches read (:func:`pad_planes`: a pad inside the
    lookup is a copy of every plane in every iteration); each GRU iteration
    then runs the fused
    kernel — recomputing correlation tiles on the MXU instead of re-reading
    a ~254 MB volume from HBM (or, at resolutions where that volume could
    not even be allocated, running where the dense path cannot).

    A caller that counts key blocks asks for the iteration's
    :meth:`schedules` itself, hands them back to the call, and reduces the
    same arrays with :meth:`keyblocks`: what is counted is what the kernels
    were given.
    """

    def __init__(self, fmap1: jax.Array, fmap2: jax.Array, num_levels: int,
                 radius: int, corr_precision="highest", q_blk: int = 128,
                 p_blk_target: int = 4096, out_dtype=jnp.float32):
        self.radius, self.prec = radius, as_precision(corr_precision)
        self.opts = (q_blk, p_blk_target)
        self.out_dtype = out_dtype      # what the caller consumes windows in
        self.plan_args = dict(q_blk=q_blk, p_blk_target=p_blk_target)
        self.f2_levels = tuple(fmap2_pyramid(fmap2.astype(jnp.float32),
                                             num_levels))
        B, H, W, _ = fmap1.shape
        self.plans = level_plans(H * W, W, level_shapes(self.f2_levels),
                                 radius, **self.plan_args)
        planes = (f2_terms(fmap1.dtype, lvl, self.prec)
                  for lvl in (fmap2,) + self.f2_levels[1:])
        self.f2_planes = tuple(f2 if plan is None else pad_planes(f2, plan)
                               for f2, plan in zip(planes, self.plans))
        # f1 as the kernel holds it, cast here once and not in every lookup
        self.fmap1 = _kernel_operands(fmap1, self.f2_planes[0], self.prec)[0]

    def schedules(self, coords: jax.Array) -> Tuple:
        return lookup_schedules(coords, level_shapes(self.f2_levels),
                                self.radius, **self.plan_args)

    def keyblocks(self, schedules: Tuple) -> jax.Array:
        return schedule_keyblocks(schedules, self.fmap1.shape[0], self.plans)

    def __call__(self, coords: jax.Array,
                 schedules: Optional[Tuple] = None) -> jax.Array:
        return fused_lookup(self.fmap1, self.f2_levels, coords, self.radius,
                            self.prec, *self.opts, self.f2_planes, schedules,
                            self.out_dtype)


@contract(fmap1="*[B,H,W,C]", fmap2="*[B,H2,W2,C]")
def make_fused_lookup(fmap1: jax.Array, fmap2: jax.Array, num_levels: int,
                      radius: int, corr_precision="highest",
                      q_blk: int = 128, p_blk_target: int = 4096,
                      out_dtype=jnp.float32) -> FusedLookup:
    """Build the per-iteration lookup closure used by models/raft.py, whose
    update block consumes the windows in ``out_dtype``."""
    return FusedLookup(fmap1, fmap2, num_levels, radius, corr_precision,
                       q_blk, p_blk_target, out_dtype)


# ---------------------------------------------------------------------------
# Ragged fused lookup: one executable for every declared resolution.
#
# Mixed-resolution items are corner-anchored crops inside one shared
# [B, Hm, Wm] max box (sizes[b] = the live (h, w) extents at the query grid).
# The query/feature streams flatten to [1, B*Qp, C] / [1, B*H2p*W2p, C] and
# ONE page-scheduled grid walks them: the k-th step of query block j visits
# the absolute f2 page S[j, k] — item base page + the relative row-block its
# live bilinear windows overlap — so the kernel never iterates a dense
# [B, H, W] box and dead tails cost neither DMA nor compute (a repeated
# schedule entry skips both, exactly like the dense kernel's scheduled levels).
# Per-level masking (ops.corr.ragged_pyramid) makes every out-of-crop feature
# row/column zero, so out-of-crop one-hot matches contribute 0 — identical to
# each crop's own zeros-padding lookup — and the differentiable XLA twin is
# simply ``lookup_blockwise_onehot`` over the masked max-box streams.
# ---------------------------------------------------------------------------


def _ragged_window_kernel(S_ref, f1_ref, coords_ref, f2_ref, out_ref,
                          acc_ref, *, taps, write, n_pb, h2_blk):
    """Page-scheduled program over the flattened query stream: grid
    ``(B*Qp/T, K)``; step k of query block j visits absolute f2 page
    ``S[j, k]`` (= item * n_pb + relative row-block).  The body needs the
    row offset *within the item's plane*, recovered as ``sel % n_pb`` —
    valid because relative entries never reach ``n_pb``.  A repeated
    schedule entry skips DMA refetch and compute."""
    j = pl.program_id(0)
    k = pl.program_id(1)
    sel = S_ref[j, k]
    prev = S_ref[j, jnp.maximum(k - 1, 0)]
    _accumulate(acc_ref, k, k == pl.num_programs(1) - 1,
                (k == 0) | (sel != prev),
                lambda: taps((sel % n_pb) * h2_blk, f1_ref, coords_ref,
                             (f2_ref,)),
                lambda: write(coords_ref, acc_ref, out_ref))


def _ragged_schedule(coords: jax.Array, live: jax.Array, rows_crop: jax.Array,
                     level_scale: float, radius: int, T: int, h2_blk: int,
                     K: int, n_pb: int) -> jax.Array:
    """[B, Qp, 2] coords + [B, Qp] live mask + [B] per-item live row counts
    (this level) -> [B*Qp/T, K] absolute page schedule.  Ranges are computed
    over LIVE queries only and clipped to the item's live rows — dead queries
    and dead pages contribute exact zeros whichever page is visited, so an
    all-dead block parks on its item's page 0."""
    B, Qp, _ = coords.shape
    n = 2 * radius + 1
    big = jnp.int32(2 ** 30)
    cy = coords[..., 1] * level_scale                      # [B, Qp]
    iy0 = jnp.floor(cy).astype(jnp.int32) - radius
    iyb = iy0.reshape(B, Qp // T, T)
    lvb = live.reshape(B, Qp // T, T)
    lo = jnp.where(lvb, iyb, big).min(axis=2)              # [B, Jb]
    hi = jnp.where(lvb, iyb, -big).max(axis=2) + n         # inclusive last row
    rc = rows_crop.astype(jnp.int32)[:, None]              # [B, 1]
    any_rows = lvb.any(axis=2) & (hi >= 0) & (lo < rc) & (rc > 0)
    b_lo = jnp.where(any_rows, jnp.clip(lo, 0, rc - 1) // h2_blk, 0)
    b_hi = jnp.where(any_rows, jnp.clip(hi, 0, rc - 1) // h2_blk, 0)
    ks = jnp.arange(K, dtype=jnp.int32)[None, None, :]
    rel = b_lo[..., None] + jnp.minimum(ks, (b_hi - b_lo)[..., None])
    item = jnp.arange(B, dtype=jnp.int32)[:, None, None] * n_pb
    return (item + rel).reshape(B * (Qp // T), K).astype(jnp.int32)


def _ragged_lookup_level(f1: jax.Array, f2_level: jax.Array,
                         coords: jax.Array, live: jax.Array,
                         rows_crop: jax.Array, radius: int, level: int, *,
                         q_blk: int, p_blk_target: int, interpret: bool,
                         grid_w: int,
                         corr_precision=jax.lax.Precision.HIGHEST,
                         out_dtype=jnp.float32) -> jax.Array:
    """f1 [B,Q,C] (dead rows zero), f2_level [B,H2,W2,C] (pre-masked; or its
    [n,B,H2,W2,C] term planes), coords [B,Q,2], live [B,Q] bool, rows_crop
    [B] int32 live rows at this level -> [B,Q,(2r+1)^2] in ``out_dtype``."""
    B, Q, C = f1.shape
    H2, W2 = f2_level.shape[-3:-1]
    n = 2 * radius + 1
    if H2 == 0 or W2 == 0:
        return jnp.zeros((B, Q, n * n), out_dtype)
    f1, f2, corr_precision = _kernel_operands(f1, f2_level, corr_precision)
    n_terms = f2.shape[0]

    # the dense path's plan (kernel_plans.py), of which this launch reads
    # the fixed row-blocks: they are its pages
    plan = corr_level_plan(Q, H2, W2, q_blk=q_blk, p_blk_target=p_blk_target,
                           radius=radius, grid_w=grid_w)
    T, Qp = plan.t, plan.qp
    if Qp != Q:
        f1 = jnp.pad(f1, ((0, 0), (0, Qp - Q), (0, 0)))
        # edge-pad coords (window schedule of the tail block stays put);
        # padded queries are DEAD, so their output is exact zero regardless
        coords = jnp.pad(coords, ((0, 0), (0, Qp - Q), (0, 0)), mode="edge")
        live = jnp.pad(live, ((0, 0), (0, Qp - Q)))
    W2p, h2_blk = plan.w2p, plan.h2_blk
    n_pb = plan.n_pblocks
    H2p = plan.rows_padded
    if H2p != H2 or W2p != W2:
        f2 = jnp.pad(f2, ((0, 0), (0, 0), (0, H2p - H2), (0, W2p - W2),
                          (0, 0)))

    taps, write = _level_body(C, level, radius, plan, corr_precision)

    # flatten to per-item-page streams: query block j serves item j // (Qp/T)
    # (Qp is uniform across items, so blocks never straddle an item), and
    # item b's plane occupies absolute pages [b*n_pb, (b+1)*n_pb).
    f1s = f1.reshape(1, B * Qp, C)
    cs = coords.astype(jnp.float32).reshape(1, B * Qp, 2)
    f2s = f2.reshape(n_terms, 1, B * H2p * W2p, C)
    grid = (B * Qp // T, n_pb)
    S = _ragged_schedule(coords.astype(jnp.float32), live, rows_crop,
                         1.0 / (2.0 ** level), radius, T, h2_blk,
                         grid[1], n_pb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, T, C), lambda j, k, S: (0, j, 0)),
            pl.BlockSpec((1, T, 2), lambda j, k, S: (0, j, 0)),
            pl.BlockSpec((n_terms, 1, h2_blk * W2p, C),
                         lambda j, k, S: (0, 0, S[j, k], 0)),
        ],
        out_specs=pl.BlockSpec((1, T, n * n), lambda j, k, S: (0, j, 0)),
        scratch_shapes=_sums_scratch(T, n),
    )
    out = pl.pallas_call(
        functools.partial(_ragged_window_kernel, taps=taps, write=write,
                          n_pb=n_pb, h2_blk=h2_blk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, B * Qp, n * n), out_dtype),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(S, f1s, cs, f2s)
    out = out.reshape(B, Qp, n * n)
    return out[:, :Q] if Qp != Q else out


@contract(fmap1="f32|bf16[B,H,W,C]", coords="f32[B,H,W,2]",
          sizes8="i32[B,2]", _returns="f32|bf16[B,H,W,N]")
def _ragged_fused_lookup_impl(fmap1: jax.Array, f2_levels: Sequence[jax.Array],
                              coords: jax.Array, sizes8: jax.Array,
                              radius: int, q_blk: int = 128,
                              p_blk_target: int = 4096,
                              interpret: Optional[bool] = None,
                              corr_precision=jax.lax.Precision.HIGHEST,
                              out_dtype=jnp.float32) -> jax.Array:
    B, H, W, C = fmap1.shape
    Q = H * W
    interp = _use_interpret() if interpret is None else interpret
    f1 = fmap1.reshape(B, Q, C)
    cf = coords.reshape(B, Q, 2)
    sizes8 = sizes8.astype(jnp.int32)
    iy = jax.lax.broadcasted_iota(jnp.int32, (B, H, W), 1)
    ix = jax.lax.broadcasted_iota(jnp.int32, (B, H, W), 2)
    live = ((iy < sizes8[:, 0, None, None])
            & (ix < sizes8[:, 1, None, None])).reshape(B, Q)
    rows = sizes8[:, 0]
    outs = []
    for i, f2l in enumerate(f2_levels):
        with stage(f"l{i}/corr_lookup"):      # as in _fused_lookup_impl
            outs.append(_ragged_lookup_level(
                f1, f2l, cf, live, rows // (2 ** i), radius, i, q_blk=q_blk,
                p_blk_target=p_blk_target, interpret=interp, grid_w=W,
                corr_precision=corr_precision, out_dtype=out_dtype))
    return jnp.concatenate(outs, axis=-1).reshape(B, H, W, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 9))
def ragged_fused_lookup(fmap1: jax.Array, f2_levels: Tuple[jax.Array, ...],
                        coords: jax.Array, sizes8: jax.Array, radius: int,
                        corr_precision=jax.lax.Precision.HIGHEST,
                        q_blk: int = 128, p_blk_target: int = 4096,
                        f2_planes: Optional[Tuple[jax.Array, ...]] = None,
                        out_dtype=jnp.float32) -> jax.Array:
    """Ragged Pallas-fused correlation lookup.

    fmap1 [B,Hm,Wm,C] with dead regions zeroed (:func:`mask_ragged_rows`),
    f2_levels the masked :func:`ragged_pyramid` of the max box, coords
    [B,Hm,Wm,2], sizes8 [B,2] int32 live (h, w) per item at the query grid
    -> [B, Hm, Wm, L*(2r+1)^2].  Restricted to item b's live crop the output
    equals ``fused_lookup`` run standalone on that crop; dead queries are
    exact zeros.  ``sizes8`` is a regular (traced) argument so ONE
    executable serves every declared resolution — it carries a float0
    cotangent (integer metadata has no gradient).  ``f2_planes`` and
    ``out_dtype`` as in :func:`fused_lookup`."""
    return _ragged_fused_lookup_impl(
        fmap1, f2_levels if f2_planes is None else f2_planes, coords, sizes8,
        radius, q_blk=q_blk, p_blk_target=p_blk_target,
        corr_precision=corr_precision, out_dtype=out_dtype)


def _ragged_fused_lookup_fwd(fmap1, f2_levels, coords, sizes8, radius,
                             corr_precision, q_blk, p_blk_target, f2_planes,
                             out_dtype):
    return ragged_fused_lookup(fmap1, f2_levels, coords, sizes8, radius,
                               corr_precision, q_blk, p_blk_target,
                               f2_planes, out_dtype), (
        fmap1, f2_levels, coords, sizes8)


def _ragged_fused_lookup_bwd(radius, corr_precision, q_blk, p_blk_target,
                             out_dtype, residuals, g):
    # gradients via the same matmul-only XLA twin as the dense kernel: the
    # masked max-box streams make lookup_blockwise_onehot the exact ragged
    # reference, so its vjp is the exact ragged backward (dead-region
    # gradients die at the upstream mask).
    fmap1, f2_levels, coords, sizes8 = residuals
    da, db, dc = _twin_vjp(fmap1, f2_levels, coords, radius, corr_precision,
                           g)
    return da, db, dc, np.zeros(sizes8.shape, jax.dtypes.float0), None


ragged_fused_lookup.defvjp(_ragged_fused_lookup_fwd, _ragged_fused_lookup_bwd)


@contract(fmap1="*[B,H,W,C]", fmap2="*[B,H,W,C]", sizes8="i32[B,2]")
def make_ragged_fused_lookup(fmap1: jax.Array, fmap2: jax.Array,
                             sizes8: jax.Array, num_levels: int, radius: int,
                             corr_precision="highest", q_blk: int = 128,
                             p_blk_target: int = 4096,
                             out_dtype=jnp.float32):
    """Ragged twin of :func:`make_fused_lookup` for mixed-resolution batches
    sharing one max box: masks frame-1 features and builds the re-masked
    pyramid and its kernel planes once, then every GRU iteration runs the
    page-scheduled ragged kernel (page scheduling IS the key-block
    schedule).
    """
    prec = as_precision(corr_precision)
    f2_levels = tuple(ragged_pyramid(fmap2.astype(jnp.float32), sizes8,
                                     num_levels))
    fmap1 = mask_ragged_rows(fmap1, sizes8)
    f2_planes = tuple(
        f2_terms(fmap1.dtype, lvl, prec)
        for lvl in (mask_ragged_rows(fmap2, sizes8),) + f2_levels[1:])
    # f1 as the kernel holds it, cast here once and not in every lookup
    fmap1 = _kernel_operands(fmap1, f2_planes[0], prec)[0]

    def lookup(coords: jax.Array) -> jax.Array:
        return ragged_fused_lookup(fmap1, f2_levels, coords, sizes8, radius,
                                   prec, q_blk, p_blk_target, f2_planes,
                                   out_dtype)

    return lookup
