"""Parity tests for the fused Pallas correlation kernel (ops/corr_pallas.py)
against the dense XLA oracle (ops/corr.py) — the kernel runs in Pallas
interpret mode on CPU so the exact kernel code is exercised (SURVEY.md §4:
multi-device/TPU paths must be testable on the CPU fake backend)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.ops.corr import (build_pyramid, dense_corr, fmap2_pyramid,
                               lookup_dense, lookup_ondemand)
from raft_tpu.ops.corr_pallas import fused_lookup, make_fused_lookup


def _every_level_scheduled(coords, f2_levels, radius, q_blk, p_blk_target):
    """A band schedule for every level that can take one: the levels of more
    rows than a band at this ``p_blk_target`` (a level of one block has no
    band: ``kernel_plans.CorrLevelPlan.banded``), built level by level from
    each level's own plan."""
    from raft_tpu.kernel_plans import corr_level_plan
    from raft_tpu.ops.corr_pallas import level_schedule

    B, H, W, _ = coords.shape
    cf = coords.reshape(B, H * W, 2)
    out = []
    for i, lvl in enumerate(f2_levels):
        h2, w2 = lvl.shape[-3:-1]
        plan = corr_level_plan(H * W, h2, w2, q_blk=q_blk,
                               p_blk_target=p_blk_target, radius=radius,
                               grid_w=W)
        out.append(level_schedule(cf, plan, i, radius) if plan.banded
                   else None)
    assert out[0] is not None
    return tuple(out)


HIGHEST, DEFAULT = jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT
BF16, F32 = jnp.bfloat16, jnp.float32


def _assert_same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, (a.dtype, b.dtype)
    bits = np.uint16 if a.dtype.itemsize == 2 else np.uint32
    np.testing.assert_array_equal(a.view(bits), b.view(bits))


def _assert_rounded_once(written_bf16, written_f32):
    """The launch written in bfloat16 against the float32 launch rounded.
    The kernel rounds the float32 blend once as it stores it, and on the
    chip the two are equal bit for bit (``chip_smoke.py``'s kernels phase
    asserts it at both served grids; Mosaic on a v5e has no fused
    multiply-add to contract).  Here the two launches are two XLA-CPU
    programs in interpret mode, and the CPU backend contracts the blend's
    multiplies and adds into FMAs in one and not in the other: the float32
    value moves by a rounding of one product, and where it then falls on
    the other side of a bfloat16 rounding tie (or, where the blend's terms
    cancel, is itself that small) the two round apart.  So: equal, but for
    at most 1e-4 of the values, and those are a correct rounding of a
    float32 value within four float32 ulp (of the largest window value) of
    the float32 launch's.  (Until PR 32 the last float32 operation before
    the rounding was a plain add of two windows, which nothing contracts,
    and the pin was exact here too.)"""
    got = np.asarray(written_bf16)
    f32 = np.asarray(written_f32, np.float32)
    want = np.asarray(jnp.asarray(f32).astype(BF16))
    assert got.dtype == want.dtype and got.shape == want.shape
    apart = got.view(np.uint16) != want.view(np.uint16)
    assert apart.mean() <= 1e-4, (int(apart.sum()), apart.size)
    if apart.any():
        half_ulp = np.abs(f32[apart]) * 2.0 ** -8     # bfloat16's, at most
        room = 4 * np.spacing(np.abs(f32).max())
        err = np.abs(got[apart].astype(np.float32) - f32[apart])
        assert (err <= half_ulp + room).all(), (err.max(), room)


def _random_case(key, B, H, W, C, dtype=jnp.float32, coord_span=None):
    k1, k2, k3 = jax.random.split(key, 3)
    fmap1 = jax.random.normal(k1, (B, H, W, C), dtype)
    fmap2 = jax.random.normal(k2, (B, H, W, C), dtype)
    span = coord_span if coord_span is not None else (max(H, W) * 1.25)
    coords = jax.random.uniform(k3, (B, H, W, 2), minval=-0.25 * span,
                                maxval=span)
    return fmap1, fmap2, coords


@pytest.mark.parametrize("B,H,W,C,levels,radius", [
    (1, 16, 24, 32, 4, 4),     # full-model shape family (r=4, 4 levels)
    (2, 12, 16, 16, 3, 3),     # small-model family (r=3), batch 2
    (1, 10, 14, 8, 2, 2),      # odd sizes, H2 not multiple of block
    (1, 8, 8, 8, 1, 1),        # single level, tiny
    # widths that are no multiple of 128 lanes at any level
    (1, 24, 40, 32, 4, 4),     # 320 px: 40, 20, 10, 5
    (2, 46, 62, 16, 4, 4),     # the 368x496 training crop: 62, 31, 15, 7
    (1, 12, 100, 8, 3, 3),     # 800 px: 100, 50, 25
])
def test_matches_dense_oracle(B, H, W, C, levels, radius):
    fmap1, fmap2, coords = _random_case(jax.random.PRNGKey(0), B, H, W, C)
    pyramid = build_pyramid(fmap1, fmap2, levels)
    want = lookup_dense(pyramid, coords, radius)
    f2_levels = tuple(fmap2_pyramid(fmap2, levels))
    got = fused_lookup(fmap1, f2_levels, coords, radius)
    assert got.shape == want.shape == (B, H, W, levels * (2 * radius + 1) ** 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------ select exactly, blend once (PR 32)
#
# A visited key row-block hands over the (n+1) x (n+1) integer taps of each
# window, gathered along the lanes of the float32 correlation tile: each tap
# IS the float32 correlation sum.  The bilinear blend runs once a query
# tile, on its last grid step.

def _one_level(fmap1, fmap2, coords, radius, *, q_blk=64, p_blk_target=256,
               scheduled=False, out_dtype=jnp.float32):
    """One launch of level 0 and the plan it ran under."""
    from raft_tpu.kernel_plans import corr_level_plan
    from raft_tpu.ops.corr_pallas import _lookup_level, level_schedule

    B, H, W, C = fmap1.shape
    cf = coords.reshape(B, H * W, 2)
    plan = corr_level_plan(H * W, H, W, q_blk=q_blk,
                           p_blk_target=p_blk_target, radius=radius,
                           grid_w=W)
    sched = level_schedule(cf, plan, 0, radius) if scheduled else None
    got = _lookup_level(fmap1.reshape(B, H * W, C), fmap2, cf, radius, 0,
                        q_blk=q_blk, p_blk_target=p_blk_target, grid_w=W,
                        interpret=True, schedule=sched, out_dtype=out_dtype)
    return got, plan


@pytest.mark.parametrize("scheduled", [False, True],
                         ids=["all-blocks", "scheduled"])
@pytest.mark.parametrize("radius", [3, 4])
def test_integer_coordinates_give_the_correlation_sums_bit_for_bit(
        radius, scheduled):
    """At ``fx = fy = 0`` the blend multiplies each tap by 1 and its
    neighbours by 0: the float32 output is the float32 correlation volume's
    own values (the tile's dot at HIGHEST, scaled), bit for bit — the
    selection added nothing and rounded nothing — with zeros where a window
    leaves the map, over several key row-blocks (windows that straddle two
    and three packed tiles of eight rows among them)."""
    B, H, W, C = 1, 40, 20, 16
    # fixed-point maps (ten bits): every product and every partial sum of
    # a correlation is exact in float32 whatever the order, so the oracle
    # below is the kernel's tile bit for bit — and a sum carries up to 22
    # significant bits: a selection that rounded to bfloat16 anywhere, or
    # kept two bfloat16 terms of three, would show
    k1, k2 = jax.random.split(jax.random.PRNGKey(60 + radius))
    i1 = np.asarray(jax.random.randint(k1, (B, H, W, C), -511, 512))
    i2 = np.asarray(jax.random.randint(k2, (B, H, W, C), -511, 512))
    fmap1 = jnp.asarray(i1 / 512.0, F32)
    fmap2 = jnp.asarray(i2 / 512.0, F32)
    xs = jnp.linspace(-6, W + 6, W).round()
    ys = jnp.linspace(-6, H + 6, H).round()
    coords = jnp.stack(jnp.meshgrid(xs, ys, indexing="xy"), -1)[None]
    # (rows of 20 columns are stored 32 lanes wide, four to a 128-lane row)
    got, plan = _one_level(fmap1, fmap2, coords, radius, p_blk_target=512,
                           scheduled=scheduled)
    assert plan.h2_blk == 16 and plan.n_pblocks == 3 and plan.pack == 4
    assert (plan.band_rows, plan.n_bands) == (16, 3)
    # the volume in integers, then the scale (a power of two at C = 16)
    exact = i1.reshape(H * W, C).astype(np.int64) @ i2.reshape(H * W, C).T
    assert np.abs(exact).max() < 2 ** 24
    vol = (exact.astype(np.float32) * np.float32(2.0 ** -18 / C ** 0.5)
           ).reshape(H * W, H, W)
    lo_plane = vol - vol.astype(BF16).astype(np.float32)
    lo_plane -= lo_plane.astype(BF16).astype(np.float32)
    assert (lo_plane != 0).mean() > 0.25      # the last eight bits matter
    n = 2 * radius + 1
    cx = np.asarray(coords[0, ..., 0], np.int64).ravel() - radius
    cy = np.asarray(coords[0, ..., 1], np.int64).ravel() - radius
    want = np.zeros((H * W, n, n), np.float32)        # x-offset-major
    for i in range(n):
        for j in range(n):
            x, y = cx + i, cy + j
            ok = (x >= 0) & (x < W) & (y >= 0) & (y < H)
            q = np.nonzero(ok)[0]
            want[q, i, j] = vol[q, y[q], x[q]]
    assert np.count_nonzero(want) > want.size // 4
    assert (want == 0).any()
    _assert_same_bits(np.asarray(got)[0], want.reshape(H * W, n * n))


@pytest.mark.parametrize("radius", [3, 4])
def test_subpixel_windows_within_two_ulp_of_the_gather_lookup(radius):
    """Random subpixel coordinates, windows straddling two key row-blocks
    and the map's edge: against ``lookup_dense`` (the gather form) on the
    SAME float32 volume the kernel's tiles hold, the output is within 2
    float32 ulp of max |corr| — the blend is the only rounding."""
    B, H, W, C = 1, 16, 24, 32
    fmap1, fmap2, coords = _random_case(jax.random.PRNGKey(70 + radius),
                                        B, H, W, C, coord_span=1.1 * W)
    got, plan = _one_level(fmap1, fmap2, coords, radius, p_blk_target=128,
                           scheduled=True)
    # bands of four rows, one 128-lane row of the planes: every window
    assert plan.n_bands == 4 and plan.band_rows == 4 == plan.pack  # straddles
    cy = np.asarray(coords[0, ..., 1])
    assert (cy < radius).any() and (cy > H - radius).any()    # map's edges
    vol = dense_corr(fmap1, fmap2, precision=HIGHEST)
    want = np.asarray(lookup_dense([vol], coords, radius))
    ulp = np.spacing(np.float32(np.abs(np.asarray(vol)).max()))
    err = np.abs(np.asarray(got).reshape(want.shape) - want).max()
    assert err <= 2 * ulp, (err, ulp)


def test_tiles_whose_windows_lie_off_the_map_write_zeros():
    """Two whole query tiles far outside the map, one above and one below
    it: their schedule parks on a block whose one-hots match nothing, the
    tap scratch is written with exact zeros, and zeros come out — also
    when the tile before left sums in the scratch."""
    B, H, W, C, radius = 1, 16, 8, 16, 4
    fmap1, fmap2, coords = _random_case(jax.random.PRNGKey(80), B, H, W, C,
                                        coord_span=0.8 * H)
    off = jnp.zeros((H, W), bool).at[0:4].set(True).at[8:12].set(True)
    far = jnp.where(jnp.arange(H)[:, None] < 6, -40.0, 90.0)
    coords = coords.at[0, ..., 1].set(
        jnp.where(off, far, coords[0, ..., 1]))
    for scheduled in (False, True):
        got, plan = _one_level(fmap1, fmap2, coords, radius, q_blk=32,
                               p_blk_target=128, scheduled=scheduled)
        # (eight rows of eight columns to a 128-lane row: two such blocks)
        assert plan.t == 32 and plan.n_pblocks == 2 == plan.n_bands
        got = np.asarray(got).reshape(H, W, -1)
        assert not got[np.asarray(off)].any()
        assert np.abs(got[~np.asarray(off)]).max() > 0.1
        _assert_same_bits(got[np.asarray(off)],
                          np.zeros_like(got[np.asarray(off)]))


@pytest.mark.parametrize("name,H,W,C,radius,p_blk", [
    ("256-lanes", 6, 240, 16, 4, 1024),   # W2 = 240: rows of 256 lanes
    ("raft-small", 12, 40, 128, 3, 512),  # radius 3 (n + 1 = 8), C = 128
])
def test_new_body_by_value(name, H, W, C, radius, p_blk):
    """A level whose rows take two lane tiles (1080p's level 0: 240 -> 256)
    and RAFT-S's shape (a 7x7 window, 128 channels), scheduled, against the
    dense oracle, in both output dtypes."""
    B = 1
    fmap1, fmap2, coords = _random_case(jax.random.PRNGKey(90), B, H, W, C,
                                        dtype=BF16, coord_span=1.1 * W)
    coords = coords.at[..., 1].multiply(H / W)
    got, plan = _one_level(fmap1, fmap2, coords, radius, q_blk=128,
                           p_blk_target=p_blk, scheduled=True)
    assert plan.w2p == (256 if W == 240 else 64) and plan.n_pblocks > 1
    want = np.asarray(lookup_dense(
        [dense_corr(fmap1.astype(F32), fmap2.astype(F32),
                    precision=HIGHEST)], coords, radius))
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(np.asarray(got).reshape(want.shape), want,
                               rtol=0, atol=4e-7 * np.abs(want).max())
    out, _ = _one_level(fmap1, fmap2, coords, radius, q_blk=128,
                        p_blk_target=p_blk, scheduled=True, out_dtype=BF16)
    _assert_rounded_once(out, got)


def test_integer_coords_and_oob_zeros_padding():
    """Exact-integer coords (fractional part 0) and windows fully/partially
    outside the map (zeros padding, reference utils.py:84-89 semantics via
    lookup_dense)."""
    B, H, W, C, levels, radius = 1, 12, 12, 16, 3, 3
    fmap1, fmap2, _ = _random_case(jax.random.PRNGKey(1), B, H, W, C)
    # grid of exact integers, including far out-of-bounds positions
    xs = jnp.linspace(-10, W + 10, W).round()
    ys = jnp.linspace(-10, H + 10, H).round()
    coords = jnp.stack(jnp.meshgrid(xs, ys, indexing="xy"), -1)[None]
    pyramid = build_pyramid(fmap1, fmap2, levels)
    want = lookup_dense(pyramid, coords, radius)
    got = fused_lookup(fmap1, tuple(fmap2_pyramid(fmap2, levels)), coords,
                       radius)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_query_block_padding():
    """Q not a multiple of the query block size exercises the pad/slice path
    (q_blk default 128 > Q here, so T rounds Q up to a multiple of 8)."""
    B, H, W, C = 1, 6, 7, 8          # Q = 42 -> T = 48
    fmap1, fmap2, coords = _random_case(jax.random.PRNGKey(2), B, H, W, C)
    pyramid = build_pyramid(fmap1, fmap2, 2)
    want = lookup_dense(pyramid, coords, 2)
    got = fused_lookup(fmap1, tuple(fmap2_pyramid(fmap2, 2)), coords, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("out", [jnp.float32, jnp.bfloat16],
                         ids=["out-f32", "out-bf16"])
def test_gradients_match_blockwise_path(out):
    """custom_vjp backward (delegating to lookup_ondemand) must match the
    dense path's gradients w.r.t. fmap1, fmap2 levels, and coords — also
    where the kernel writes bfloat16: the cotangent then arrives in bfloat16
    and is raised to float32 before the twin, which is what differentiating
    ``.astype(bfloat16)`` of the float32 lookup gives."""
    B, H, W, C, levels, radius = 1, 8, 10, 16, 2, 2
    fmap1, fmap2, coords = _random_case(jax.random.PRNGKey(3), B, H, W, C)
    f2_levels = tuple(fmap2_pyramid(fmap2, levels))
    cot = jax.random.normal(jax.random.PRNGKey(4),
                            (B, H, W, levels * (2 * radius + 1) ** 2))

    def loss_fused(f1, f2l, c):
        got = fused_lookup(f1, f2l, c, radius, out_dtype=out)
        assert got.dtype == out
        return jnp.sum(got * cot)

    def loss_dense(f1, f2l, c):
        return jnp.sum(lookup_ondemand(f1, list(f2l), c, radius).astype(out)
                       * cot)

    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2))(fmap1, f2_levels, coords)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(fmap1, f2_levels, coords)
    for a, b in zip(jax.tree.leaves(g_fused), jax.tree.leaves(g_dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_make_fused_lookup_closure():
    B, H, W, C = 1, 8, 12, 16
    fmap1, fmap2, coords = _random_case(jax.random.PRNGKey(5), B, H, W, C)
    lookup = make_fused_lookup(fmap1, fmap2, num_levels=4, radius=4)
    got = lookup(coords=coords)
    want = lookup_dense(build_pyramid(fmap1, fmap2, 4), coords, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_model_forward_pallas_vs_dense():
    """Whole-model integration: corr_impl='pallas' output == 'dense'."""
    import dataclasses

    from raft_tpu.config import RAFTConfig
    from raft_tpu.models import init_raft
    from raft_tpu.models.raft import raft_forward

    config = RAFTConfig.small_model(iters=3)
    params = init_raft(jax.random.PRNGKey(0), config)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    im1 = jax.random.uniform(k1, (1, 64, 96, 3))
    im2 = jax.random.uniform(k2, (1, 64, 96, 3))

    out_dense, _ = raft_forward(
        params, im1, im2, dataclasses.replace(config, corr_impl="dense"))
    out_pallas, _ = raft_forward(
        params, im1, im2, dataclasses.replace(config, corr_impl="pallas"))
    # per-lookup parity is ~1e-5 (tests above); through the recurrent GRU the
    # accumulation-order difference amplifies, so compare at flow scale
    np.testing.assert_allclose(np.asarray(out_pallas.flow),
                               np.asarray(out_dense.flow),
                               rtol=1e-3, atol=0.05)


@pytest.mark.parametrize("B,H,W,C,levels,radius", [
    (1, 16, 24, 32, 4, 4),
    (2, 12, 16, 16, 3, 3),
    (1, 10, 14, 8, 2, 2),
    (1, 24, 40, 32, 4, 4),     # as in test_matches_dense_oracle
    (2, 46, 62, 16, 4, 4),
    (1, 12, 100, 8, 3, 3),
])
def test_window_schedule_matches_dense_oracle(B, H, W, C, levels, radius):
    """The key-block schedule (scalar-prefetch row-block schedule; only
    blocks a query block's bilinear windows touch do DMA+compute) must be
    value-identical to the full pass — including out-of-map windows, which
    the schedule parks on block 0 where the one-hot matches nothing."""
    from raft_tpu.ops.corr_pallas import _fused_lookup_impl

    fmap1, fmap2, coords = _random_case(jax.random.PRNGKey(5), B, H, W, C)
    want = lookup_dense(build_pyramid(fmap1, fmap2, levels), coords, radius)
    f2_levels = tuple(fmap2_pyramid(fmap2, levels))
    sched = _every_level_scheduled(coords, f2_levels, radius, 64, 128)
    got = _fused_lookup_impl(fmap1, f2_levels, coords, radius,
                             q_blk=64, p_blk_target=128, schedules=sched)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # a schedule made for another block plan is refused, not misread
    with pytest.raises(ValueError, match="schedule"):
        _fused_lookup_impl(fmap1, f2_levels, coords, radius,
                           q_blk=64, p_blk_target=256, schedules=sched)


def test_window_schedule_model_forward():
    """End-to-end: at blocks fine enough for the kernel's rule to schedule
    the two top levels of a 20x40 grid, the model matches the default plan,
    under which each level is one block — and counts fewer key blocks
    visited."""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models import init_raft, raft_forward

    base = RAFTConfig.full(iters=2, corr_impl="pallas")
    win = RAFTConfig.full(iters=2, corr_impl="pallas", pallas_p_blk=256)
    params = init_raft(jax.random.PRNGKey(0), base)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    im1 = jax.random.uniform(k1, (1, 160, 320, 3))
    im2 = jax.random.uniform(k2, (1, 160, 320, 3))
    out_a, _ = raft_forward(params, im1, im2, base)
    out_b, _ = raft_forward(params, im1, im2, win)
    # other blocks, another order of the float32 sums across them: the GRU
    # recurrence amplifies it (flows of 40 px here), so the tolerance is
    # relative to that
    np.testing.assert_allclose(np.asarray(out_a.flow), np.asarray(out_b.flow),
                               rtol=1e-3, atol=1e-3)
    visited, possible, tiles, steps, stored, live = (
        int(v) for v in out_a.corr_keyblocks)
    assert visited == possible == tiles == steps  # one block a level at 4096
    # a 20x40 grid: rows of 40, 20, 10 and 5 columns stored 64, 32, 16 and
    # 16 lanes wide, blocks of 20, 12, 8 and 8 map rows
    assert stored == tiles // 4 * (20 * 64 + 12 * 32 + 8 * 16 + 8 * 16)
    assert live == tiles // 4 * (20 * 40 + 12 * 20 + 8 * 10 + 8 * 5)
    visited, possible, tiles, steps, stored, live = (
        int(v) for v in out_b.corr_keyblocks)
    assert 0 < tiles <= visited <= steps <= possible and visited < possible
    assert 0 < live < stored and stored % 128 == 0


def test_model_forward_at_the_training_crop_width():
    """Two iterations of the full model on a 496-pixel-wide frame (62
    queries a row, 31, 15 and 7 at the pooled levels: every level
    lane-padded) against the stored volume."""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models import init_raft, raft_forward

    dense = RAFTConfig.full(iters=2, corr_impl="dense")
    fused = RAFTConfig.full(iters=2, corr_impl="pallas")
    params = init_raft(jax.random.PRNGKey(0), dense)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    im1 = jax.random.uniform(k1, (1, 64, 496, 3))
    im2 = jax.random.uniform(k2, (1, 64, 496, 3))
    out_a, _ = raft_forward(params, im1, im2, dense)
    out_b, _ = raft_forward(params, im1, im2, fused)
    # per-lookup parity is ~1e-6; the GRU recurrence amplifies summation-
    # order noise, so model-level comparison uses the same tolerance as
    # test_model_forward_pallas_vs_dense
    np.testing.assert_allclose(np.asarray(out_b.flow), np.asarray(out_a.flow),
                               rtol=1e-3, atol=0.05)


def test_window_schedule_invariants():
    """The prefetched band schedule must (a) name band starts, in granules,
    whose ``R`` rows lie inside the padded planes, (b) be non-decreasing,
    its active prefix stepping by one band (``R / g`` granules: disjoint
    bands, in order) and then constant, and (c) cover every map row any
    query's bilinear window touches — the properties the kernel's skip
    logic and the granule blocks' index maps rely on."""
    from raft_tpu.kernel_plans import corr_level_plan
    from raft_tpu.ops.corr_pallas import _band_schedule

    B, Qp, T, radius = 2, 256, 64, 4
    n = 2 * radius + 1
    H2 = 54
    plan = corr_level_plan(Qp, H2, 128, q_blk=T, p_blk_target=1024,
                           radius=radius, grid_w=128)
    g, n_g, K = plan.band_granule, plan.band_granules, plan.n_bands
    assert plan.banded and (g, plan.band_rows, K) == (4, 8, 7)
    key = jax.random.PRNGKey(11)
    coords = jax.random.uniform(key, (B, Qp, 2), minval=-20.0, maxval=80.0)
    S = np.asarray(_band_schedule(coords, 1.0, radius, T, plan))
    assert S.shape == (B, Qp // T, K)
    assert S.min() >= 0
    assert (S.max() + n_g) * g <= plan.band_rows_padded, S.max()
    d = np.diff(S, axis=2)
    assert np.isin(d, (0, n_g)).all(), "bands are disjoint and in order"
    assert (np.diff((d > 0).astype(int), axis=2) <= 0).all(), \
        "the active prefix, then repeats"

    cy = np.asarray(coords[..., 1]).reshape(B, Qp // T, T)
    iy0 = np.floor(cy).astype(int) - radius
    for b in range(B):
        for j in range(Qp // T):
            touched = {row for t in range(T)
                       for row in range(iy0[b, j, t], iy0[b, j, t] + n + 1)
                       if 0 <= row < H2}
            covered = {s * g + i for s in S[b, j].tolist()
                       for i in range(plan.band_rows)}
            assert touched <= covered, (b, j, touched, S[b, j].tolist())


# --- MXU passes from the operands' dtypes (corr_terms) ----------------------

@pytest.mark.parametrize("f1,f2,precision,terms,passes", [
    (BF16, BF16, HIGHEST, (1, 1), 1),    # level 0 of a bfloat16 encoder
    (BF16, F32, HIGHEST, (1, 3), 3),     # its pooled levels
    (F32, F32, HIGHEST, (3, 3), 6),      # float32 training / serving
    (F32, BF16, HIGHEST, (3, 3), 6),     # any float32 f1: today's program
    (BF16, BF16, DEFAULT, (1, 1), 1),    # DEFAULT: one pass, MXU's rounding
    (F32, F32, DEFAULT, (1, 1), 1),
])
def test_corr_terms_truth_table(f1, f2, precision, terms, passes):
    from raft_tpu.ops.corr_pallas import corr_mxu_passes, corr_terms, f2_terms

    assert corr_terms(f1, f2, precision) == terms
    assert corr_mxu_passes(*terms) == passes
    # what the kernel is handed follows: bfloat16 planes only for exact terms
    planes = f2_terms(f1, jnp.ones((1, 2, 2, 8), f2), precision)
    exact = precision == HIGHEST and f1 == BF16
    assert planes.dtype == (BF16 if exact else F32)
    assert planes.shape == ((terms[1] if exact else 1), 1, 2, 2, 8)


def _bf16_case(key, B, H, W, C, levels):
    """bfloat16 maps, their float32-pooled pyramid (level 0 the map itself,
    as make_fused_lookup hands it), coords with out-of-map windows."""
    fmap1, fmap2, coords = _random_case(key, B, H, W, C, dtype=BF16)
    pooled = fmap2_pyramid(fmap2.astype(F32), levels)
    return fmap1, [fmap2] + pooled[1:], coords


def test_split_reproduces_a_pooled_level_bit_for_bit():
    from raft_tpu.ops.corr_pallas import split_bf16_terms

    _, f2_levels, _ = _bf16_case(jax.random.PRNGKey(21), 2, 16, 24, 32, 4)
    for level in f2_levels[1:]:
        x = np.asarray(level)
        # the test means something: most pooled values are not bfloat16
        assert (x != x.astype(BF16).astype(np.float32)).mean() > 0.3
        planes = split_bf16_terms(level, 3)
        assert planes.dtype == BF16 and planes.shape == (3,) + x.shape
        p = np.asarray(planes.astype(F32))
        back = (p[2] + p[1]) + p[0]                    # smallest term first
        np.testing.assert_array_equal(back.view(np.uint32),
                                      x.view(np.uint32))
    # a bfloat16-valued map is its own single term
    one = split_bf16_terms(f2_levels[0], 1)
    np.testing.assert_array_equal(np.asarray(one[0].astype(F32)),
                                  np.asarray(f2_levels[0].astype(F32)))


@pytest.mark.parametrize("kernel,level,grid", [
    *[(kernel, level, (20, 28)) for kernel in ("all", "scheduled", "ragged")
      for level in range(4)],
    # the 368x496 training crop's grid: 23 row-blocks of 2 x 128 lanes
    ("all", 0, (46, 62)), ("scheduled", 0, (46, 62))])
def test_bf16_maps_equal_the_float32_highest_program(kernel, level, grid):
    """bfloat16 maps through the one-pass (level 0) / three-pass (pooled
    levels) form against the six-pass float32 program on the same values:
    the same products summed in float32, so float32 round-off apart (1e-6 of
    max |corr|, the band the kernel keeps against ``lookup_dense``) — with
    zero padding (20x28 pools to 10x14, 5x7, 2x3) and out-of-map windows."""
    from raft_tpu.kernel_plans import corr_level_plan
    from raft_tpu.ops.corr import mask_ragged_rows, ragged_pyramid
    from raft_tpu.ops.corr_pallas import (_lookup_level, _ragged_lookup_level,
                                          level_schedule)

    (H, W), B, C, radius = grid, 2, 32, 4
    fmap1, f2_levels, coords = _bf16_case(jax.random.PRNGKey(20 + level),
                                          B, H, W, C, 4)
    f1 = fmap1.reshape(B, H * W, C)
    cf = coords.reshape(B, H * W, 2)
    if kernel == "ragged":
        sizes = jnp.array([[H, W], [13, 17]], jnp.int32)
        pooled = ragged_pyramid(f2_levels[0].astype(F32), sizes, 4)
        f2l = (mask_ragged_rows(f2_levels[0], sizes) if level == 0
               else pooled[level])
        live = mask_ragged_rows(jnp.ones((B, H, W), bool), sizes)
        f1 = mask_ragged_rows(fmap1, sizes).reshape(B, H * W, C)
        fn = lambda a, b, prec, out=F32: _ragged_lookup_level(  # noqa: E731
            a, b, cf, live.reshape(B, H * W), sizes[:, 0] // 2 ** level,
            radius, level, q_blk=64, p_blk_target=256, interpret=True,
            grid_w=W, corr_precision=prec, out_dtype=out)
    else:
        f2l = f2_levels[level]
        h2, w2 = f2l.shape[1:3]
        plan = corr_level_plan(H * W, h2, w2, q_blk=64, p_blk_target=256,
                               radius=radius, grid_w=W)
        # (20x28's levels 2 and 3 are one block of 256 positions: no band)
        sched = (level_schedule(cf, plan, level, radius)
                 if kernel == "scheduled" and plan.banded else None)
        fn = lambda a, b, prec, out=F32: _lookup_level(   # noqa: E731
            a, b, cf, radius, level, q_blk=64, p_blk_target=256,
            interpret=True, grid_w=W, corr_precision=prec, schedule=sched,
            out_dtype=out)
    assert f2l.dtype == (BF16 if level == 0 else F32)
    got = fn(f1, f2l, HIGHEST)
    # written in bfloat16, the launch gives that float32 blend rounded once
    _assert_rounded_once(fn(f1, f2l, HIGHEST, BF16), got)
    got = np.asarray(got)
    want = np.asarray(fn(f1.astype(F32), f2l.astype(F32), HIGHEST))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-6 * np.abs(want).max())
    # and the exact form is what ran: bfloat16 operands, 1 or 3 one-pass dots
    # for each block of key rows a step is handed (a band's granules)
    text = str(jax.make_jaxpr(lambda a, b: fn(a, b, HIGHEST))(f1, f2l))
    # (the selection that follows them moves lanes and multiplies nothing:
    # until PR 32 two more dots, a_y and a_x, interpolated every block)
    n_dots = text.count("dot_general")
    blocks = (plan.band_granules if kernel == "scheduled" and plan.banded
              else 1)
    assert n_dots == (1 if level == 0 else 3) * blocks, text
    assert f"bf16[{1 if level == 0 else 3},1," in text


def test_float32_maps_keep_the_six_pass_program():
    """float32 maps that are NOT bfloat16 values: one float32 plane, one
    correlation dot at HIGHEST (the MXU's own six passes) on float32
    operands and the only dot of the program (the window's taps are
    gathered, not multiplied), nothing bfloat16 anywhere in it, the dense
    oracle's values."""
    from raft_tpu.ops.corr_pallas import _lookup_level, f2_terms

    B, H, W, C, radius = 1, 12, 16, 16, 3
    fmap1, fmap2, coords = _random_case(jax.random.PRNGKey(30), B, H, W, C)
    assert (np.asarray(fmap2)
            != np.asarray(fmap2.astype(BF16).astype(F32))).mean() > 0.9
    planes = f2_terms(F32, fmap2, HIGHEST)
    np.testing.assert_array_equal(np.asarray(planes[0]), np.asarray(fmap2))
    fn = lambda a, b: _lookup_level(                      # noqa: E731
        a.reshape(B, H * W, C), b, coords.reshape(B, H * W, 2), radius, 0,
        q_blk=64, p_blk_target=256, grid_w=W, interpret=True)
    text = str(jax.make_jaxpr(fn)(fmap1, fmap2))
    assert "bf16" not in text
    assert text.count("dot_general") == 1        # the correlation's, alone
    assert re.search(r"f32\[64,256\] = dot_general\[\s*dimension_numbers="
                     r"\(\(\[1\], \[1\]\), \(\[\], \[\]\)\)\s*precision="
                     r"\(Precision\.HIGHEST, Precision\.HIGHEST\)", text)
    want = lookup_dense(build_pyramid(fmap1, fmap2, 1), coords, radius)
    np.testing.assert_allclose(
        np.asarray(fn(fmap1, fmap2)).reshape(want.shape), np.asarray(want),
        rtol=0, atol=2e-6 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("out", [F32, BF16], ids=["out-f32", "out-bf16"])
def test_bf16_gradients_match_blockwise_twin(out):
    """bfloat16 maps: the forward rides the exact-terms kernel, the backward
    the float32 XLA twin at the configured precision, and each cotangent
    comes back in its primal's dtype — what ``astype(float32)`` before the
    lookup gave (the twin differentiated through that cast).  With a
    bfloat16 output the twin is followed by ``astype(bfloat16)``."""
    from raft_tpu.ops.corr import lookup_blockwise_onehot

    B, H, W, C, levels, radius = 1, 8, 10, 16, 2, 2
    fmap1, fmap2, coords = _random_case(jax.random.PRNGKey(3), B, H, W, C,
                                        dtype=BF16)
    cot = jax.random.normal(jax.random.PRNGKey(4),
                            (B, H, W, levels * (2 * radius + 1) ** 2))

    def loss_fused(f1, f2, c):
        return jnp.sum(make_fused_lookup(f1, f2, levels, radius,
                                         out_dtype=out)(c) * cot)

    def loss_twin(f1, f2, c):
        f2l = tuple(fmap2_pyramid(f2.astype(F32), levels))
        return jnp.sum(lookup_blockwise_onehot(
            f1.astype(F32), f2l, c, radius,
            precision=HIGHEST).astype(out) * cot)

    # float32 round-off apart before the rounding: a bfloat16 ulp after it
    np.testing.assert_allclose(loss_fused(fmap1, fmap2, coords),
                               loss_twin(fmap1, fmap2, coords),
                               rtol=1e-5 if out == F32 else 1e-3)
    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2))(fmap1, fmap2, coords)
    g_twin = jax.grad(loss_twin, argnums=(0, 1, 2))(fmap1, fmap2, coords)
    for a, b, x in zip(g_fused, g_twin, (fmap1, fmap2, coords)):
        assert a.dtype == b.dtype == x.dtype
        np.testing.assert_allclose(np.asarray(a.astype(F32)),
                                   np.asarray(b.astype(F32)),
                                   rtol=1e-2, atol=1e-4)


# ------------------------------------------- the output's dtype and layout
#
# A launch adds its visited key row-blocks' windows in a float32 VMEM scratch
# and writes [B, Q, n*n] once, in the dtype its consumer states: bit for bit
# ``astype`` of the float32 result, and the float32 result the values the
# kernel gave when it still accumulated in a [T, n, n] float32 output block
# (recorded from the parent commit of PR 29, interpret mode, this seed).

_RECORDED = {
    # name: (maps, B, H, W, C, levels, radius, seed, size, nonzero, sum|x|,
    #        ((flat index, value), ...))
    "things-bf16": (BF16, 2, 20, 28, 32, 4, 4, 40, 362880, 151797,
                    41250.1421002008,
                    ((0, 0.17217610776424408), (41625, 0.08778263628482819),
                     (82894, -0.0481746532022953),
                     (122935, 0.14495036005973816),
                     (164065, 0.08016001433134079),
                     (204656, 0.02320902794599533),
                     (241803, 0.04089152067899704),
                     (279342, -0.5822199583053589),
                     (321700, 0.08740711212158203),
                     (362865, 0.0012531970860436559))),
    "small-f32": (F32, 2, 12, 16, 16, 3, 3, 41, 56448, 28180,
                  8011.835166038254,
                  ((0, -1.170413851737976), (7218, -0.07167434692382812),
                   (13345, 0.17826677858829498), (19499, -0.0763673484325409),
                   (25955, 0.03567490726709366), (32061, -0.5320842266082764),
                   (37634, 0.9011698365211487), (43751, -0.2676534652709961),
                   (50188, 0.3870690166950226),
                   (56432, -0.00024574375129304826))),
}


@pytest.mark.parametrize("launches", ["rule", "all-blocks"])
@pytest.mark.parametrize("name", sorted(_RECORDED))
def test_float32_result_is_the_parents_and_bfloat16_its_rounding(name,
                                                                 launches):
    """raft-things' window (radius 4, bfloat16 maps) and raft-small's
    (radius 3: 49 values a row, float32 maps), under the kernel's own rule
    (level 0 scheduled at these blocks) and with every block walked."""
    from raft_tpu.ops.corr_pallas import _fused_lookup_impl

    (maps, B, H, W, C, levels, radius, seed, size, nonzero, abs_sum,
     samples) = _RECORDED[name]
    fmap1, fmap2, coords = _random_case(jax.random.PRNGKey(seed), B, H, W, C,
                                        dtype=maps,
                                        coord_span=0.9 * max(H, W))
    f2_levels = [fmap2] + fmap2_pyramid(fmap2.astype(F32), levels)[1:]
    run = lambda out: _fused_lookup_impl(                 # noqa: E731
        fmap1, f2_levels, coords, radius, q_blk=64, p_blk_target=256,
        interpret=True, out_dtype=out,
        schedules=None if launches == "rule" else (None,) * levels)
    got = run(F32)
    assert got.dtype == F32
    assert got.shape == (B, H, W, levels * (2 * radius + 1) ** 2)
    _assert_rounded_once(run(BF16), got)
    flat = np.asarray(got).ravel()
    assert flat.size == size and np.count_nonzero(flat) == nonzero
    np.testing.assert_allclose(np.abs(flat.astype(np.float64)).sum(),
                               abs_sum, rtol=1e-6)
    idx, want = zip(*samples)
    np.testing.assert_allclose(flat[list(idx)], want, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("grid,level", [
    ((135, 240), 0),     # 1080x1920: 32,400 queries in 254 tiles (112 padded)
    ((135, 240), 1),     #   nine, three, two row-blocks, then one (unscheduled)
    ((135, 240), 3),
    ((46, 62), 0),       # the 368x496 crop: 2,852 queries in 23 tiles
    ((46, 62), 2),
])
def test_out_dtype_where_queries_do_not_fill_the_tiles(grid, level):
    """The served block plan (q_blk 128, p_blk 4096) at grids whose query
    count is no multiple of the tile: the padded tail is written and cut,
    in both dtypes, and the scheduled launches end on repeated entries."""
    from raft_tpu.kernel_plans import corr_level_plan
    from raft_tpu.ops.coords import coords_grid
    from raft_tpu.ops.corr_pallas import _lookup_level, level_schedule

    (H, W), B, C, radius = grid, 1, 8, 4
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(50 + level), 3)
    f1 = jax.random.normal(k1, (B, H * W, C), BF16)
    f2 = fmap2_pyramid(jax.random.normal(k2, (B, H, W, C), BF16).astype(F32),
                       4)[level]
    f2 = f2.astype(BF16) if level == 0 else f2
    coords = (coords_grid(B, H, W) + jax.random.uniform(
        k3, (B, H, W, 2), minval=-3.0, maxval=3.0)).reshape(B, H * W, 2)
    h2, w2 = f2.shape[1:3]
    plan = corr_level_plan(H * W, h2, w2, q_blk=128, p_blk_target=4096,
                           radius=radius, grid_w=W)
    assert plan.qp != H * W
    sched = None
    if plan.banded:
        sched = level_schedule(coords, plan, level, radius)
        S = np.asarray(sched)
        assert (S[..., -1] == S[..., -2]).any()     # a tile ends on a repeat
    run = lambda out: _lookup_level(                      # noqa: E731
        f1, f2, coords, radius, level, q_blk=128, p_blk_target=4096,
        grid_w=W, interpret=True, schedule=sched, out_dtype=out)
    got = run(F32)
    assert got.shape == (B, H * W, (2 * radius + 1) ** 2)
    assert np.abs(np.asarray(got)).max() > 0.1
    _assert_rounded_once(run(BF16), got)
    if H * W * h2 * w2 > 5e7:   # a dense volume of a gigabyte and more:
        return                  # the other cases hold the values
    want = lookup_dense(
        [dense_corr(f1.reshape(B, H, W, C).astype(F32), f2.astype(F32),
                    precision=HIGHEST)],
        coords.reshape(B, H, W, 2) / 2 ** level, radius)
    np.testing.assert_allclose(np.asarray(got).reshape(want.shape),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_closures_hand_over_the_dtype_they_were_built_for():
    """What ``models/raft.py`` builds: the closure states its consumer's
    dtype once and every call returns it; the default stays float32 (a
    caller that adds results up, as the ring lookup does)."""
    fmap1, fmap2, coords = _random_case(jax.random.PRNGKey(5), 1, 8, 12, 16,
                                        dtype=BF16)
    f32 = make_fused_lookup(fmap1, fmap2, num_levels=4, radius=4)
    bf16 = make_fused_lookup(fmap1, fmap2, num_levels=4, radius=4,
                             out_dtype=BF16)
    got = f32(coords)
    assert got.dtype == F32
    _assert_rounded_once(bf16(coords, bf16.schedules(coords)), got)


@pytest.mark.parametrize("small", [False, True], ids=["things", "small"])
def test_served_flow_is_what_the_converted_float32_lookup_gave(small,
                                                               monkeypatch):
    """The whole model in bfloat16 at 216x384, twelve iterations, pallas
    lookup: with the kernels writing bfloat16 the flow is the flow of the
    program that takes the kernels' float32 output and converts it in
    ``gru_step`` (PR 29's parent: the factory forced back to float32 here,
    the cast still in the model).  On the chip the two launches are equal
    bit for bit; here a few values in a million round apart
    (:func:`_assert_rounded_once` has the reason), and twelve updates carry
    such a value into every flow vector.  So the flow head is damped as the
    benchmark damps it (``benchmark/weights.py``: updates of a fraction of
    a pixel, the recurrence contractive, where untrained weights make two
    chaotic orbits of any difference), and the flows agree to a bfloat16
    ulp of their largest value."""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models import init_raft, raft_forward
    from raft_tpu.ops import corr_pallas

    make = RAFTConfig.small_model if small else RAFTConfig.full
    config = make(iters=12, compute_dtype="bfloat16", corr_impl="pallas")
    params = init_raft(jax.random.PRNGKey(0), config)
    head = params["update_block"]["flow_head"]["conv2"]
    head["w"], head["b"] = 0.005 * head["w"], 0.005 * head["b"]
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    im1 = jax.random.uniform(k1, (1, 216, 384, 3))
    im2 = jax.random.uniform(k2, (1, 216, 384, 3))
    seen = []
    factory = corr_pallas.make_fused_lookup

    def as_the_parent(*args, out_dtype, **kw):
        seen.append(out_dtype)
        return factory(*args, **kw)                # float32, the default

    written = raft_forward(params, im1, im2, config)[0].flow
    monkeypatch.setattr(corr_pallas, "make_fused_lookup", as_the_parent)
    converted = raft_forward(params, im1, im2, config)[0].flow
    assert seen == [jnp.bfloat16]          # the model states its compute dtype
    assert written.dtype == converted.dtype
    largest = float(jnp.abs(converted).max())
    assert largest > 1.0
    assert float(jnp.abs(written - converted).max()) <= 2.0 ** -7 * largest
