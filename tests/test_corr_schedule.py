"""The lookup kernel's band schedule: the plan that gives a level one
(``kernel_plans.corr_level_plan``, read from the level's shape, the radius,
the tile and the grid's width alone), its values against the all-rows walk
(bit for bit: every tap lies in one band, rows left out added exact zeros,
the visited ones keep their order), its counts, and the whole model under it
against the benchmark's plain reference.  The kernel runs in Pallas
interpret mode, at grids cut down from the served ones and at small
``p_blk_target``s standing in for the many rows of a large frame."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.kernel_plans import corr_level_plan
from raft_tpu.ops.coords import coords_grid
from raft_tpu.ops.corr import build_pyramid, fmap2_pyramid, lookup_dense
from raft_tpu.ops.corr_pallas import (_fused_lookup_impl, _lookup_level,
                                      level_plans, level_schedule,
                                      level_shapes, lookup_schedules,
                                      schedule_keyblocks)

# the served grids' four flows of a launch's step count (tests/ is on the path)
from test_corr_bands import MODELS, SERVED, SHORT_GRID_KINDS, served_flow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BF16, F32 = jnp.bfloat16, jnp.float32
RADIUS = 4


#: grid, radius -> per level ``(g, R, K)`` of its band, None where the map is
#: one whole-map block: the plan of ``kernel_plans.corr_level_plan`` at the
#: served q_blk 128 / p_blk 4096, pinned.
PLAN_TABLE = [
    # the three served configurations (BENCHMARK.json), four levels each:
    # raft-things at 440x1024, raft-things and RAFT-S at 1080x1920
    # (440x1024's level 1, 27 rows of 64 columns, is banded since PR 43: a
    # level is banded where it holds more rows than a band)
    ((55, 128), 4, ((4, 16, 4), (4, 16, 2), None, None)),
    ((135, 240), 4, ((4, 16, 9), (4, 16, 5), (4, 16, 3), None)),
    ((135, 240), 3, ((4, 16, 9), (4, 16, 5), (4, 16, 3), None)),
    # RAFT-S at 440x1024, which no cell runs: the same bands (8 + 1 + 2 + 3
    # rows round up to 16 as 10 + 1 + 2 + 3 do)
    ((55, 128), 3, ((4, 16, 4), (4, 16, 2), None, None)),
    # 4K: the rule needs no new case (level 0's rows take 512 lanes, so a
    # step's 4096 positions are 8 rows: under a window, two bands a tile)
    ((270, 480), 4, ((4, 8, 34), (4, 16, 9), (4, 16, 5), (4, 16, 3))),
    # a training crop (368x496): a tile of 128 queries spans three rows of 62
    ((46, 62), 4, ((4, 20, 3), (4, 20, 2), None, None)),
    # thumbnails (128x160): one block a level, no schedule anywhere
    ((16, 20), 4, (None,) * 4),
]

#: ``pallas_p_blk`` -> the bands of 55x128's four levels (rows of 64, 32 and
#: 16 lanes at levels 1-3: a step's positions hold 2, 4 and 8 x the rows
#: they held at 128 lanes, and level 3's six rows are one 128-lane-row block
#: at any of these)
FINER_TABLE = [
    (4096, ((4, 16, 4), (4, 16, 2), None, None)),
    (1024, ((4, 8, 7), (4, 16, 2), None, None)),
    (256, ((2, 2, 28), (4, 4, 7), (4, 8, 2), None)),
]

#: stored row width and map rows to a 128-lane row, by map columns
LANES_TABLE = [(16, 16, 8), (30, 32, 4), (32, 32, 4), (60, 64, 2),
               (64, 64, 2), (120, 128, 1), (128, 128, 1), (240, 256, 1),
               (7, 16, 8), (17, 32, 4), (33, 64, 2), (65, 128, 1),
               (129, 256, 1), (480, 512, 1)]


# ------------------------------------------------------------ the shape rule

def _bands(h, w, q_blk=128, p_blk=4096, levels=4, radius=RADIUS):
    """Per level ``(g, R, K)`` of the band, or None where the map is one
    block."""
    plans = level_plans(h * w, w, [(h >> i, w >> i) for i in range(levels)],
                        radius, q_blk, p_blk)
    assert all(p.banded == (p.rows > p.step_rows) for p in plans)
    return tuple((p.band_granule, p.band_rows, p.n_bands) if p.banded
                 else None for p in plans)


@pytest.mark.parametrize("grid,radius,bands", PLAN_TABLE)
def test_rule_reads_the_plan_alone(grid, radius, bands):
    """The three served configurations' four levels each, and what the
    rule gives shapes no cell runs: ``(g, R, K)`` where a level's map
    holds more rows than a band, None (one whole-map block, no schedule)
    where it does not.  No flag, no config field: shapes and the radius."""
    assert _bands(*grid, radius=radius) == bands
    for level, band in enumerate(bands):
        plan = corr_level_plan(grid[0] * grid[1], grid[0] >> level,
                               grid[1] >> level, q_blk=128,
                               p_blk_target=4096, radius=radius,
                               grid_w=grid[1])
        # a block, a band and its granule are whole 128-lane rows of the
        # planes: 2, 4 or 8 map rows where a row is stored under 128 lanes
        assert plan.pack == max(1, 128 // plan.w2p)
        assert plan.h2_blk % plan.pack == 0 == plan.rows_padded % plan.pack
        if band is None:
            assert plan.h2_blk == plan.rows_padded < plan.rows + plan.pack
            assert plan.n_pblocks == 1 and plan.step_rows == plan.h2_blk
            continue
        g, rows, k = band
        assert g % plan.pack == 0 and plan.step_rows == rows
        assert rows % g == 0 and rows * plan.w2p <= 4096
        assert (k - 1) * rows < plan.rows <= k * rows
        # the last band a tile can name starts on the map's last granule
        assert plan.band_rows_padded == (plan.rows - 1) // g * g + rows


@pytest.mark.parametrize("p_blk,bands", FINER_TABLE)
def test_finer_blocks_bring_the_schedule_to_sintels_grid(p_blk, bands):
    """``pallas_p_blk`` caps the positions of one step: a finer one bands
    more levels of 55x128, and caps the band itself (``R x w2p <= p_blk``),
    down to bands shorter than a window, which every tile then takes
    several of."""
    assert _bands(55, 128, p_blk=p_blk) == bands
    for level, band in enumerate(bands):
        assert band is None or band[1] * (128 >> min(level, 3)) <= p_blk


@pytest.mark.parametrize("w2,lanes,pack", LANES_TABLE)
def test_a_row_is_stored_in_the_lanes_that_hold_it(w2, lanes, pack):
    """``kernel_plans.corr_row_lanes``: the smallest of 16, 32, 64 and 128
    lanes that holds a level's columns, a multiple of 128 above; under 128,
    2, 4 or 8 map rows share a 128-lane row of the planes and the plan's
    rows (block, band, granule, padding) are multiples of that."""
    from raft_tpu.kernel_plans import corr_row_lanes
    assert corr_row_lanes(w2) == lanes
    for h2 in (5, 16, 27, 67):
        plan = corr_level_plan(4096, h2, w2, q_blk=128, p_blk_target=4096,
                               radius=RADIUS, grid_w=8 * w2)
        assert (plan.w2, plan.w2p, plan.pack) == (w2, lanes, pack)
        assert plan.banded == (h2 > min(16, 4096 // lanes))
        rows = plan.band_rows_padded if plan.banded else plan.rows_padded
        assert rows % pack == 0 and rows >= h2
        assert plan.step_rows % pack == 0
        assert plan.step_rows * lanes % 128 == 0
        if plan.banded:
            assert plan.band_granule == max(4, pack)


def test_lookup_schedules_follows_the_rule():
    B, H, W, C = 1, 20, 40, 8
    f2_levels = fmap2_pyramid(jnp.zeros((B, H, W, C)), 4)
    sched = lookup_schedules(coords_grid(B, H, W), level_shapes(f2_levels),
                             RADIUS, q_blk=64, p_blk_target=128)
    want = [b is not None for b in _bands(H, W, q_blk=64, p_blk=128)]
    # (levels 2 and 3, 6 and 3 rows of 16 lanes, are one 128-lane-row block)
    assert [s is not None for s in sched] == want == [True, True, False,
                                                      False]
    plan = corr_level_plan(H * W, H, W, q_blk=64, p_blk_target=128,
                           radius=RADIUS, grid_w=W)
    assert sched[0].shape == (B, plan.qp // plan.t, plan.n_bands)
    # one block a level at the default plan of so small a grid
    assert lookup_schedules(coords_grid(B, H, W), level_shapes(f2_levels),
                            RADIUS) == (None,) * 4
    with pytest.raises(ValueError, match="one block"):
        level_schedule(coords_grid(B, H, W).reshape(B, H * W, 2),
                       corr_level_plan(H * W, H, W, q_blk=64,
                                       p_blk_target=4096, radius=RADIUS,
                                       grid_w=W), 0, RADIUS)


# ------------------------------------------- scheduled == all blocks, bitwise

H, W, C = 30, 44, 32            # Q = 1320: a ragged tail tile at q_blk 128
P_BLK = 128                     # level 0: 15 bands of 2 rows (of 64 lanes);
#                                 level 1: 4 bands of 4 rows (of 32 lanes)


def _flow_field(kind: str, B: int) -> jax.Array:
    """Coordinates [B, H, W, 2] for each case of the issue's list."""
    base = coords_grid(B, H, W)
    if kind == "zero":
        return base
    if kind == "three-blocks":
        # the tile's rows move apart: its windows lie across three and more
        # of the 2-row bands
        shift = jnp.where(jnp.arange(W)[None, None, :] % 2 == 0, -3.25, 4.5)
        return base.at[..., 1].add(jnp.broadcast_to(shift, (B, H, W)))
    if kind == "outside":
        # left half wholly outside the map (above it), right half partly
        # outside (straddling the bottom edge)
        off = jnp.where(jnp.arange(W)[None, None, :] < W // 2,
                        -(H + 20.5), H - 2.75)
        return (base.at[..., 1].set(jnp.broadcast_to(off, (B, H, W)))
                .at[..., 0].add(-6.5))
    assert kind == "random"
    return jax.random.uniform(jax.random.PRNGKey(9), (B, H, W, 2),
                              minval=-8.0, maxval=1.2 * W)


def _tile_bands(S, plan):
    """Bands each tile takes, from its schedule."""
    return (np.asarray(S)[..., -1] - np.asarray(S)[..., 0]) \
        // plan.band_granules + 1


@pytest.mark.parametrize("out", [F32, BF16], ids=["out-f32", "out-bf16"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["zero", "three-blocks", "outside",
                                  "random"])
def test_scheduled_lookup_equals_all_blocks_bit_for_bit(kind, dtype, out):
    """At a grid with 15 bands of key rows at level 0 and 4 at level 1
    (bands of two and four rows, two and four map rows to a 128-lane row:
    every tile takes several), Q not a multiple of the tile: the rule's
    program (levels 0 and 1 banded, levels 2 and 3 one packed block each)
    equals the all-rows program bit for bit, and the reference's lookup (``lookup_dense`` on
    the same values) to ``tests/test_corr_pallas.py``'s tolerance.  Written
    in bfloat16 (``out``), both are the float32 result rounded once: the
    write happens at a tile's last grid step, which under a schedule is
    nearly always a repeated entry that skips the compute."""
    B = 2
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    fmap1 = jax.random.normal(k1, (B, H, W, C), dtype)
    fmap2 = jax.random.normal(k2, (B, H, W, C), dtype)
    coords = _flow_field(kind, B)
    f2_levels = [fmap2] + fmap2_pyramid(fmap2.astype(F32), 4)[1:]
    sched = lookup_schedules(coords, level_shapes(f2_levels), RADIUS,
                             q_blk=128, p_blk_target=P_BLK)
    assert [s is not None for s in sched] == [True, True, False, False]
    plan0 = corr_level_plan(H * W, H, W, q_blk=128, p_blk_target=P_BLK,
                            radius=RADIUS, grid_w=W)
    assert plan0.n_bands >= 4 and plan0.qp != H * W
    run = lambda s, out=F32: np.asarray(_fused_lookup_impl(   # noqa: E731
        fmap1, f2_levels, coords, RADIUS, q_blk=128, p_blk_target=P_BLK,
        interpret=True, schedules=s, out_dtype=out))
    S = np.asarray(sched[0])
    if kind in ("zero", "three-blocks"):        # every tile ends on a repeat
        assert (S[..., -1] == S[..., -2]).all()
    got = run(sched)
    if out == BF16:
        want = np.asarray(jnp.asarray(got).astype(BF16)).view(np.uint16)
        for s in (sched, (None,) * 4):
            np.testing.assert_array_equal(run(s, BF16).view(np.uint16), want)
        if kind == "outside":
            assert not want[:, :, : W // 2].any()             # +0.0, all of it
        return
    whole = run((None,) * 4)
    np.testing.assert_array_equal(got.view(np.uint32), whole.view(np.uint32))
    want = lookup_dense(build_pyramid(fmap1.astype(F32), fmap2.astype(F32),
                                      4), coords, RADIUS)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-5)
    if kind == "three-blocks":
        assert _tile_bands(S, plan0).max() >= 3
    if kind == "outside":
        assert np.abs(got[:, :, : W // 2]).max() == 0.0   # wholly outside


def _counts_by_hand(coords, shapes, plans, sched, radius=RADIUS):
    """``(visited, possible, tiles, steps, stored, live)`` counted in numpy
    from the coords: per tile the bands from the granule of its lowest
    touched row to its highest (the parent's three numbers), per banded
    level its tiles times the bands of the tile that takes most, which is
    the launch's third grid dimension, and a level's steps in key positions
    (x the map rows of a step x the lanes a row is stored in, and x the
    map's own columns)."""
    B, h, w, _ = coords.shape
    cf = np.asarray(coords).reshape(B, h * w, 2)
    want_v = want_p = want_t = 0
    want_s = []
    for lvl, (plan, (h2, w2)) in enumerate(zip(plans, shapes)):
        tiles = plan.qp // plan.t
        want_t += B * tiles
        if sched[lvl] is None:
            want_p += B * tiles
            want_v += B * tiles
            want_s.append(B * tiles)
            continue
        want_p += B * tiles * plan.n_bands
        cy = np.pad(cf[..., 1], ((0, 0), (0, plan.qp - h * w)), mode="edge")
        top = np.floor(cy / 2 ** lvl).astype(int).reshape(B, tiles, -1) \
            - radius
        lo, hi = top.min(-1), top.max(-1) + 2 * radius + 1
        most = 1
        for b in range(B):
            for j in range(tiles):
                bands = 1                           # parked on row 0
                if hi[b, j] >= 0 and lo[b, j] < h2:
                    rows = np.clip([lo[b, j], hi[b, j]], 0, h2 - 1)
                    start = rows[0] // plan.band_granule * plan.band_granule
                    bands = int((rows[1] - start) // plan.band_rows + 1)
                want_v += bands
                most = max(most, bands)
        want_s.append(B * tiles * most)
    stored = sum(n * p.step_rows * p.w2p for n, p in zip(want_s, plans))
    live = sum(n * p.step_rows * w2
               for n, p, (_, w2) in zip(want_s, plans, shapes))
    return want_v, want_p, want_t, sum(want_s), stored, live


def test_keyblock_counts_are_the_schedules_distinct_blocks():
    """``schedule_keyblocks``' numbers against a count made in numpy from
    the same coords: per tile the bands from the granule of its lowest
    touched row to its highest, per level the steps of a walk of every band,
    per lookup its (tile, level) pairs, per launch its grid steps, and the
    key positions those steps multiplied over, stored and live."""
    B = 2
    coords = _flow_field("three-blocks", B)
    shapes = [(H, W), (H // 2, W // 2), (H // 4, W // 4), (H // 8, W // 8)]
    p_blk = P_BLK               # two banded levels and two of one block
    sched = lookup_schedules(coords, shapes, RADIUS, q_blk=128,
                             p_blk_target=p_blk)
    plans = level_plans(H * W, W, shapes, RADIUS, 128, p_blk)
    assert [s is not None for s in sched] == [True, True, False, False]
    got = tuple(int(v) for v in schedule_keyblocks(sched, B, plans))
    assert got == _counts_by_hand(coords, shapes, plans, sched)
    visited, possible, n_tiles, steps, stored, live = got
    assert n_tiles < visited <= steps < possible
    assert 0 < live <= stored and stored % 128 == 0


@pytest.mark.parametrize("grid,radius,fill", [
    ((135, 240), 4, (93.75, 93.75, 93.75, 93.75)),
    ((135, 240), 3, (93.75, 93.75, 93.75, 93.75)),
    ((55, 128), 4, (100.0, 100.0, 100.0, 100.0)),
    ((46, 62), 4, (96.875, 96.875, 93.75, 43.75)),
])
def test_key_positions_are_the_steps_by_the_plans(grid, radius, fill):
    """``schedule_keyblocks``' ``stored`` and ``live``: per level, steps x
    the map rows of a step (a band's, or the one block's; a row-block's
    where a banded level is walked without a schedule) x the lanes a row is
    stored in, and x the map's own columns; live / stored is each level's
    columns over its stored width (240 / 256, 120 / 128, 60 / 64, 30 / 32 at
    1080x1920: 93.75 % where rows of 128 lanes gave 46.9 and 23.4 at levels
    2 and 3), whatever the steps."""
    h, w = grid
    B = 2
    shapes = [(h >> i, w >> i) for i in range(4)]
    plans = level_plans(h * w, w, shapes, radius)
    sched = lookup_schedules(coords_grid(B, h, w), shapes, radius)
    for level, plan in enumerate(plans):
        alone = tuple(p if i == level else None for i, p in enumerate(plans))
        tiles = B * (plan.qp // plan.t)
        # still flow: a step a tile under the rule's schedule
        _, _, _, steps, stored, live = (
            int(v) for v in schedule_keyblocks(sched, B, alone))
        assert steps == tiles and stored == tiles * plan.step_rows * plan.w2p
        assert live == tiles * plan.step_rows * (w >> level)
        assert 100.0 * live / stored == fill[level]
        # and the walk of every row-block
        _, _, _, steps, stored, live = (
            int(v) for v in schedule_keyblocks((None,) * 4, B, alone))
        assert steps == tiles * plan.n_pblocks
        assert stored == steps * plan.h2_blk * plan.w2p
        assert live == steps * plan.h2_blk * plan.w2
    # a level pooled away (None) holds no positions: the four together
    got = [int(v) for v in schedule_keyblocks(sched + (None,), B,
                                              plans + (None,))]
    assert got[4] == sum(B * (p.qp // p.t) * p.step_rows * p.w2p
                         for p in plans)


@pytest.mark.parametrize("kind", SHORT_GRID_KINDS)
@pytest.mark.parametrize("grid", list(SERVED))
@pytest.mark.parametrize("model", list(MODELS))
def test_grid_steps_are_counted_from_the_schedules(model, grid, kind):
    """``tests/test_corr_bands.py``'s four flows at the served grids, all
    four levels, both models' radius: the fourth number of
    ``schedule_keyblocks`` is, by hand, each banded launch's tiles times the
    bands of its widest tile (one-block levels one step a tile), and the
    three numbers the parent counted are what it counted.  Smooth flow and a
    tile off the map: a step a tile; one wide tile in one batch row holds
    its level-0 launch (both rows) to three steps a tile while ``visited``
    grows by two; a tile across the whole map brings every banded launch to
    the walk of every band."""
    radius, _ = MODELS[model]
    (h, w), _ = SERVED[grid]
    B = 2
    coords = served_flow(kind, grid, B)
    shapes = [(h >> i, w >> i) for i in range(4)]
    sched = lookup_schedules(coords, shapes, radius)
    plans = level_plans(h * w, w, shapes, radius)
    banded = [p.banded for p in plans]
    assert banded == [s is not None for s in sched]
    assert banded == ([True] * 3 + [False] if grid == "135x240"
                      else [True] * 2 + [False] * 2)
    got = tuple(int(v) for v in schedule_keyblocks(sched, B, plans))
    visited, possible, n_tiles, steps = got[:4]
    assert got == _counts_by_hand(coords, shapes, plans, sched, radius)
    per_level = B * plans[0].qp // plans[0].t
    assert n_tiles == 4 * per_level
    assert possible == per_level * sum(p.n_bands if p.banded else 1
                                       for p in plans)
    if kind in ("smooth", "outside"):
        assert visited == steps == n_tiles          # K' = 1 at every level
    elif kind == "all-bands":
        assert steps == possible                    # K' = K at every level
        assert visited == n_tiles + sum(p.n_bands - 1 for p in plans
                                        if p.banded)
    else:
        # level 0: one tile of three bands, so three steps a tile for all
        assert n_tiles + 2 <= visited < n_tiles + 8
        assert steps >= n_tiles + 2 * per_level
        assert steps < possible


def test_a_schedule_of_another_plan_is_refused():
    f1 = jnp.zeros((1, H * W, C))
    coords = coords_grid(1, H, W).reshape(1, H * W, 2)
    plan = corr_level_plan(H * W, H, W, q_blk=128, p_blk_target=512,
                           radius=RADIUS, grid_w=W)
    with pytest.raises(ValueError, match="schedule"):
        _lookup_level(f1, jnp.zeros((1, H, W, C)), coords, RADIUS, 0,
                      q_blk=128, p_blk_target=P_BLK, interpret=True,
                      grid_w=W,
                      schedule=level_schedule(coords, plan, 0, RADIUS))


# --------------------------------- the whole model against the plain reference

def test_model_under_the_schedule_agrees_with_the_benchmarks_reference():
    """raft-things at 216x384 (16:9; a 27x48 grid), batch 2, 4 updates,
    float32, ``corr_impl=pallas`` in interpret mode with levels 0 and 1 under
    the band schedule (levels 2 and 3 are one packed block each), on the benchmark's seeded weights, against
    ``benchmark/reference.py`` (float32, dense volume, gather lookup): 1e-4
    of the mean flow, the tolerance ``benchmark/tests/test_reference.py``
    states for the program's dense forward — the kernel multiplies the same
    products and sums them in float32, so float32 round-off over 4 updates
    (about 1e-6) is all that separates them."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import check
        import inputs
        import reference
        import weights as weights_mod
    finally:
        sys.path.remove(os.path.join(REPO, "benchmark"))
    from raft_tpu import RAFTConfig
    from raft_tpu.models import raft_forward

    with open(os.path.join(REPO, "benchmark", "configs",
                           "raft-things-1080p.json")) as f:
        mcfg = weights_mod.model_cfg(json.load(f))
    wts = weights_mod.make_weights(2_600_000_033, mcfg)
    pcfg = RAFTConfig.full(iters=4, corr_impl="pallas", pallas_q_blk=64,
                           pallas_p_blk=128)
    assert _bands(27, 48, q_blk=64, p_blk=128) == (
        (2, 2, 14), (4, 4, 4), None, None)
    pairs = inputs.make_pairs(26, 2, 216, 384, 4)
    im1, im2 = (jnp.asarray(np.stack([p[i] for p in pairs])
                            / np.float32(255)) for i in (0, 1))
    out, _ = jax.jit(lambda w, a, b: raft_forward(w, a, b, pcfg))(wts, im1,
                                                                  im2)
    visited, possible, tiles, steps, stored, live = (
        int(v) for v in out.corr_keyblocks)
    assert 0 < tiles <= visited <= steps <= possible
    assert 0 < live < stored and stored % 128 == 0
    assert visited < 0.7 * possible
    for i, (a, b) in enumerate(pairs):
        ref = np.asarray(reference.flow(wts, a, b, mcfg, 4))
        assert check.rel_epe(np.asarray(out.flow[i]), ref) < 1e-4
