"""The banded lookup launches at the served shapes, cut down for the CPU: a
query tile fetches its own band of key rows (``kernel_plans.corr_level_plan``)
and the launch gives what the walk over every row-block gives, bit for bit,
for raft-things' and RAFT-S's radius and channels, bfloat16 and float32 maps,
both output dtypes, and every kind of flow a band can meet; the launch's
third grid dimension is the bands its widest tile needs (PR 38) and it gives
what the launch of the plan's ``K`` steps a tile gave; the ragged launch,
which keeps the fixed row-blocks as its pages, gives what it gave.
(``tests/test_corr_schedule.py`` has the plan's table, the counts and the
model; this file is its own so that the suite's workers share the load.)
Pallas interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.kernel_plans import corr_level_plan
from raft_tpu.ops import corr_pallas
from raft_tpu.ops.coords import coords_grid
from raft_tpu.ops.corr import fmap2_pyramid, mask_ragged_rows
from raft_tpu.ops.corr_pallas import (_fused_lookup_impl, _lookup_level,
                                      _ragged_lookup_level, level_plans,
                                      level_schedule, level_shapes,
                                      lookup_schedules, schedule_steps)

BF16, F32 = jnp.bfloat16, jnp.float32


def _tile_bands(S, plan):
    """Bands each tile takes, from its schedule."""
    return (np.asarray(S)[..., -1] - np.asarray(S)[..., 0]) \
        // plan.band_granules + 1


#: name -> (radius, channels): raft-things and RAFT-S
MODELS = {"things": (4, 256), "small": (3, 128)}
#: name -> (grid, p_blk_target).  ``hd``: rows of 256 lanes like 1080p's
#: level 0 (135 x 240), tiles that begin mid-row and a padded tail tile
#: (4,896 queries); levels 0 and 1 (18 rows of 68 in 128 lanes) hold more
#: rows than a band of 16 and are banded at the served 4096 positions.
#: ``sintel``: 128 queries a row, a tile a row like 55 x 128; at 2048
#: positions levels 0 and 1 are both banded, and level 1's rows (64 columns)
#: lie two to a 128-lane row of the planes, as 440x1024's do (PR 43).
GRIDS = {"hd": ((36, 136), 4096), "sintel": ((40, 128), 2048)}


def _served_case(model, grid, kind, dtype):
    radius, c = MODELS[model]
    (h, w), p_blk = GRIDS[grid]
    plans = level_plans(h * w, w, [(h, w), (h // 2, w // 2)], radius, 128,
                        p_blk)
    rows = plans[0].band_rows
    base = coords_grid(1, h, w)
    x = jnp.arange(w)[None, None, :]
    if kind == "rest":
        # a smooth flow of under a cell: every tile's windows in one band
        y = jnp.arange(h)[None, :, None]
        coords = base + jnp.stack([0.8 * jnp.sin(x / 9.0 + y / 7.0),
                                   0.7 * jnp.cos(x / 8.0 - y / 6.0)], -1)
    elif kind == "two-bands":
        # (i) inside every tile the flow jumps by a band's rows: its windows
        # span more than one band
        jump = jnp.where(x % 2 == 0, -0.5 * rows, 0.5 * rows + 0.5)
        coords = base.at[..., 1].add(jnp.broadcast_to(jump, (1, h, w)))
    elif kind == "edges":
        # (ii) a third of the columns wholly above the map, a third wholly
        # below it, a third across its last rows
        off = jnp.where(x < w // 3, -(h + 20.5),
                        jnp.where(x < 2 * w // 3, h + 30.25, h - 2.75))
        coords = (base.at[..., 1].set(jnp.broadcast_to(off, (1, h, w)))
                  .at[..., 0].add(-3.5))
    else:
        # (iii) every window's first row is the map's last: the band starts
        # on the last granule and reads the padding's zero rows after it
        assert kind == "last-granule"
        coords = base.at[..., 1].set(h - 1 + radius + 0.5)
    k1, k2 = jax.random.split(jax.random.PRNGKey(36))
    fmap1 = jax.random.normal(k1, (1, h, w, c), dtype)
    fmap2 = jax.random.normal(k2, (1, h, w, c), dtype)
    f2_levels = [fmap2] + fmap2_pyramid(fmap2.astype(F32), 2)[1:]
    return radius, p_blk, plans, fmap1, f2_levels, coords


@pytest.mark.parametrize("out", [F32, BF16], ids=["out-f32", "out-bf16"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["rest", "two-bands", "edges",
                                  "last-granule"])
@pytest.mark.parametrize("grid", list(GRIDS))
@pytest.mark.parametrize("model", list(MODELS))
def test_banded_launch_equals_the_all_rows_launch(model, grid, kind, dtype,
                                                  out):
    """raft-things' and RAFT-S's shapes (radius 4 / 256 channels, radius 3 /
    128) at grids cut down from the two served ones, the two top levels,
    bfloat16 maps (1 + 3 planes) and float32 maps, both output dtypes: the
    launch that fetches each tile's own band of key rows equals the launch
    that walks every row-block, bit for bit — for a flow at rest (one band a
    tile), (i) tiles whose windows span more than one band, (ii) windows
    wholly above, below and across the map's last rows, (iii) bands that
    start on the map's last granule, and (iv) the padded tail tile (grid
    ``hd`` has one in every case)."""
    radius, p_blk, plans, fmap1, f2_levels, coords = _served_case(
        model, grid, kind, dtype)
    (h, w), _ = GRIDS[grid]
    assert plans[0].banded and (plans[0].qp != h * w) == (grid == "hd")
    assert plans[1].banded and plans[1].pack == (2 if grid == "sintel" else 1)
    sched = lookup_schedules(coords, level_shapes(f2_levels), radius,
                             q_blk=128, p_blk_target=p_blk)
    bands = _tile_bands(sched[0], plans[0])
    s_last = (plans[0].rows - 1) // plans[0].band_granule
    if kind == "rest":
        assert bands.max() == 1                       # the mechanism working
    elif kind == "two-bands":
        assert (bands >= 2).mean() > 0.7      # (the map's edges clip some)
    elif kind == "last-granule":
        assert (np.asarray(sched[0]) == s_last).all()
    run = lambda s: np.asarray(_fused_lookup_impl(        # noqa: E731
        fmap1, f2_levels, coords, radius, q_blk=128, p_blk_target=p_blk,
        interpret=True, schedules=s, out_dtype=out))
    got, whole = run(sched), run((None, None))
    bits = np.uint16 if out == BF16 else np.uint32
    np.testing.assert_array_equal(got.view(bits), whole.view(bits))
    n = (2 * radius + 1) ** 2
    got = got.astype(np.float32).reshape(h, w, 2 * n)
    if kind == "edges":
        assert not got[:, : 2 * w // 3].any()         # wholly off the map
        assert np.abs(got[:, 2 * w // 3:]).max() > 0.1
    elif kind == "last-granule":
        # window row 0 is the map's last row, the others lie under the map
        by_row = got[..., :n].reshape(h, w, -1, 2 * radius + 1)   # [.., i, j]
        assert np.abs(by_row[..., 0]).max() > 0.1
        assert not by_row[..., 2:].any()
    else:
        assert np.abs(got).max() > 0.1


# --------------------- rows that share their 128 lanes (PR 43), one launch

#: map columns -> (stored lanes, map rows to a 128-lane row): every width the
#: served grids' levels have (240, 120, 60, 30 at 1080x1920; 128, 64, 32, 16
#: at 440x1024)
PACKED_WIDTHS = {16: (16, 8), 30: (32, 4), 32: (32, 4), 60: (64, 2),
                 64: (64, 2), 120: (128, 1), 128: (128, 1), 240: (256, 1)}


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("w2", list(PACKED_WIDTHS))
@pytest.mark.parametrize("model", list(MODELS))
def test_packed_rows_equal_the_all_rows_walk_and_the_dense_lookup(model, w2,
                                                                  dtype):
    """One launch over a map of 37 rows of ``w2`` columns, for raft-things'
    and RAFT-S's radius and channels, float32 maps and bfloat16 ones (three
    planes, as a pooled level's): rows of 16, 32 and 64 lanes lie eight,
    four and two to a 128-lane row of the planes and one gather a row
    serves them all.  Queries lie over the whole map and past each of its
    four sides, and their rows are rough enough for a second and a third
    band.  The banded launch equals the walk over every row-block bit for
    bit (every tap lies in one band), and ``ops.corr.lookup_dense`` on the
    same values to float32 round-off."""
    from raft_tpu.ops.corr import dense_corr, lookup_dense

    radius, c = MODELS[model]
    lanes, pack = PACKED_WIDTHS[w2]
    h2, (H, W) = 37, (4, 128)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(43), 3)
    fmap1 = jax.random.normal(k1, (1, H, W, c), dtype)
    f2 = jax.random.normal(k2, (1, h2, w2, c), F32)
    # columns from 8 left of the map to 8 right of it, rows from 8 above to
    # 8 below; every second query a further 0-19 rows down: a tile's
    # windows span up to three bands of 16
    x = jnp.linspace(-8.0, w2 + 8.0, W)[None, :] + jnp.zeros((H, 1))
    y = jnp.linspace(-8.0, h2 + 8.0, H)[:, None] + jnp.zeros((1, W))
    rough = jax.random.uniform(k3, (H, W, 2), minval=0.0, maxval=1.0)
    y = y + jnp.where(jnp.arange(W)[None, :] % 2 == 0, 0.0,
                      19.0 * rough[..., 1])
    coords = jnp.stack([x + rough[..., 0], y], -1)[None]
    cf = coords.reshape(1, H * W, 2)
    kw = dict(q_blk=128, p_blk_target=4096, grid_w=W)
    plan = corr_level_plan(H * W, h2, w2, radius=radius, **kw)
    assert (plan.w2p, plan.pack) == (lanes, pack)
    # a band starts on whole 128-lane rows: a granule of 8 map rows at 16
    # lanes, where 7 rows of rounding make the band 24 rows
    assert plan.band_granule == max(4, pack)
    assert plan.banded and (plan.band_rows, plan.n_bands) == (
        (24, 2) if pack == 8 else (16, 3))
    S = level_schedule(cf, plan, 0, radius)
    assert _tile_bands(S, plan).max() >= 2            # a second band
    run = lambda s: np.asarray(_lookup_level(         # noqa: E731
        fmap1.reshape(1, H * W, c), f2, cf, radius, 0, interpret=True,
        schedule=s, **kw))
    got, whole = run(S), run(None)
    np.testing.assert_array_equal(got.view(np.uint32), whole.view(np.uint32))
    want = np.asarray(lookup_dense(
        [dense_corr(fmap1.astype(F32), f2,
                    precision=jax.lax.Precision.HIGHEST)], coords, radius))
    np.testing.assert_allclose(got.reshape(want.shape), want, rtol=0,
                               atol=1.5e-6 * np.abs(want).max())
    n = 2 * radius + 1
    by_q = got.reshape(H, W, n, n)                    # [.., x offset, y]
    assert not by_q[:, 0, :radius].any()      # columns left of the map
    assert not by_q[:, -1, -radius:].any()    # and right of it
    assert not by_q[0, ::2, :, :radius].any()         # rows above it
    assert np.abs(by_q[1:3, 8:-8]).max() > 0.1


# ------------------------------ the grid's third dimension: K' = 1 + max(more)

#: the served grids themselves (1080x1920 and 440x1024 at 1/8): the key map
#: whole, so level 0 has the cells' own ``K`` (9 and 4).  ``strip``: the
#: query rows a kernel case runs (first row, rows), tile-aligned in the whole
#: grid (64 x 240 = 120 x 128), or None for all of them: interpret mode takes
#: 30 ms a grid step at 256 lanes, and a launch's steps go with its tiles.
SERVED = {"135x240": ((135, 240), (64, 6)), "55x128": ((55, 128), None)}
#: the query tile of batch row 1 that the flow singles out (its first query)
SPECIAL = {"135x240": 124 * 128, "55x128": 27 * 128}
SHORT_GRID_KINDS = ["smooth", "one-wide-tile", "outside", "all-bands"]


def served_flow(kind, grid, B=2):
    """Coordinates ``[B, h, w, 2]`` at a served grid for the four cases of
    a launch's step count: (i) ``smooth``, under half a cell everywhere (one
    band a tile: ``K'`` = 1); and the same with ONE tile of batch row 1
    (``SPECIAL``) (ii) pulled 14 rows up and down (``one-wide-tile``: three
    bands, every other tile one), (iii) wholly above the map (``outside``)
    or (iv) from the map's first row to its last (``all-bands``: ``K'`` =
    ``K``)."""
    (h, w), _ = SERVED[grid]
    base = coords_grid(B, h, w)
    x, y = base[..., 0], base[..., 1]
    cf = (base + 0.4 * jnp.stack([jnp.sin(x / 9.0 + y / 7.0),
                                  jnp.cos(x / 8.0 - y / 6.0)], -1)
          ).reshape(B, h * w, 2)
    q = SPECIAL[grid] + jnp.arange(128)
    if kind == "one-wide-tile":
        cf = cf.at[1, q, 1].add(jnp.where(q % 2 == 0, -14.0, 14.5))
    elif kind == "outside":
        cf = cf.at[1, q, 1].set(-(h + 20.5))
    elif kind == "all-bands":
        cf = cf.at[1, q, 1].set(jnp.where(q % 2 == 0, 0.25, h - 1.0))
    else:
        assert kind == "smooth"
    return cf.reshape(B, h, w, 2)


@pytest.mark.parametrize("kind", SHORT_GRID_KINDS)
@pytest.mark.parametrize("grid", list(SERVED))
@pytest.mark.parametrize("model", list(MODELS))
def test_short_grid_launch_equals_the_k_step_launch(model, grid, kind,
                                                    monkeypatch):
    """Level 0 of the served grids (``K`` = 9 and 4) at both models'
    channels and radius, bfloat16 maps and windows as served: the launch
    whose third grid dimension is ``schedule_steps`` (the bands of the tile
    that needs most) equals the launch of ``K`` steps a tile (the parent's
    grid, here by making ``schedule_steps`` say ``K``) and the all-rows
    walk, bit for bit, with ``K'`` = 1, 3 (one wide tile in one batch row),
    1 (a tile wholly outside the map) and ``K``."""
    radius, c = MODELS[model]
    (h, w), strip = SERVED[grid]
    row0, rows = strip or (0, h)
    B, Q = 2, rows * w
    coords = served_flow(kind, grid, B)[:, row0:row0 + rows].reshape(B, Q, 2)
    special = slice(SPECIAL[grid] - row0 * w, SPECIAL[grid] - row0 * w + 128)
    k1, k2 = jax.random.split(jax.random.PRNGKey(38))
    f1 = jax.random.normal(k1, (B, Q, c), BF16)
    f2 = jax.random.normal(k2, (B, h, w, c), BF16)
    kw = dict(q_blk=128, p_blk_target=4096, grid_w=w)
    plan = corr_level_plan(Q, h, w, radius=radius, **kw)
    assert plan.n_bands == {"135x240": 9, "55x128": 4}[grid]
    S = level_schedule(coords, plan, 0, radius)
    bands = _tile_bands(S, plan)
    wide = {"smooth": 1, "one-wide-tile": 3, "outside": 1,
            "all-bands": plan.n_bands}[kind]
    assert int(schedule_steps(S, plan)) == bands.max() == wide
    # the special tile alone is wide: every other tile needs one band
    assert (bands > 1).sum() == (wide > 1)
    assert bands[1, special.start // 128] == wide

    def launch(schedule):
        return np.asarray(_lookup_level(
            f1, f2, coords, radius, 0, interpret=True, schedule=schedule,
            out_dtype=BF16, **kw)).view(np.uint16)

    short, whole = launch(S), launch(None)
    monkeypatch.setattr(corr_pallas, "schedule_steps",
                        lambda S, plan: plan.n_bands)
    np.testing.assert_array_equal(short, launch(S))
    np.testing.assert_array_equal(short, whole)
    assert short[0].any() and short[1, :special.start].any()
    if kind == "outside":
        assert not short[1, special].any()              # +0.0, all of it
    else:
        assert short[1, special].any()


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_the_ragged_launch_keeps_its_pages(dtype):
    """The ragged launch reads the plan's fixed row-blocks as its pages and
    the shared body takes the page's first row: with every item at the box
    its output is the dense all-rows launch's, bit for bit — what it was
    before the dense launches took bands."""
    B, H, W, C, RADIUS = 2, 30, 44, 32, 4       # 8 pages of four rows of 64
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    fmap1 = jax.random.normal(k1, (B, H, W, C), dtype)
    fmap2 = jax.random.normal(k2, (B, H, W, C), dtype)
    coords = jax.random.uniform(k3, (B, H * W, 2), minval=-8.0,
                                maxval=1.2 * W)
    sizes = jnp.array([[H, W]] * B, jnp.int32)
    kw = dict(q_blk=128, p_blk_target=256, interpret=True, grid_w=W)
    got = _ragged_lookup_level(
        mask_ragged_rows(fmap1, sizes).reshape(B, H * W, C),
        mask_ragged_rows(fmap2, sizes), coords, jnp.ones((B, H * W), bool),
        sizes[:, 0], RADIUS, 0, **kw)
    want = _lookup_level(fmap1.reshape(B, H * W, C), fmap2, coords, RADIUS,
                         0, **kw)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))
