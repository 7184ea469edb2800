"""The lookup kernel's key-block schedule: the rule that gives a level one
(``kernel_plans.corr_level_scheduled``, read from the level's block plan
alone), its values against the all-blocks walk (bit for bit: a skipped block
added exact zeros, the visited ones keep their order), its counts, and the
whole model under it against the benchmark's plain reference.  The kernel
runs in Pallas interpret mode, small ``p_blk_target``s standing in for the
many row-blocks of a large frame."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.kernel_plans import corr_level_plan, corr_level_scheduled
from raft_tpu.ops.coords import coords_grid
from raft_tpu.ops.corr import build_pyramid, fmap2_pyramid, lookup_dense
from raft_tpu.ops.corr_pallas import (_fused_lookup_impl, _lookup_level,
                                      level_schedule, level_shapes,
                                      lookup_schedules, schedule_keyblocks)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BF16, F32 = jnp.bfloat16, jnp.float32
RADIUS = 4


# ------------------------------------------------------------ the shape rule

def _levels_scheduled(h, w, q_blk=128, p_blk=4096, levels=4, radius=RADIUS):
    out, (h2, w2) = [], (h, w)
    for lvl in range(levels):
        plan = corr_level_plan(h * w, h2, w2, q_blk=q_blk, p_blk_target=p_blk)
        out.append((plan.n_pblocks, corr_level_scheduled(plan)))
        h2, w2 = h2 // 2, w2 // 2
    return out


@pytest.mark.parametrize("grid,blocks,scheduled", [
    # 440x1024: level 0 is two blocks of 32 rows, the pooled levels one each
    ((55, 128), (2, 1, 1, 1), (True, False, False, False)),
    # 1080x1920: nine blocks of 16 rows x 256 lanes at level 0, three of
    # 32 x 128 at level 1, two at level 2 (33 rows: the second holds one),
    # one at level 3
    ((135, 240), (9, 3, 2, 1), (True, True, True, False)),
    # 4K: the rule needs no new case
    ((270, 480), (34, 9, 3, 2), (True, True, True, True)),
    # a training crop (368x496): 46 rows of 128 lanes are two blocks too
    ((46, 62), (2, 1, 1, 1), (True, False, False, False)),
    # thumbnails (128x160): one block a level, no schedule anywhere
    ((16, 20), (1, 1, 1, 1), (False,) * 4),
])
def test_rule_reads_the_plan_alone(grid, blocks, scheduled):
    got = _levels_scheduled(*grid)
    assert tuple(g[0] for g in got) == blocks
    assert tuple(g[1] for g in got) == scheduled


@pytest.mark.parametrize("p_blk,scheduled", [
    (4096, (True, False, False, False)),
    (1024, (True, True, True, False)),      # 7, 4, 2, 1 blocks of 8 rows
    (256, (True, True, True, True)),
])
def test_finer_blocks_bring_the_schedule_to_sintels_grid(p_blk, scheduled):
    """What ``pallas_p_select='window'`` used to ask for by name (with a
    ``pallas_p_blk`` fine enough to have something to skip) now follows from
    the block size alone."""
    got = _levels_scheduled(55, 128, p_blk=p_blk)
    assert tuple(g[1] for g in got) == scheduled


def test_lookup_schedules_follows_the_rule():
    B, H, W, C = 1, 24, 40, 8
    f2_levels = fmap2_pyramid(jnp.zeros((B, H, W, C)), 4)
    sched = lookup_schedules(coords_grid(B, H, W), level_shapes(f2_levels),
                             RADIUS, q_blk=64, p_blk_target=128)
    want = [g[1] for g in _levels_scheduled(H, W, q_blk=64, p_blk=128)]
    assert [s is not None for s in sched] == want == [True] * 4
    plan = corr_level_plan(H * W, H, W, q_blk=64, p_blk_target=128)
    assert sched[0].shape == (B, plan.qp // plan.t, plan.n_pblocks)
    # one block a level at the default plan of so small a grid
    assert lookup_schedules(coords_grid(B, H, W), level_shapes(f2_levels),
                            RADIUS) == (None,) * 4


# ------------------------------------------- scheduled == all blocks, bitwise

H, W, C = 30, 44, 32            # Q = 1320: a ragged tail tile at q_blk 128
P_BLK = 256                     # level 0: 15 blocks of 2 rows; level 1: 8


def _flow_field(kind: str, B: int) -> jax.Array:
    """Coordinates [B, H, W, 2] for each case of the issue's list."""
    base = coords_grid(B, H, W)
    if kind == "zero":
        return base
    if kind == "three-blocks":
        # the tile's rows move apart: its windows lie across three and more
        # of the 2-row blocks
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


@pytest.mark.parametrize("out", [F32, BF16], ids=["out-f32", "out-bf16"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["zero", "three-blocks", "outside",
                                  "random"])
def test_scheduled_lookup_equals_all_blocks_bit_for_bit(kind, dtype, out):
    """At a grid with 15 key row-blocks at level 0 and 8 at level 1, Q not a
    multiple of the tile: the rule's program (every level scheduled) equals
    the all-blocks program bit for bit, and the reference's lookup
    (``lookup_dense`` on the same values) to ``tests/test_corr_pallas.py``'s
    tolerance.  Written in bfloat16 (``out``), both are the float32 result
    rounded once: the write happens at a tile's last grid step, which under
    a schedule is nearly always a repeated entry that skips the compute."""
    B = 2
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    fmap1 = jax.random.normal(k1, (B, H, W, C), dtype)
    fmap2 = jax.random.normal(k2, (B, H, W, C), dtype)
    coords = _flow_field(kind, B)
    f2_levels = [fmap2] + fmap2_pyramid(fmap2.astype(F32), 4)[1:]
    sched = lookup_schedules(coords, level_shapes(f2_levels), RADIUS,
                             q_blk=128, p_blk_target=P_BLK)
    assert [s is not None for s in sched] == [True] * 4
    plan0 = corr_level_plan(H * W, H, W, q_blk=128, p_blk_target=P_BLK)
    assert plan0.n_pblocks >= 4 and plan0.qp != H * W
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
        assert (S[..., -1] - S[..., 0] + 1).max() >= 3
    if kind == "outside":
        assert np.abs(got[:, :, : W // 2]).max() == 0.0   # wholly outside


def test_keyblock_counts_are_the_schedules_distinct_blocks():
    """``schedule_keyblocks`` against a count made by hand from the window
    rows: per tile the blocks between its lowest and highest touched row."""
    B = 2
    coords = _flow_field("three-blocks", B)
    shapes = [(H, W), (H // 2, W // 2), (H // 4, W // 4), (H // 8, W // 8)]
    sched = lookup_schedules(coords, shapes, RADIUS, q_blk=128,
                             p_blk_target=P_BLK)
    visited, possible = (int(v) for v in schedule_keyblocks(
        sched, B, H * W, shapes, q_blk=128, p_blk_target=P_BLK))
    want_v = want_p = 0
    cf = np.asarray(coords).reshape(B, H * W, 2)
    for lvl, (h2, w2) in enumerate(shapes):
        plan = corr_level_plan(H * W, h2, w2, q_blk=128, p_blk_target=P_BLK)
        tiles = plan.qp // plan.t
        want_p += B * tiles * plan.n_pblocks
        if sched[lvl] is None:
            want_v += B * tiles * plan.n_pblocks
            continue
        cy = np.pad(cf[..., 1], ((0, 0), (0, plan.qp - H * W)), mode="edge")
        top = np.floor(cy / 2 ** lvl).astype(int).reshape(B, tiles, -1) - 4
        lo, hi = top.min(-1), top.max(-1) + 9
        for b in range(B):
            for j in range(tiles):
                if hi[b, j] < 0 or lo[b, j] >= h2:
                    want_v += 1                     # parked on block 0
                    continue
                rows = np.clip([lo[b, j], hi[b, j]], 0, h2 - 1)
                want_v += int(rows[1] // plan.h2_blk
                              - rows[0] // plan.h2_blk + 1)
    assert (visited, possible) == (want_v, want_p)
    assert visited < possible


def test_a_schedule_of_another_plan_is_refused():
    f1 = jnp.zeros((1, H * W, C))
    coords = coords_grid(1, H, W).reshape(1, H * W, 2)
    plan = corr_level_plan(H * W, H, W, q_blk=128, p_blk_target=512)
    with pytest.raises(ValueError, match="schedule"):
        _lookup_level(f1, jnp.zeros((1, H, W, C)), coords, RADIUS, 0,
                      q_blk=128, p_blk_target=P_BLK, interpret=True,
                      schedule=level_schedule(coords, plan, H, 0, RADIUS))


# --------------------------------- the whole model against the plain reference

def test_model_under_the_schedule_agrees_with_the_benchmarks_reference():
    """raft-things at 216x384 (16:9; a 27x48 grid), batch 2, 4 updates,
    float32, ``corr_impl=pallas`` in interpret mode with every level under
    the key-block schedule, on the benchmark's seeded weights, against
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
    assert [g[1] for g in _levels_scheduled(27, 48, q_blk=64, p_blk=128)] \
        == [True] * 4
    pairs = inputs.make_pairs(26, 2, 216, 384, 4)
    im1, im2 = (jnp.asarray(np.stack([p[i] for p in pairs])
                            / np.float32(255)) for i in (0, 1))
    out, _ = jax.jit(lambda w, a, b: raft_forward(w, a, b, pcfg))(wts, im1,
                                                                  im2)
    visited, possible = (int(v) for v in out.corr_keyblocks)
    assert 0 < visited < 0.7 * possible
    for i, (a, b) in enumerate(pairs):
        ref = np.asarray(reference.flow(wts, a, b, mcfg, 4))
        assert check.rel_epe(np.asarray(out.flow[i]), ref) < 1e-4
