"""Multi-device tests on the 8-virtual-CPU-device mesh (SURVEY.md §4): DP
train step equivalence vs single device, halo-exchange convs, distributed
blockwise correlation, pjit spatial inference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.models import init_raft
from raft_tpu.models.raft import make_inference_fn
from raft_tpu.ops import build_pyramid, conv2d, coords_grid, lookup_dense
from raft_tpu.parallel import (SPATIAL_AXIS, compat_shard_map,
                               conv2d_row_sharded, halo_exchange,
                               make_dp_eval_fn, make_dp_train_step, make_mesh,
                               make_spatial_corr_lookup,
                               make_spatial_inference_fn, shard_batch)
from raft_tpu.training import Batch, TrainState, make_optimizer, make_train_step


def test_eight_devices_available():
    assert len(jax.devices()) == 8, jax.devices()


def _batch(B=8, H=48, W=64, seed=0):
    rng = np.random.RandomState(seed)
    return Batch(
        image1=jnp.asarray(rng.rand(B, H, W, 3), jnp.float32),
        image2=jnp.asarray(rng.rand(B, H, W, 3), jnp.float32),
        flow=jnp.asarray(rng.randn(B, H, W, 2) * 2, jnp.float32),
        valid=jnp.ones((B, H, W), jnp.float32))


@pytest.mark.slow
def test_dp_train_step_matches_single_device():
    config = RAFTConfig.small_model(iters=2)
    tconfig = TrainConfig(num_steps=10, lr=1e-4, schedule="constant",
                          optimizer="sgd")   # sgd: exactly linear in grads
    tx = make_optimizer(tconfig)
    state = TrainState.create(init_raft(jax.random.PRNGKey(0), config), tx)
    batch = _batch()
    rng = jax.random.PRNGKey(1)

    single = jax.jit(make_train_step(config, tconfig, tx))
    s1, m1 = single(state, batch, rng)

    mesh = make_mesh()
    dp = make_dp_train_step(config, tconfig, tx, mesh)
    sharded = shard_batch(mesh, batch)
    # dp donates (consumes) its input state; give it its own copy since
    # `state` is compared against afterwards via s1
    state_dp = jax.tree.map(jnp.copy, state)
    s8, m8 = dp(state_dp, sharded, rng)

    # pmean of per-shard grads == global grad (equal shard sizes, mean loss)
    np.testing.assert_allclose(float(m1["loss"]), float(m8["loss"]), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s8.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4)


@pytest.mark.slow
def test_dp_train_step_donate_opt_out():
    """donate=False restores the pre-donation contract: the input state stays
    alive after the step (readable, no 'Array has been deleted'), and the
    update matches the donating path."""
    config = RAFTConfig.small_model(iters=2)
    tconfig = TrainConfig(num_steps=10, lr=1e-4, schedule="constant",
                          optimizer="sgd")
    tx = make_optimizer(tconfig)
    state = TrainState.create(init_raft(jax.random.PRNGKey(0), config), tx)
    batch = _batch()
    rng = jax.random.PRNGKey(1)
    mesh = make_mesh()
    sharded = shard_batch(mesh, batch)

    step = make_dp_train_step(config, tconfig, tx, mesh, donate=False)
    s_new, _ = step(state, sharded, rng)
    # old state must still be materializable — with donation this raises
    for leaf in jax.tree.leaves(state.params):
        np.asarray(leaf)
    # and the non-donating step computes the same update
    donating = make_dp_train_step(config, tconfig, tx, mesh)
    s_don, _ = donating(jax.tree.map(jnp.copy, state), sharded, rng)
    for a, b in zip(jax.tree.leaves(s_new.params),
                    jax.tree.leaves(s_don.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.slow
def test_dp_train_step_composes_with_accumulation():
    """accum_steps inside the DP shard_map splits each DEVICE's slice: the
    update must match the plain DP step (equal valid counts, SGD)."""
    config = RAFTConfig.small_model(iters=2)
    base = dict(num_steps=10, lr=1e-4, schedule="constant", optimizer="sgd")
    tconfig = TrainConfig(**base)
    t_acc = TrainConfig(accum_steps=2, **base)
    tx = make_optimizer(tconfig)
    state = TrainState.create(init_raft(jax.random.PRNGKey(0), config), tx)
    batch = _batch(B=16)                  # 2 per device on the 8-dev mesh
    rng = jax.random.PRNGKey(1)
    mesh = make_mesh()
    sharded = shard_batch(mesh, batch)

    s_plain, m_plain = make_dp_train_step(config, tconfig, tx, mesh)(
        jax.tree.map(jnp.copy, state), sharded, rng)
    s_acc, m_acc = make_dp_train_step(config, t_acc, tx, mesh)(
        jax.tree.map(jnp.copy, state), sharded, rng)
    np.testing.assert_allclose(float(m_acc["loss"]), float(m_plain["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s_acc.params),
                    jax.tree.leaves(s_plain.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-4)


def test_dp_eval_fn():
    config = RAFTConfig.small_model(iters=2)
    params = init_raft(jax.random.PRNGKey(0), config)
    mesh = make_mesh()
    fn = make_dp_eval_fn(config, mesh)
    batch = _batch()
    flow = fn(params, batch.image1, batch.image2)
    assert flow.shape == (8, 48, 64, 2)
    want = jax.jit(make_inference_fn(config, iters=2))(
        params, batch.image1, batch.image2)
    np.testing.assert_allclose(np.asarray(flow), np.asarray(want),
                               atol=2e-2, rtol=1e-3)


def test_halo_exchange_matches_full_conv():
    """Row-sharded conv with halo exchange == unsharded torch-padding conv."""
    rng = np.random.RandomState(0)
    B, H, W, C = 2, 32, 16, 4
    x = jnp.asarray(rng.randn(B, H, W, C), jnp.float32)
    w = jnp.asarray(rng.randn(5, 5, C, 8), jnp.float32)
    want = conv2d(x, w)

    mesh = make_mesh(axes=(SPATIAL_AXIS,))
    f = compat_shard_map(
        lambda xl: conv2d_row_sharded(xl, w),
        mesh=mesh, in_specs=P(None, SPATIAL_AXIS),
        out_specs=P(None, SPATIAL_AXIS))
    got = jax.jit(f)(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_spatial_corr_lookup_matches_dense():
    rng = np.random.RandomState(1)
    B, H, W, C = 1, 16, 12, 32
    f1 = jnp.asarray(rng.randn(B, H, W, C), jnp.float32)
    f2 = jnp.asarray(rng.randn(B, H, W, C), jnp.float32)
    coords = coords_grid(B, H, W) + jnp.asarray(
        rng.uniform(-3, 3, (B, H, W, 2)), jnp.float32)
    radius, levels = 3, 2
    want = lookup_dense(build_pyramid(f1, f2, levels), coords, radius)

    mesh = make_mesh(axes=(SPATIAL_AXIS,))
    fn = make_spatial_corr_lookup(mesh, levels, radius)
    got = fn(f1, f2, coords)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_spatial_inference_pjit():
    """Whole model with row-sharded images via jit sharding annotations:
    XLA SPMD must produce the same flow as single-device."""
    config = RAFTConfig.small_model(iters=2)
    params = init_raft(jax.random.PRNGKey(0), config)
    rng = np.random.RandomState(2)
    im1 = jnp.asarray(rng.rand(1, 64, 64, 3), jnp.float32)
    im2 = jnp.asarray(rng.rand(1, 64, 64, 3), jnp.float32)
    want = jax.jit(make_inference_fn(config))(params, im1, im2)

    mesh = make_mesh(axes=(SPATIAL_AXIS,))
    fn = make_spatial_inference_fn(config, mesh)
    got = fn(params, im1, im2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=1e-3)


def test_dp_requires_divisible_batch():
    config = RAFTConfig.small_model(iters=2)
    mesh = make_mesh()
    fn = make_dp_eval_fn(config, mesh)
    params = init_raft(jax.random.PRNGKey(0), config)
    b = _batch(B=5)
    with pytest.raises(Exception):
        fn(params, b.image1, b.image2)


def test_ring_corr_lookup_matches_dense():
    """Ring-pass correlation (ppermute accumulation of one-hot partial
    lookups) must equal the single-device dense lookup."""
    from raft_tpu.parallel import make_ring_corr_lookup

    rng = np.random.RandomState(3)
    B, H, W, C = 1, 32, 12, 16         # H/8-slab analog: 32 rows over 8 devs
    f1 = jnp.asarray(rng.randn(B, H, W, C), jnp.float32)
    f2 = jnp.asarray(rng.randn(B, H, W, C), jnp.float32)
    coords = coords_grid(B, H, W) + jnp.asarray(
        rng.uniform(-5, 5, (B, H, W, 2)), jnp.float32)
    radius, levels = 3, 2              # slab 4 rows, level-1 pool shard-local
    want = lookup_dense(build_pyramid(f1, f2, levels), coords, radius)

    mesh = make_mesh(axes=(SPATIAL_AXIS,))
    fn = make_ring_corr_lookup(mesh, levels, radius)
    got = fn(f1, f2, coords)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_onehot_lookup_matches_gather_lookup():
    from raft_tpu.ops import lookup_dense_onehot

    rng = np.random.RandomState(4)
    B, H, W, C = 2, 14, 10, 16
    f1 = jnp.asarray(rng.randn(B, H, W, C), jnp.float32)
    f2 = jnp.asarray(rng.randn(B, H, W, C), jnp.float32)
    coords = coords_grid(B, H, W) + jnp.asarray(
        rng.uniform(-20, 20, (B, H, W, 2)), jnp.float32)
    pyramid = build_pyramid(f1, f2, 3)
    want = lookup_dense(pyramid, coords, 4)
    got = lookup_dense_onehot(pyramid, coords, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("small", [True, False])
def test_shard_inference_matches_single_device(small):
    """Whole model row-sharded via shard_map (halo convs, psum'd instance
    norm, ring correlation, sharded upsampling) must equal the single-device
    forward for both variants."""
    from raft_tpu.parallel import make_shard_inference_fn

    config = (RAFTConfig.small_model(iters=2) if small
              else RAFTConfig.full(iters=2))
    params = init_raft(jax.random.PRNGKey(0), config)
    rng = np.random.RandomState(5)
    # H divisible by 8 * n_dev * 2^(levels-1) = 8*4*8
    im1 = jnp.asarray(rng.rand(1, 256, 48, 3), jnp.float32)
    im2 = jnp.asarray(rng.rand(1, 256, 48, 3), jnp.float32)
    want = jax.jit(make_inference_fn(config))(params, im1, im2)

    mesh = make_mesh(axes=(SPATIAL_AXIS,), shape=(4,),
                     devices=jax.devices()[:4])
    fn = make_shard_inference_fn(config, mesh)
    got = fn(params, im1, im2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=1e-3)


def test_shard_inference_ctx_hoist_matches_single_device():
    """gru_ctx_hoist composes with row-sharding: the precompute convs run on
    sharded `inp` (halo exchanges for the 5x1/3x3 gate kernels) and must
    still match the unsharded plain forward."""
    from raft_tpu.parallel import make_shard_inference_fn

    plain = RAFTConfig.small_model(iters=2, gru_ctx_hoist=False)
    hoisted = RAFTConfig.small_model(iters=2, gru_ctx_hoist=True)
    params = init_raft(jax.random.PRNGKey(0), plain)
    rng = np.random.RandomState(5)
    im1 = jnp.asarray(rng.rand(1, 256, 48, 3), jnp.float32)
    im2 = jnp.asarray(rng.rand(1, 256, 48, 3), jnp.float32)
    want = jax.jit(make_inference_fn(plain))(params, im1, im2)

    mesh = make_mesh(axes=(SPATIAL_AXIS,), shape=(4,),
                     devices=jax.devices()[:4])
    got = make_shard_inference_fn(hoisted, mesh)(params, im1, im2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=1e-3)


def test_shard_inference_halo_wider_than_slab():
    """Tiny slabs (2 rows at 1/8 res) force the 7x7 conv's halo (3) past the
    neighbor exchange — the all_gather fallback must keep exact parity."""
    import dataclasses

    from raft_tpu.parallel import make_shard_inference_fn

    config = dataclasses.replace(RAFTConfig.full(iters=2), corr_levels=2)
    params = init_raft(jax.random.PRNGKey(1), config)
    rng = np.random.RandomState(6)
    im1 = jnp.asarray(rng.rand(1, 128, 32, 3), jnp.float32)  # 8*8dev*2^1
    im2 = jnp.asarray(rng.rand(1, 128, 32, 3), jnp.float32)
    want = jax.jit(make_inference_fn(config))(params, im1, im2)

    mesh = make_mesh(axes=(SPATIAL_AXIS,))
    got = make_shard_inference_fn(config, mesh)(params, im1, im2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=1e-3)


def test_ring_lookup_via_fused_kernel_matches_dense():
    """The ring pass riding the fused Pallas kernel per slab (global coords
    shifted by the slab start row; window schedule on) must equal the
    single-device dense lookup — the sequence-parallel path and
    the first-party kernel composing."""
    from jax.sharding import Mesh, PartitionSpec as P

    from raft_tpu.parallel.spatial import make_ring_lookup_local

    rng = np.random.RandomState(5)
    B, H, W, C, levels, radius = 1, 16, 12, 16, 2, 3
    f1 = jnp.asarray(rng.randn(B, H, W, C), jnp.float32)
    f2 = jnp.asarray(rng.randn(B, H, W, C), jnp.float32)
    coords = coords_grid(B, H, W) + jnp.asarray(
        rng.uniform(-4, 4, (B, H, W, 2)), jnp.float32)
    want = lookup_dense(
        build_pyramid(f1, f2, levels, precision=jax.lax.Precision.HIGHEST),
        coords, radius)

    mesh = Mesh(np.array(jax.devices()[:4]), (SPATIAL_AXIS,))

    def inner(f1l, f2l, cl):
        lk = make_ring_lookup_local(
            f1l, f2l, levels, radius, SPATIAL_AXIS,
            precision=jax.lax.Precision.HIGHEST, kernel="pallas",
            # 256: each 4-row slab is two row-blocks at level 0, so the
            # slab's launch runs under a key-block schedule
            pallas_opts=dict(q_blk=64, p_blk_target=256))
        return lk(cl)

    f = jax.jit(compat_shard_map(
        inner, mesh=mesh,
        in_specs=(P(None, SPATIAL_AXIS), P(None, SPATIAL_AXIS),
                  P(None, SPATIAL_AXIS)),
        out_specs=P(None, SPATIAL_AXIS)))
    got = np.asarray(f(f1, f2, coords)).reshape(np.asarray(want).shape)
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_shard_inference_pallas_matches_single_device():
    """Whole-model row-sharded inference with corr_impl='pallas': the ring
    pass rides the fused kernel and must match the unsharded model."""
    from raft_tpu.parallel.spatial import make_shard_inference_fn

    cfg = RAFTConfig.full(iters=2, corr_levels=2, corr_impl="pallas",
                          pallas_p_blk=1024)
    params = init_raft(jax.random.PRNGKey(0), cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    im1 = jax.random.uniform(k1, (1, 64, 48, 3))
    im2 = jax.random.uniform(k2, (1, 64, 48, 3))
    from raft_tpu.models.raft import raft_forward
    want, _ = raft_forward(params, im1, im2, cfg)

    mesh = make_mesh(axes=(SPATIAL_AXIS,),
                     shape=(2,), devices=jax.devices()[:2])
    got = make_shard_inference_fn(cfg, mesh)(params, im1, im2)
    scale = np.abs(np.asarray(want.flow)).mean()
    diff = np.abs(np.asarray(got) - np.asarray(want.flow)).max()
    assert diff / scale < 1e-3, (diff, scale)
