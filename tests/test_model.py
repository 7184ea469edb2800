"""Model-level tests: shapes, jit, free batch/resolution, scan-vs-unroll
equivalence, training-mode outputs (SURVEY.md §4 strategy)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.config import RAFTConfig
from raft_tpu.models import init_raft, raft_forward
from raft_tpu.models.raft import make_inference_fn


def _params_and_images(config, B=1, H=64, W=96, seed=0):
    key = jax.random.PRNGKey(seed)
    params = init_raft(key, config)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    im1 = jax.random.uniform(k1, (B, H, W, 3))
    im2 = jax.random.uniform(k2, (B, H, W, 3))
    return params, im1, im2


@pytest.mark.parametrize("small", [False, True])
def test_forward_shapes(small):
    config = RAFTConfig.small_model(iters=3) if small else RAFTConfig.full(iters=3)
    params, im1, im2 = _params_and_images(config)
    out, _ = raft_forward(params, im1, im2, config)
    assert out.flow.shape == (1, 64, 96, 2)
    assert out.flow_lr.shape == (1, 8, 12, 2)
    assert out.flow_iters is None
    assert np.all(np.isfinite(np.asarray(out.flow)))


def test_param_count_full():
    """Official RAFT: 5.3M params (full), ~1.0M (small) — BASELINE.md."""
    config = RAFTConfig.full()
    params = init_raft(jax.random.PRNGKey(0), config)
    trainable = sum(x.size for x in jax.tree.leaves(params))
    # running BN stats included; subtract them for the trainable count
    assert 5.1e6 < trainable < 5.5e6, trainable

    small = init_raft(jax.random.PRNGKey(0), RAFTConfig.small_model())
    n_small = sum(x.size for x in jax.tree.leaves(small))
    assert 0.9e6 < n_small < 1.1e6, n_small


@pytest.mark.slow
def test_free_batch_and_resolution():
    config = RAFTConfig.small_model(iters=2)
    params, im1, im2 = _params_and_images(config, B=2, H=48, W=64)
    out, _ = raft_forward(params, im1, im2, config)
    assert out.flow.shape == (2, 48, 64, 2)
    _, im1b, im2b = _params_and_images(config, B=3, H=64, W=48)
    out2, _ = raft_forward(params, im1b, im2b, config)
    assert out2.flow.shape == (3, 64, 48, 2)


def test_jit_and_iters_override():
    config = RAFTConfig.full(iters=2)
    params, im1, im2 = _params_and_images(config)
    fn = jax.jit(make_inference_fn(config))
    flow = fn(params, im1, im2)
    assert flow.shape == (1, 64, 96, 2)

    out4, _ = raft_forward(params, im1, im2, config, iters=4)
    out2, _ = raft_forward(params, im1, im2, config, iters=2)
    assert not np.allclose(np.asarray(out4.flow), np.asarray(out2.flow))
    # jit-vs-eager tolerance: XLA reassociates fp32 reductions through the
    # recurrent loop, so bit-exactness is not expected
    np.testing.assert_allclose(np.asarray(out2.flow),
                               np.asarray(fn(params, im1, im2)),
                               atol=2e-2, rtol=1e-3)


@pytest.fixture(scope="module")
def dense_gather_flow():
    """The reference path's flow (stored volume, take_along_axis lookup)."""
    base = RAFTConfig.full(iters=3, corr_lookup="gather")
    params, im1, im2 = _params_and_images(base)
    return params, im1, im2, raft_forward(params, im1, im2, base)[0]


@pytest.mark.parametrize("path", [
    dict(corr_impl="dense", corr_lookup="onehot"),
    dict(corr_impl="blockwise", corr_lookup="gather"),
    dict(corr_impl="blockwise", corr_lookup="onehot"),
    dict(corr_impl="pallas"),
], ids=lambda p: "-".join(p.values()))
def test_corr_impls_agree(dense_gather_flow, path):
    """Every correlation path ``_iterate_flow`` has, by value, against the
    stored volume with the gather lookup."""
    params, im1, im2, out_a = dense_gather_flow
    out_b, _ = raft_forward(params, im1, im2,
                            RAFTConfig.full(iters=3, **path))
    # the raw lookups agree to ~1e-6 (test_corr); recurrence amplifies the
    # different-summation-order noise, so compare relative to flow magnitude
    scale = np.abs(np.asarray(out_a.flow)).mean()
    diff = np.abs(np.asarray(out_a.flow) - np.asarray(out_b.flow)).max()
    assert diff / scale < 1e-3, (diff, scale)


def test_train_mode_outputs_all_iters():
    config = RAFTConfig.full(iters=3)
    params, im1, im2 = _params_and_images(config, B=2, H=48, W=64)
    out, new_params = raft_forward(params, im1, im2, config, train=True)
    assert out.flow_iters.shape == (3, 2, 48, 64, 2)
    # BN running stats must have moved
    old_mean = params["cnet"]["norm1"]["mean"]
    new_mean = new_params["cnet"]["norm1"]["mean"]
    assert not np.allclose(np.asarray(old_mean), np.asarray(new_mean))


@pytest.mark.slow
def test_gradients_flow_and_finite():
    config = RAFTConfig.full(iters=2)
    params, im1, im2 = _params_and_images(config, H=48, W=64)

    def loss_fn(p):
        out, _ = raft_forward(p, im1, im2, config, train=True)
        return jnp.mean(jnp.abs(out.flow_iters)) * 1e3

    grads = jax.grad(loss_fn)(params)
    leaves = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in leaves)
    # the update block must receive gradient
    gnorm = float(jnp.linalg.norm(grads["update_block"]["flow_head"]["conv2"]["w"]))
    assert gnorm > 0.0


def test_flow_init_warm_start():
    config = RAFTConfig.small_model(iters=2)
    params, im1, im2 = _params_and_images(config)
    init = jnp.ones((1, 8, 12, 2))
    out, _ = raft_forward(params, im1, im2, config, flow_init=init)
    out0, _ = raft_forward(params, im1, im2, config)
    assert not np.allclose(np.asarray(out.flow), np.asarray(out0.flow))


@pytest.mark.slow
def test_bfloat16_compute():
    config = RAFTConfig.full(iters=2, compute_dtype="bfloat16")
    params, im1, im2 = _params_and_images(config)
    out, _ = raft_forward(params, im1, im2, config)
    assert out.flow.dtype == jnp.float32
    ref, _ = raft_forward(params, im1, im2, RAFTConfig.full(iters=2))
    # bf16 compute should stay in the same ballpark as fp32
    diff = np.abs(np.asarray(out.flow) - np.asarray(ref.flow)).mean()
    scale = np.abs(np.asarray(ref.flow)).mean() + 1e-6
    assert diff / scale < 0.5, (diff, scale)


@pytest.mark.parametrize("impl", ["dense", "blockwise", "pallas"])
def test_unknown_corr_lookup_rejected_all_impls(impl):
    """A corr_lookup typo must raise for EVERY impl, not silently fall back
    to the gather path (the blockwise branch used to do exactly that)."""
    cfg = RAFTConfig.full(iters=1, corr_impl=impl, corr_lookup="one-hot")
    params = init_raft(jax.random.PRNGKey(0), cfg)
    im = jnp.zeros((1, 32, 32, 3))
    with pytest.raises(ValueError, match="corr_lookup"):
        raft_forward(params, im, im, cfg)


@pytest.mark.parametrize("small", [False, True])
def test_gru_ctx_hoist_equivalence(small):
    """gru_ctx_hoist is an exact rewrite (conv linearity over input-channel
    blocks): forward outputs must match the plain path, both variants."""
    mk = RAFTConfig.small_model if small else RAFTConfig.full
    # explicit False: the config DEFAULT is now hoisted, so an inherited
    # default would compare hoisted-vs-hoisted and prove nothing
    base = mk(iters=3, corr_levels=2, gru_ctx_hoist=False)
    hoisted = mk(iters=3, corr_levels=2, gru_ctx_hoist=True)
    params, im1, im2 = _params_and_images(base, H=32, W=48)
    out_a, _ = raft_forward(params, im1, im2, base, train=True)
    out_b, _ = raft_forward(params, im1, im2, hoisted, train=True)
    a = np.asarray(out_a.flow_iters)
    b = np.asarray(out_b.flow_iters)
    scale = max(np.abs(a).mean(), 1e-3)
    diff = np.abs(a - b).max()
    assert diff / scale < 1e-4, (diff, scale)


@pytest.mark.slow
def test_gru_ctx_hoist_gradient_equivalence():
    """The hoisted path must also produce the same parameter gradients (the
    kernel slices recombine in the cotangent)."""
    base = RAFTConfig.small_model(iters=2, corr_levels=2,
                                  gru_ctx_hoist=False)
    hoisted = RAFTConfig.small_model(iters=2, corr_levels=2,
                                     gru_ctx_hoist=True)
    params, im1, im2 = _params_and_images(base, H=16, W=24)

    def loss(p, cfg):
        out, _ = raft_forward(p, im1, im2, cfg, train=True)
        return jnp.abs(out.flow_iters).mean()

    g_a = jax.grad(loss)(params, base)
    g_b = jax.grad(loss)(params, hoisted)
    # The rewrite is exact (verified to 1e-15 in float64 on the isolated
    # GRUs); in fp32 the only differences are reassociation noise, which
    # dominates leaves whose TRUE gradient is zero (fnet conv biases under
    # instance norm).  Compare against the global gradient scale, not
    # per-element — noise sits ~4 orders below it, a real bug would not.
    leaves_b = [np.asarray(x) for x in jax.tree.leaves(g_b)]
    global_scale = max(np.abs(b).max() for b in leaves_b)
    for la, b in zip(jax.tree.leaves(g_a), leaves_b):
        diff = np.abs(np.asarray(la) - b).max()
        assert diff < 1e-3 * global_scale, (diff, global_scale)


def test_gru_ctx_hoist_bfloat16():
    """Hoisting composes with the bf16 compute policy (terms stay bf16)."""
    cfg = RAFTConfig.full(iters=2, corr_levels=2, compute_dtype="bfloat16",
                          gru_ctx_hoist=True)
    params, im1, im2 = _params_and_images(cfg, H=32, W=48)
    out, _ = raft_forward(params, im1, im2, cfg)
    assert np.all(np.isfinite(np.asarray(out.flow)))


# ------------------------------------------- adaptive compute (round 8) --

def test_iters_policy_parse():
    from raft_tpu.config import parse_iters_policy
    assert parse_iters_policy("fixed") == ("fixed", None, None)
    assert parse_iters_policy("converge:1e-2") == ("converge", 1e-2, 1)
    assert parse_iters_policy("converge:0.5:4") == ("converge", 0.5, 4)
    for bad in ("convrge:1e-2", "converge", "converge:xyz",
                "converge:-1", "converge:nan", "converge:1e-2:0",
                "converge:1e-2:two", "converge:1:2:3"):
        with pytest.raises(ValueError, match="iters_policy"):
            parse_iters_policy(bad)


def test_iters_policy_typo_raises_in_forward():
    cfg = RAFTConfig.small_model(iters=1, iters_policy="converge")
    params = init_raft(jax.random.PRNGKey(0), cfg)
    im = jnp.zeros((1, 32, 32, 3))
    with pytest.raises(ValueError, match="iters_policy"):
        raft_forward(params, im, im, cfg)


def test_converge_zero_matches_fixed_bitwise():
    """converge:0 never triggers (a norm is never < 0): both the masked
    scan and the while-loop fast path must reproduce 'fixed' BIT-FOR-BIT
    (same ops on every sample, the masks all-true)."""
    fixed = RAFTConfig.small_model(iters=4)
    conv = RAFTConfig.small_model(iters=4, iters_policy="converge:0")
    params, im1, im2 = _params_and_images(fixed, B=2, H=32, W=48)
    # inference: fixed scan vs the adaptive while_loop
    out_f, _ = raft_forward(params, im1, im2, fixed)
    out_c, _ = raft_forward(params, im1, im2, conv)
    assert np.array_equal(np.asarray(out_f.flow), np.asarray(out_c.flow))
    assert np.asarray(out_c.iters_used).tolist() == [4, 4]
    assert np.asarray(out_f.iters_used).tolist() == [4, 4]
    # train path: plain scan vs masked scan
    out_ft, _ = raft_forward(params, im1, im2, fixed, train=True)
    out_ct, _ = raft_forward(params, im1, im2, conv, train=True)
    assert np.array_equal(np.asarray(out_ft.flow_iters),
                          np.asarray(out_ct.flow_iters))


def test_converge_freeze_repeats_frozen_flow():
    """Once a sample converges, every later flow_iters entry must repeat
    its frozen flow exactly — the sequence loss and --dump-flow contract.
    eps=1e9 with min_iters=2 freezes everything right after iteration 2."""
    cfg = RAFTConfig.small_model(iters=5, iters_policy="converge:1e9:2")
    params, im1, im2 = _params_and_images(cfg, B=2, H=32, W=48)
    out, _ = raft_forward(params, im1, im2, cfg, all_flows=True)
    fi = np.asarray(out.flow_iters)
    assert np.asarray(out.iters_used).tolist() == [2, 2]
    for t in range(2, 5):
        assert np.array_equal(fi[t], fi[1]), t
    # the pre-freeze prefix is the same computation as 'fixed'
    ref, _ = raft_forward(params, im1, im2, RAFTConfig.small_model(iters=5),
                          all_flows=True)
    assert np.array_equal(fi[:2], np.asarray(ref.flow_iters)[:2])


def test_converge_per_sample_freeze_mixed_batch():
    """Easy + hard pair in ONE batch: with eps between the two samples'
    first-iteration update norms, the easy sample freezes after iteration
    1 while the hard one keeps iterating — and (small variant: per-sample
    normalization only) the hard sample's trajectory is untouched by its
    frozen batch-mate."""
    fixed = RAFTConfig.small_model(iters=5)
    params, im1, im2 = _params_and_images(fixed, B=2, H=32, W=48)
    # measure each sample's first-iteration ‖Δflow‖ at the 1/8 grid, then
    # pick eps strictly between them — deterministic mixed difficulty
    # without assuming anything about the random-weight dynamics
    probe, _ = raft_forward(params, im1, im2, fixed, iters=1)
    dn = np.linalg.norm(np.asarray(probe.flow_lr), axis=-1).mean(axis=(1, 2))
    lo, hi = sorted(dn)
    assert lo < hi                      # distinct inputs -> distinct norms
    eps = float(np.sqrt(lo * hi))
    easy = int(np.argmin(dn))
    cfg = RAFTConfig.small_model(iters=5, iters_policy=f"converge:{eps!r}")
    out, _ = raft_forward(params, im1, im2, cfg, all_flows=True)
    used = np.asarray(out.iters_used)
    assert used[easy] == 1
    assert used[1 - easy] >= 2
    fi = np.asarray(out.flow_iters)
    for t in range(1, 5):               # frozen sample repeats its flow
        assert np.array_equal(fi[t, easy], fi[0, easy]), t
    # the active sample's trajectory matches a run without the frozen mate
    # (small variant: per-sample normalization only; compare relative to
    # flow scale — batch-1 vs batch-2 convs reassociate fp32 reductions)
    hard = 1 - easy
    solo, _ = raft_forward(params, im1[hard:hard + 1],
                           im2[hard:hard + 1], cfg, all_flows=True)
    a = fi[:, hard]
    b = np.asarray(solo.flow_iters)[:, 0]
    scale = max(np.abs(a).mean(), 1e-3)
    assert np.abs(a - b).max() / scale < 1e-3
    # the while-loop fast path agrees with the masked scan, per sample
    out_w, _ = raft_forward(params, im1, im2, cfg)
    assert np.asarray(out_w.iters_used).tolist() == used.tolist()
    np.testing.assert_allclose(np.asarray(out_w.flow), fi[-1],
                               atol=1e-5, rtol=1e-5)


def test_converge_gradients_flow_through_masked_scan_remat():
    """Gradient must flow through the masked scan (frozen samples simply
    contribute zero past their exit), composing with remat_iters."""
    cfg = RAFTConfig.small_model(iters=3, iters_policy="converge:1e9:2",
                                 remat_iters=True)
    params, im1, im2 = _params_and_images(cfg, B=2, H=16, W=24)

    def loss(p):
        out, _ = raft_forward(p, im1, im2, cfg, train=True)
        return jnp.abs(out.flow_iters).mean()

    grads = jax.grad(loss)(params)
    leaves = [np.asarray(g) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(g).all() for g in leaves)
    gnorm = float(jnp.linalg.norm(
        grads["update_block"]["flow_head"]["conv2"]["w"]))
    assert gnorm > 0.0


def test_converge_jit_and_counted_fn():
    """The counted inference fn jits, and under jit the early exit still
    reports per-sample counts (static shapes, data-dependent trip count)."""
    cfg = RAFTConfig.small_model(iters=4, iters_policy="converge:1e9:2")
    params, im1, im2 = _params_and_images(cfg, B=2, H=32, W=48)
    flow, used = jax.jit(make_inference_fn(cfg, counted=True))(
        params, im1, im2)
    assert flow.shape == (2, 32, 48, 2)
    assert used.dtype == jnp.int32
    assert np.asarray(used).tolist() == [2, 2]
    # fixed policy reports the declared count
    flowf, usedf = make_inference_fn(
        RAFTConfig.small_model(iters=4), counted=True)(params, im1, im2)
    assert np.asarray(usedf).tolist() == [4, 4]


def test_converge_spatial_sharding_rejected():
    """Per-sample ‖Δflow‖ on a row shard sees only the local slab —
    adaptive + spatial must raise, not silently diverge across shards."""
    from raft_tpu.ops import spmd
    cfg = RAFTConfig.small_model(iters=2, iters_policy="converge:1e-2")
    params, im1, im2 = _params_and_images(cfg, H=32, W=48)
    with spmd.spatial_sharding("spatial"):
        with pytest.raises(NotImplementedError, match="converge"):
            raft_forward(params, im1, im2, cfg)


def test_scan_unroll_equivalence():
    """scan_unroll is a pure scheduling knob: outputs must match unroll=1."""
    base = RAFTConfig.full(iters=4)
    unrolled = RAFTConfig.full(iters=4, scan_unroll=2)
    params, im1, im2 = _params_and_images(base)
    out_a, _ = raft_forward(params, im1, im2, base)
    out_b, _ = raft_forward(params, im1, im2, unrolled)
    scale = np.abs(np.asarray(out_a.flow)).mean()
    diff = np.abs(np.asarray(out_a.flow) - np.asarray(out_b.flow)).max()
    assert diff / scale < 1e-4, (diff, scale)
    # unroll larger than iters is clamped, not an error
    clamped = RAFTConfig.full(iters=2, scan_unroll=8)
    out_c, _ = raft_forward(params, im1, im2, clamped)
    assert np.all(np.isfinite(np.asarray(out_c.flow)))


# ------------------------------------------- streaming feature-reuse path --

def _assert_close_rel(got, ref, rel=1e-4):
    """max|got - ref| within ``rel`` of the reference's magnitude.

    The streaming path computes what the pairwise path computes, but encodes
    the two frames in separate batch-1 passes where raft_forward runs one
    batched 2B pass, and XLA fuses (and so orders the f32 reductions of)
    the two differently.  With untrained weights the flows are ~190 px, so
    a round-off of 2.5e-5 RELATIVE is 5e-3 px: an absolute 1e-5 gate tests
    the compiler's fusion choices, not the model.  1e-4 of the magnitude
    still fails any bf16 path (~1e-2) or a wrong operand."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    scale = max(float(np.abs(ref).max()), 1.0)
    diff = float(np.abs(got - ref).max())
    assert diff / scale < rel, (diff, scale)


def test_forward_from_features_matches_pairwise():
    """The streaming path's contract: encode_frame + forward_from_features
    must reproduce raft_forward on the same frames — the cached-feature
    advance IS the pairwise computation, just with the encoders factored
    out (equal up to f32 reduction order, see _assert_close_rel)."""
    from raft_tpu.models import encode_frame, forward_from_features

    config = RAFTConfig.small_model(iters=3)
    params, im1, im2 = _params_and_images(config, H=32, W=48)
    ref, _ = raft_forward(params, im1, im2, config, train=False,
                          all_flows=False)
    fmap1, cnet1 = encode_frame(params, im1, config)
    fmap2, _ = encode_frame(params, im2, config)
    out = forward_from_features(params, fmap1, fmap2, cnet1, config)
    _assert_close_rel(out.flow, ref.flow)
    _assert_close_rel(out.flow_lr, ref.flow_lr)


def test_forward_from_features_flow_init_matches():
    """flow_init threads through the factored path exactly as through
    raft_forward (the warm-start seed of the streaming advance)."""
    from raft_tpu.models import encode_frame, forward_from_features

    config = RAFTConfig.small_model(iters=2)
    params, im1, im2 = _params_and_images(config, H=32, W=48, seed=3)
    init = jax.random.normal(jax.random.PRNGKey(9), (1, 4, 6, 2)) * 2.0
    ref, _ = raft_forward(params, im1, im2, config, train=False,
                          all_flows=False, flow_init=init)
    fmap1, cnet1 = encode_frame(params, im1, config)
    fmap2, _ = encode_frame(params, im2, config)
    out = forward_from_features(params, fmap1, fmap2, cnet1, config,
                                flow_init=init)
    _assert_close_rel(out.flow, ref.flow)


def test_stream_step_fn_jits_and_matches():
    """The fused one-call stream step (encode current + recurrent core):
    jittable, one fnet pass, output within float-reassociation tolerance
    of the pairwise run (the encoder sees batch 1 instead of the pairwise
    2B concat, so reductions associate differently)."""
    from raft_tpu.models import encode_frame, make_stream_step_fn

    config = RAFTConfig.small_model(iters=2)
    params, im1, im2 = _params_and_images(config, H=32, W=48, seed=5)
    ref, _ = raft_forward(params, im1, im2, config, train=False,
                          all_flows=False)
    fmap1, cnet1 = encode_frame(params, im1, config)
    step = jax.jit(make_stream_step_fn(config))
    zeros = jnp.zeros((1, 4, 6, 2), jnp.float32)
    flow, flow_lr, fmap2, cnet2, = step(params, im2, fmap1, cnet1, zeros)
    _assert_close_rel(flow, ref.flow)
    # the returned current-frame maps equal a direct encode (cacheable)
    fmap2_ref, cnet2_ref = encode_frame(params, im2, config)
    _assert_close_rel(fmap2, fmap2_ref)
    _assert_close_rel(cnet2, cnet2_ref)


def test_stream_step_fn_counted_under_converge():
    """Under an adaptive policy the stream step returns iters_used — the
    counted-executable convention the serving engine keys on."""
    import dataclasses

    from raft_tpu.models import encode_frame, make_stream_step_fn

    config = dataclasses.replace(RAFTConfig.small_model(iters=4),
                                 iters_policy="converge:1e9:2")
    params, im1, im2 = _params_and_images(config, H=32, W=48, seed=7)
    fmap1, cnet1 = encode_frame(params, im1, config)
    step = jax.jit(make_stream_step_fn(config))
    zeros = jnp.zeros((1, 4, 6, 2), jnp.float32)
    flow, flow_lr, _, _, iters_used = step(params, im2, fmap1, cnet1, zeros)
    assert iters_used.shape == (1,)
    assert int(iters_used[0]) == 2               # exited at min_iters
    assert np.isfinite(np.asarray(flow)).all()


# ------------------------------- continuous-batched stream step (slots) --


def _slot_fixture(config, n=3, cap=4, H=32, W=48, seed=11):
    """N sessions' prev/cur frames + slot-pool buffers holding the prev
    maps in rows 0..n-1 (row `cap` is the scratch slot)."""
    from raft_tpu.models import encode_frame

    rng = np.random.RandomState(seed)
    params = init_raft(jax.random.PRNGKey(seed), config)
    h, w = H // 8, W // 8
    prev = [rng.rand(1, H, W, 3).astype(np.float32) for _ in range(n)]
    cur = [rng.rand(1, H, W, 3).astype(np.float32) for _ in range(n)]
    maps = [encode_frame(params, jnp.asarray(p), config) for p in prev]
    fbuf = jnp.zeros((cap + 1, h, w, maps[0][0].shape[-1]),
                     maps[0][0].dtype)
    cbuf = jnp.zeros((cap + 1, h, w, maps[0][1].shape[-1]),
                     maps[0][1].dtype)
    flbuf = jnp.zeros((cap + 1, h, w, 2), jnp.float32)
    for i, (fm, cn) in enumerate(maps):
        fbuf = fbuf.at[i].set(fm[0])
        cbuf = cbuf.at[i].set(cn[0])
    return params, prev, cur, maps, (fbuf, cbuf, flbuf)


def test_stream_batch_step_equals_solo_rows():
    """The continuous-batched stream step (ISSUE 15): N sessions advanced
    in one batch vs each advanced alone.

    Pinned exactly (bit-for-bit, converge:0): (a) at the SAME batch
    width, a row's output is independent of its batch-mates — real
    neighbors vs scratch-slot padding rows produce identical bits (the
    per-row independence + active-mask correctness the batcher relies
    on); (b) the width-1 batched step (gather from slots) equals the
    solo make_stream_step_fn (maps as arguments) bit-for-bit.  Across
    DIFFERENT widths XLA reassociates conv reductions (same caveat as
    test_converge_per_sample_freeze_mixed_batch), so batch-N vs batch-1
    is pinned scale-relative instead."""
    from raft_tpu.models import make_stream_batch_step_fn, make_stream_step_fn

    config = RAFTConfig.small_model(iters=3, iters_policy="converge:0")
    n, cap = 3, 4
    params, prev, cur, maps, bufs = _slot_fixture(config, n=n, cap=cap)
    fbuf, cbuf, flbuf = bufs
    step = jax.jit(make_stream_batch_step_fn(config))

    # one batched call, padded 3 -> 4 with an inactive scratch row
    images = jnp.asarray(np.concatenate(cur + [cur[-1]]))
    slots = jnp.asarray([0, 1, 2, cap], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    flow_n, flr_n, fm_n, cn_n, it_n = step(params, images, fbuf, cbuf,
                                           flbuf, slots, active)
    assert np.asarray(it_n).tolist() == [3, 3, 3, 0]   # padding: 0 iters

    # (a) same-width independence: 1 real row + 3 padding rows — row 0's
    # bits must not change with its batch-mates
    flow_p, _, _, _, it_p = step(
        params, jnp.asarray(np.concatenate([cur[0]] * 4)), fbuf, cbuf,
        flbuf, jnp.asarray([0, cap, cap, cap], jnp.int32),
        jnp.asarray([True, False, False, False]))
    assert np.array_equal(np.asarray(flow_p[0]), np.asarray(flow_n[0]))
    assert np.asarray(it_p).tolist() == [3, 0, 0, 0]

    solo = jax.jit(make_stream_step_fn(config))
    h, w = 4, 6
    for i in range(n):
        # (b) width-1 batched == solo step, bit-for-bit (same width, the
        # gather feeds identical values)
        f1, fl1, fm1, cn1, it1 = step(params, jnp.asarray(cur[i]),
                                      fbuf, cbuf, flbuf,
                                      jnp.asarray([i], jnp.int32),
                                      jnp.asarray([True]))
        f_s, fl_s, fm_s, cn_s, _ = solo(params, jnp.asarray(cur[i]),
                                        maps[i][0], maps[i][1],
                                        jnp.zeros((1, h, w, 2),
                                                  jnp.float32))
        assert np.array_equal(np.asarray(f1), np.asarray(f_s)), i
        assert np.array_equal(np.asarray(fl1), np.asarray(fl_s)), i
        # batch-N vs batch-1: scale-relative (cross-width conv
        # reassociation), per row
        a = np.asarray(flow_n[i])
        scale = max(np.abs(a).mean(), 1e-3)
        assert np.abs(a - np.asarray(f1[0])).max() / scale < 1e-2, i
        assert int(it1[0]) == int(it_n[i]) == 3
        # the returned current-frame map rows equal the solo step's
        # (they become the session cache)
        np.testing.assert_allclose(np.asarray(fm_n[i]), np.asarray(fm1[0]),
                                   rtol=1e-4, atol=1e-4)


def test_stream_batch_step_padding_never_extends_while_loop():
    """Under a converge policy, inactive rows start CONVERGED: they
    report iters_used == 0 and a batch whose real rows all exit at
    min_iters exits the whole while_loop there — padding can never cost
    iterations (the padding-exclusion contract of the serving
    metrics)."""
    from raft_tpu.models import make_stream_batch_step_fn

    config = RAFTConfig.small_model(iters=5, iters_policy="converge:1e9:2")
    params, prev, cur, maps, bufs = _slot_fixture(config, n=2, cap=4,
                                                  seed=13)
    fbuf, cbuf, flbuf = bufs
    step = jax.jit(make_stream_batch_step_fn(config))
    images = jnp.asarray(np.concatenate(cur + [cur[-1]] * 2))
    out = step(params, images, fbuf, cbuf, flbuf,
               jnp.asarray([0, 1, 4, 4], jnp.int32),
               jnp.asarray([True, True, False, False]))
    flow, _, _, _, iters_used = out
    assert np.asarray(iters_used).tolist() == [2, 2, 0, 0]
    assert np.isfinite(np.asarray(flow[:2])).all()
