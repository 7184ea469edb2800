"""Full-model torch-golden parity: official RAFT (torch oracle, eval mode)
vs raft-tpu, driven by weights converted with ``from_torch_state_dict`` from
a REAL official-architecture state_dict (not a round-trip of our own export).

This is the honest substitute for trained-weights validation in this
environment: any divergence in channel plan, parameter naming, padding, norm
semantics, correlation window ordering, or upsampling breaks it.  The
reference repo never closed this parity gap (reference readme.md:45 — "a few
of differences from the official implementation"); raft-tpu must.
"""

import numpy as np
import pytest
import torch

import jax
import jax.numpy as jnp

from raft_tpu.config import RAFTConfig
from raft_tpu.convert import assert_tree_shapes_match, from_torch_state_dict
from raft_tpu.models import init_raft, raft_forward

from torch_raft_golden import RAFT as TorchRAFT


def _run_pair(small: bool, B, H, W, iters, corr_impl="dense",
              corr_lookup="gather", **cfg_overrides):
    torch.manual_seed(0)
    tmodel = TorchRAFT(small=small).eval()
    # non-trivial BN running stats so eval-mode normalization is exercised
    with torch.no_grad():
        for m in tmodel.modules():
            if isinstance(m, torch.nn.BatchNorm2d):
                m.running_mean.uniform_(-0.05, 0.05)
                m.running_var.uniform_(0.9, 1.1)

    sd = {k: v.detach().numpy() for k, v in tmodel.state_dict().items()}
    params = from_torch_state_dict(sd)

    # literal (un-hoisted) GRU formulation unless a test opts in: the config
    # DEFAULT is hoisted, and this oracle is what keeps the still-selectable
    # --no-ctx-hoist path covered (the hoisted path has its own parity test)
    cfg = (RAFTConfig.small_model if small else RAFTConfig.full)(
        iters=iters, corr_impl=corr_impl, corr_lookup=corr_lookup,
        compute_dtype="float32", **{"gru_ctx_hoist": False, **cfg_overrides})
    expected = init_raft(jax.random.PRNGKey(0), cfg)
    assert_tree_shapes_match(params, expected)
    params = jax.tree.map(jnp.asarray, params)

    rng = np.random.RandomState(7)
    im = rng.rand(2, B, H, W, 3).astype(np.float32)   # [0, 1]

    with torch.no_grad():
        tflows = tmodel(
            torch.from_numpy(255.0 * im[0].transpose(0, 3, 1, 2)),
            torch.from_numpy(255.0 * im[1].transpose(0, 3, 1, 2)),
            iters=iters)
    tflows = np.stack([f.numpy().transpose(0, 2, 3, 1) for f in tflows])

    out, _ = raft_forward(params, jnp.asarray(im[0]), jnp.asarray(im[1]),
                          cfg, train=False, all_flows=True)
    jflows = np.asarray(out.flow_iters)
    return tflows, jflows


@pytest.mark.parametrize("small", [False, True], ids=["full", "small"])
def test_full_model_torch_parity(small):
    tflows, jflows = _run_pair(small, B=1, H=128, W=128, iters=3)
    assert tflows.shape == jflows.shape
    for i, (tf_i, jf_i) in enumerate(zip(tflows, jflows)):
        err = np.abs(tf_i - jf_i).max()
        scale = np.abs(tf_i).max()
        assert err <= 1e-3 + 1e-3 * scale, (
            f"iter {i}: max|Δflow|={err:.2e} vs scale {scale:.2e}")


def test_full_model_torch_parity_ctx_hoist():
    """The hoisted-context GRU rewrite must match the official architecture
    directly (not just the plain JAX path): same oracle, same gate."""
    tflows, jflows = _run_pair(False, B=1, H=128, W=128, iters=3,
                               gru_ctx_hoist=True)
    for i, (tf_i, jf_i) in enumerate(zip(tflows, jflows)):
        err = np.abs(tf_i - jf_i).max()
        scale = np.abs(tf_i).max()
        assert err <= 1e-3 + 1e-3 * scale, (
            f"iter {i}: max|Δflow|={err:.2e} vs scale {scale:.2e}")


def test_full_model_torch_parity_blockwise_onehot():
    """The tuned lookup paths must match the official model too, not just
    the dense/gather correctness reference."""
    tflows, jflows = _run_pair(False, B=1, H=128, W=128, iters=2,
                               corr_impl="blockwise", corr_lookup="onehot")
    err = np.abs(tflows[-1] - jflows[-1]).max()
    scale = np.abs(tflows[-1]).max()
    assert err <= 1e-3 + 1e-3 * scale, (err, scale)


def test_full_model_torch_parity_dense_onehot_default():
    """dense + onehot + ctx-hoist is the SHIPPING default config since
    round 4 (both knobs measured winners) — the exact default path needs
    its own full-model oracle, not just the gather correctness reference."""
    tflows, jflows = _run_pair(False, B=1, H=128, W=128, iters=2,
                               corr_impl="dense", corr_lookup="onehot",
                               gru_ctx_hoist=True)
    err = np.abs(tflows[-1] - jflows[-1]).max()
    scale = np.abs(tflows[-1]).max()
    assert err <= 1e-3 + 1e-3 * scale, (err, scale)


def test_full_model_torch_parity_pallas_window():
    """The fused kernel under its key-block schedule must match the
    official model end-to-end (W=128 -> fmap width 16, two row-blocks of 8
    at level 0).

    Note the oracle constraint: sizes where a pyramid level collapses to
    1 px (e.g. W=120 -> level-3 width 1) make the torch/official
    align_corners grid normalization divide by (size-1)=0 and go NaN —
    an official-RAFT edge case, not a lookup bug; this framework returns
    zeros for degenerate levels instead."""
    tflows, jflows = _run_pair(False, B=1, H=128, W=128, iters=2,
                               corr_impl="pallas", pallas_p_blk=1024)
    err = np.abs(tflows[-1] - jflows[-1]).max()
    scale = np.abs(tflows[-1]).max()
    assert err <= 1e-3 + 1e-3 * scale, (err, scale)


def test_full_model_torch_parity_pallas_window_160():
    """Second geometry for the window-schedule parity claim (VERDICT r2
    item 7): 160x160 -> fmap 20x20, pyramid widths 20/10/5/2 — every level
    odd or non-power-of-two but none degenerate (the oracle's align_corners
    normalization stays finite), each lane-padded to 128, and Q = 400 not
    a multiple of the 128 query block."""
    tflows, jflows = _run_pair(False, B=1, H=160, W=160, iters=2,
                               corr_impl="pallas", pallas_p_blk=1024)
    err = np.abs(tflows[-1] - jflows[-1]).max()
    scale = np.abs(tflows[-1]).max()
    assert err <= 1e-3 + 1e-3 * scale, (err, scale)


def test_full_model_torch_parity_blockwise_odd_q_160():
    """Blockwise lookup at a Q (=400) that is NOT a multiple of the query
    chunk, with odd pyramid widths — the remainder-block path against the
    official oracle."""
    tflows, jflows = _run_pair(False, B=1, H=160, W=160, iters=2,
                               corr_impl="blockwise", corr_lookup="onehot")
    err = np.abs(tflows[-1] - jflows[-1]).max()
    scale = np.abs(tflows[-1]).max()
    assert err <= 1e-3 + 1e-3 * scale, (err, scale)


def test_small_model_torch_parity_pallas():
    """raft-small (r=3, ConvGRU, bilinear upflow) through the fused kernel
    must match the official torch model too — golden coverage for the
    radius-3 window family."""
    tflows, jflows = _run_pair(True, B=1, H=128, W=128, iters=2,
                               corr_impl="pallas")
    err = np.abs(tflows[-1] - jflows[-1]).max()
    scale = np.abs(tflows[-1]).max()
    assert err <= 1e-3 + 1e-3 * scale, (err, scale)


@pytest.mark.parametrize("small", [True, False], ids=["small", "full"])
@pytest.mark.slow
def test_full_model_gradient_torch_parity(small):
    """Training-fidelity golden: gradients of the SAME scalar loss through
    the official torch model (autograd) and this framework (jax.grad) must
    match leaf-for-leaf.  The torch grads are converted with the SAME
    from_torch_state_dict transposes as the weights, so any divergence in
    backward semantics (BN eval affine, GRU gating, upsampling, corr
    lookup) — not just forward values — breaks this test.  Loss =
    mean(|final flow|): no ground truth needed, gradient flows through
    every parameter that affects the prediction.  Covers both variants:
    raft-small (instance norm, ConvGRU, bilinear upflow) and raft-things
    (eval-mode BN, SepConvGRU, convex upsampling)."""
    torch.manual_seed(0)
    tmodel = TorchRAFT(small=small).eval()  # eval: BN running stats fixed
    sd = {k: v.detach().numpy() for k, v in tmodel.state_dict().items()}
    params = from_torch_state_dict(sd)

    cfg = (RAFTConfig.small_model if small else RAFTConfig.full)(
        iters=2, compute_dtype="float32")
    params = jax.tree.map(jnp.asarray, params)

    rng = np.random.RandomState(3)
    im = rng.rand(2, 1, 128, 128, 3).astype(np.float32)  # 16x16 fmap: no degenerate pyramid level for the oracle

    t1 = torch.from_numpy(255.0 * im[0].transpose(0, 3, 1, 2))
    t2 = torch.from_numpy(255.0 * im[1].transpose(0, 3, 1, 2))
    tflows = tmodel(t1, t2, iters=2)
    tloss = tflows[-1].abs().mean()
    tloss.backward()
    grad_sd = {k: (p.grad if p.grad is not None
                   else torch.zeros_like(p)).numpy()
               for k, p in tmodel.named_parameters()}
    # buffers (running stats) carry no autograd grad while the jax side DOES
    # differentiate through eval-mode normalization, so they must be SKIPPED
    # below, not compared against fabricated zeros; zero-fill only to keep
    # the converter's tree structure, and build a parallel is-parameter mask
    # through the same conversion so the skip follows the converted paths.
    # The full model's shortcut-norm ALIASING (downsample.1.* is the same
    # parameter as norm3.*, deduped out of named_parameters) needs the grad
    # copied to the alias name, or the converter's alias-consistency check
    # would see real grads under one name and zeros under the other.
    pnames = set(grad_sd)
    mask_sd = {}
    for k, v in sd.items():
        twin = k.replace(".downsample.1.", ".norm3.")
        if k not in pnames and twin in pnames:
            grad_sd[k] = grad_sd[twin]
            mask_sd[k] = np.full_like(v, 1.0)
            continue
        mask_sd[k] = np.full_like(v, 1.0 if k in pnames else 0.0)
        if k not in pnames:
            grad_sd[k] = np.zeros_like(v)
    tgrads = from_torch_state_dict(grad_sd)
    is_param = from_torch_state_dict(mask_sd)

    def loss_fn(p):
        out, _ = raft_forward(p, jnp.asarray(im[0]), jnp.asarray(im[1]),
                              cfg, train=False, all_flows=False)
        return jnp.abs(out.flow).mean()

    jloss, jgrads = jax.value_and_grad(loss_fn)(params)
    np.testing.assert_allclose(float(jloss), float(tloss.detach()),
                               rtol=1e-4)

    flat_t = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(np.asarray, tgrads))[0]
    flat_j = dict(jax.tree_util.tree_flatten_with_path(
        jax.tree.map(np.asarray, jgrads))[0])
    flat_m = dict(jax.tree_util.tree_flatten_with_path(
        jax.tree.map(np.asarray, is_param))[0])
    checked = 0
    gscale = max(float(np.abs(g).max()) for _, g in flat_t)
    for path, tg in flat_t:
        if not flat_m[path].any():
            continue          # buffer leaf: torch has no autograd grad here
        jg = flat_j[path]
        np.testing.assert_allclose(
            jg, tg, atol=1e-5 + 1e-3 * gscale, rtol=5e-3,
            err_msg=f"gradient mismatch at {jax.tree_util.keystr(path)}")
        checked += 1
    assert checked > 50, checked   # every parameter leaf, not a subset


def test_official_state_dict_shape_contract():
    """The official checkpoints carry DataParallel 'module.' prefixes,
    num_batches_tracked counters, and aliased shortcut norms — the converter
    must digest all of that from a REAL official-architecture state_dict."""
    torch.manual_seed(1)
    tmodel = TorchRAFT(small=False).eval()
    sd = {f"module.{k}": v.detach().numpy()
          for k, v in tmodel.state_dict().items()}
    # the aliasing quirk really is present in the architecture
    assert "module.cnet.layer2.0.norm3.weight" in sd
    assert "module.cnet.layer2.0.downsample.1.weight" in sd
    assert any(k.endswith("num_batches_tracked") for k in sd)

    params = from_torch_state_dict(sd)
    expected = init_raft(jax.random.PRNGKey(0), RAFTConfig.full())
    assert_tree_shapes_match(params, expected)


@pytest.mark.slow
def test_official_state_dict_shape_contract_small():
    """Same contract for the raft-small variant (bottleneck blocks, instance
    norms, ConvGRU): the converter must digest a REAL official-architecture
    small state_dict — with the DataParallel 'module.' prefix current torch
    exports carry — into exactly our small init tree."""
    torch.manual_seed(2)
    tmodel = TorchRAFT(small=True).eval()
    sd = {f"module.{k}": v.detach().numpy()
          for k, v in tmodel.state_dict().items()}
    assert "module.fnet.layer1.0.conv3.weight" in sd       # bottleneck
    params = from_torch_state_dict(sd)
    expected = init_raft(jax.random.PRNGKey(0), RAFTConfig.small_model())
    assert_tree_shapes_match(params, expected)


def test_sequence_loss_torch_oracle_sparse_valid():
    """Pin the sequence-loss NORMALIZATION against the official recipe with
    torch autograd, on a ~30%-valid batch (the KITTI finetune regime where
    the denominator choice matters most: a valid-count mean would be ~3x the
    official element-count mean, silently inflating the effective LR).

    The torch restatement below is the official repo's sequence_loss
    semantics verbatim-in-spirit: ``(valid[:, None] * i_loss).mean()`` over
    ALL elements.  Both the loss VALUE and d(loss)/d(flow_preds) — the
    gradient a training step backpropagates into the model — must match.
    """
    n, B, H, W = 3, 2, 16, 24
    rng = np.random.RandomState(11)
    preds = rng.randn(n, B, H, W, 2).astype(np.float32) * 3
    gt = rng.randn(B, H, W, 2).astype(np.float32) * 3
    gt[0, :4, :4] = 900.0                      # beyond max_flow: masked out
    valid = (rng.rand(B, H, W) < 0.3).astype(np.float32)
    gamma, max_flow = 0.85, 400.0

    # torch oracle (official train.py semantics, NCHW)
    tpreds = torch.tensor(preds.transpose(0, 1, 4, 2, 3), requires_grad=True)
    tgt = torch.tensor(gt.transpose(0, 3, 1, 2))
    tvalid = torch.tensor(valid)
    mag = torch.sum(tgt ** 2, dim=1).sqrt()
    tv = (tvalid >= 0.5) & (mag < max_flow)
    tloss = 0.0
    for i in range(n):
        i_loss = (tpreds[i] - tgt).abs()
        tloss = tloss + gamma ** (n - i - 1) * (tv[:, None] * i_loss).mean()
    tloss.backward()
    tgrad = tpreds.grad.numpy().transpose(0, 1, 3, 4, 2)   # -> [n,B,H,W,2]

    from raft_tpu.training import sequence_loss

    def loss_fn(p):
        loss, _ = sequence_loss(p, jnp.asarray(gt), jnp.asarray(valid),
                                gamma=gamma, max_flow=max_flow)
        return loss

    jloss, jgrad = jax.value_and_grad(loss_fn)(jnp.asarray(preds))
    np.testing.assert_allclose(float(jloss), float(tloss.detach()), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(jgrad), tgrad, atol=1e-7)

    # epe metric stays a VALID-pixel mean (official evaluation convention:
    # epe.view(-1)[valid.view(-1)].mean())
    _, metrics = sequence_loss(jnp.asarray(preds), jnp.asarray(gt),
                               jnp.asarray(valid), gamma=gamma,
                               max_flow=max_flow)
    tepe = torch.sum((tpreds[-1].detach() - tgt) ** 2, dim=1).sqrt()
    tepe_mean = tepe.reshape(-1)[tv.reshape(-1)].mean()
    np.testing.assert_allclose(float(metrics["epe"]), float(tepe_mean),
                               rtol=1e-5)
