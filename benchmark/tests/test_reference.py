"""The plain reference against the program's own dense forward, and the
control of the output check, at sizes a CPU holds."""

import json
import os

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import check  # noqa: E402
import control  # noqa: E402
import inputs  # noqa: E402
import reference  # noqa: E402
import weights as weights_mod  # noqa: E402


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["raft-things", "raft-small"])
def test_reference_agrees_with_the_programs_dense_gather_forward(name):
    """Two descriptions written apart (this one from the paper and the torch
    restatement, the program's from its own history) give the same flow in
    float32: 1e-4 of the mean flow, where float32 round-off over 4 updates
    reads about 1e-6."""
    import jax
    import jax.numpy as jnp
    from raft_tpu import RAFTConfig
    from raft_tpu.data.pipeline import pad_to_shape, unpad
    from raft_tpu.models import init_raft
    from raft_tpu.models.raft import make_inference_fn

    cfg = config(name)
    mcfg = weights_mod.model_cfg(cfg)
    wts = weights_mod.make_weights(3_000_000_019, mcfg)
    make = RAFTConfig.small_model if cfg["small"] else RAFTConfig.full
    pcfg = make(iters=4, corr_impl="dense", corr_lookup="gather")
    want = jax.eval_shape(lambda: init_raft(jax.random.PRNGKey(0), pcfg))
    assert jax.tree.structure(want) == jax.tree.structure(wts)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(wts))
    stats = 0 if cfg["small"] else sum(
        2 * c for _, c in weights_mod.conv_plan(mcfg)[1])
    assert n - stats == cfg["parameters"] == weights_mod.n_parameters(mcfg)

    a, b = inputs.make_pairs(7, 1, 60, 92)[0]       # 60x92: padded both ways
    ref = np.asarray(reference.flow(wts, a, b, mcfg, 4))
    p1, pads = pad_to_shape((a / np.float32(255))[None], (64, 96))
    p2, _ = pad_to_shape((b / np.float32(255))[None], (64, 96))
    got = unpad(np.asarray(jax.jit(make_inference_fn(pcfg))(
        wts, jnp.asarray(p1), jnp.asarray(p2))), pads)[0]
    assert got.shape == ref.shape == (60, 92, 2)
    assert check.rel_epe(got, ref) < 1e-4


@pytest.mark.parametrize("name", ["raft-things", "raft-small"])
def test_control_at_a_lower_precision_fails_the_limit(name):
    """The reference in the program's place at e4m3 reads over the
    configuration's limit (the reference at the configuration's own
    precision reads 1 by construction)."""
    cfg = config(name)
    limit = cfg["check"]["ratio_limit"]
    (row,) = control.readings(cfg, seed=11, n_pairs=1, height=120, width=192)
    assert row["precision_ratio"] > limit > 1.0, (row, limit)
