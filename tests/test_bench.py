"""Unit tests for the driver benchmark's candidate-config mapping — a silent
mis-mapping (a candidate name measuring a different configuration than its
label) must be caught in CI — and for its device contract: no chip, no
number."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _cfg_for, _peak_flops


@pytest.mark.parametrize("name,impl,precision,lookup,style,p_blk,pack", [
    ("pallas-bf16corr",         "pallas",    "default", "gather", "matmul", 4096, False),
    ("pallas-bf16corr-win",     "pallas",    "default", "gather", "matmul", 1024, False),
    ("pallas-bf16corr-winpack", "pallas",    "default", "gather", "matmul", 1024, True),
    ("pallas-bf16corr-pack",    "pallas",    "default", "gather", "matmul", 4096, True),
    ("pallas-bf16corr-vpu",     "pallas",    "default", "gather", "vpu",    4096, False),
    ("pallas",                  "pallas",    "highest", "gather", "matmul", 4096, False),
    ("dense-onehot",            "dense",     "highest", "onehot", "matmul", 4096, False),
    ("dense",                   "dense",     "highest", "gather", "matmul", 4096, False),
    ("blockwise-onehot",        "blockwise", "highest", "onehot", "matmul", 4096, False),
    ("blockwise",               "blockwise", "highest", "gather", "matmul", 4096, False),
])
def test_candidate_config_mapping(name, impl, precision, lookup, style, p_blk, pack):
    cfg = _cfg_for(name)
    assert cfg.corr_impl == impl
    assert cfg.corr_precision == precision
    assert cfg.corr_lookup == lookup
    assert cfg.pallas_lookup_style == style
    assert cfg.pallas_pack == pack
    # "-win": blocks fine enough that the kernel's rule schedules them
    assert cfg.pallas_p_blk == p_blk
    assert cfg.compute_dtype == "bfloat16"
    assert cfg.gru_impl == "xla"
    assert not cfg.small


def test_gru_candidate_config_mapping():
    """The fused-GRU candidates: '-gru' flips gru_impl on any candidate;
    the 'pallas-gru' prefix additionally rides the dense-onehot-ctx
    correlation path (the GRU kernel without the corr kernel)."""
    cfg = _cfg_for("pallas-gru")
    assert cfg.gru_impl == "pallas"
    assert cfg.corr_impl == "dense"
    assert cfg.corr_lookup == "onehot"
    assert cfg.gru_ctx_hoist           # the kernel consumes hoisted ctx
    assert cfg.corr_precision == "highest"

    cfg = _cfg_for("pallas-bf16corr-ctx-gru")
    assert cfg.gru_impl == "pallas"
    assert cfg.corr_impl == "pallas"
    assert cfg.corr_precision == "default"
    assert cfg.gru_ctx_hoist


def test_listed_candidates_exclude_what_the_chip_refuses():
    """Every listed candidate must compile for the chip (a candidate that
    raises fails the run): the row-packed kernel is refused by Mosaic, so
    its names map but are not swept."""
    from bench import CANDIDATES

    assert not [c for c in CANDIDATES if _cfg_for(c).pallas_pack]
    assert "pallas-bf16corr-vpu" in CANDIDATES      # compiles since PR 21
    assert CANDIDATES[-1] == "blockwise"            # gather-bound: last


@pytest.mark.slow
def test_candidate_configs_construct_valid_models():
    """Every candidate's config must pass the model's validation layer (the
    forward raises on unknown corr_lookup/corr_precision/lookup_style)."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.models import init_raft
    from raft_tpu.models.raft import raft_forward

    # one tiny forward per distinct (impl, lookup, style) triple; pallas
    # runs in interpret mode on CPU, so keep it to a single iteration
    seen = set()
    for name in ("pallas-bf16corr-vpu", "dense-onehot", "blockwise"):
        cfg = _cfg_for(name)
        key = (cfg.corr_impl, cfg.corr_lookup, cfg.pallas_lookup_style)
        assert key not in seen
        seen.add(key)
        import dataclasses
        cfg = dataclasses.replace(cfg, iters=1, corr_levels=2)
        params = init_raft(jax.random.PRNGKey(0), cfg)
        im = jnp.zeros((1, 16, 24, 3), jnp.float32)
        out, _ = raft_forward(params, im, im, cfg)
        assert out.flow.shape == (1, 16, 24, 2)


def test_peak_flops_table():
    assert _peak_flops("TPU v5 lite") == pytest.approx(197e12)
    assert _peak_flops("TPU v5e") == pytest.approx(197e12)
    assert _peak_flops("TPU v5p") == pytest.approx(459e12)
    assert _peak_flops("TPU v4") == pytest.approx(275e12)


@pytest.mark.parametrize("kind", ["cpu", "TPU v5", "TPU v9 mega", ""])
def test_peak_flops_unknown_device_is_an_error(kind):
    """A device that is not in the table is an error, not a default — and
    a bare 'TPU v5' is not guessed to be a v5p."""
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        _peak_flops(kind)


# ------------------------------------------- device contract (no fallback)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_script(argv, **env):
    return subprocess.run(
        [sys.executable] + argv, cwd=REPO, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_bench_without_cpu_flag_fails_when_there_is_no_tpu():
    """No --cpu on a machine with no TPU: non-zero exit, the JSON line
    carries the error and no value — never a CPU number."""
    proc = _run_script(["bench.py"])
    assert proc.returncode != 0
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["value"] is None
    assert "measures the chip" in line["error"]
    assert line["unit"] == "pairs/sec/chip"


def test_bench_cpu_flag_needs_a_named_candidate():
    """--cpu is a functional check of ONE candidate: without --impl it is a
    usage error (no swapped-in CPU candidate list)."""
    proc = _run_script(["bench.py", "--cpu"])
    assert proc.returncode == 2 and "--impl" in proc.stderr
    assert not proc.stdout.strip()
