"""Static budget analyzer tests (lint/budget.py + raftlint --budget).

Covers the ISSUE-16 acceptance surface: eval_shape byte accounting,
SlotPool sizing and donation accounting, the Pallas block-plan arithmetic
(raft_tpu/kernel_plans.py, the kernels' own — identity-checked, not just
value-checked),
headroom monotonicity, EXACT grid-enumeration parity against a live warm
engine, and the CLI gate (JSON output, oversized-config strict failure,
grid-size regression vs a committed baseline).
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

jax = pytest.importorskip("jax")
import numpy as np  # noqa: E402

from raft_tpu.config import RAFTConfig, init_rng  # noqa: E402
from raft_tpu import kernel_plans  # noqa: E402
from raft_tpu.lint import budget  # noqa: E402
from raft_tpu.serving.config import (ServeConfig,  # noqa: E402
                                     enumerate_warmup_grid)

BUCKET = (32, 48)


def small_serve(**kw) -> ServeConfig:
    base = dict(buckets=(BUCKET,), max_batch=2, max_sessions=4, port=0)
    base.update(kw)
    return ServeConfig(**base)


@pytest.fixture(scope="module")
def config():
    return RAFTConfig.small_model(iters=2)


@pytest.fixture(scope="module")
def pspecs(config):
    return budget.param_specs(config)


def programs(config, pspecs, capacity=4, **kw):
    """The engine's table of kinds over abstract params, as
    ``budget.analyze`` builds it."""
    from raft_tpu.serving.engine import Programs
    return Programs(config, pspecs, capacity, **kw)


# ---------------------------------------------------------------- bytes


def test_bytes_of_matches_numpy():
    spec = jax.ShapeDtypeStruct((3, 5, 7), jax.numpy.bfloat16)
    assert budget.bytes_of(spec) == 3 * 5 * 7 * 2
    assert budget.bytes_of(jax.ShapeDtypeStruct((), jax.numpy.float32)) == 4


def test_param_specs_match_real_init(config, pspecs):
    # the abstract tree and a real init agree leaf-for-leaf — the byte
    # model counts exactly what a replica loads
    from raft_tpu.models.raft import init_raft
    params = init_raft(init_rng(0), config)
    real = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert budget.tree_bytes(pspecs) == real > 0


def test_slot_specs_shapes(config, pspecs):
    h, w = BUCKET
    fs, cs, flow = programs(config, pspecs).slot_specs(h, w)
    assert fs.shape[0] == cs.shape[0] == flow.shape[0] == 5  # cap + scratch
    assert flow.shape == (5, h // 8, w // 8, 2)
    assert fs.shape[1:3] == cs.shape[1:3] == (h // 8, w // 8)


# ------------------------------------------------------------ enumeration


def test_enumeration_pairwise_only(config):
    sconfig = small_serve(max_sessions=0)
    keys = enumerate_warmup_grid(config, sconfig)
    assert {k[0] for k in keys} == {"pair"}
    assert len(keys) == len(sconfig.batch_steps)


def test_enumeration_stream_kinds_and_dedup(config):
    sconfig = small_serve(max_batch=1)   # batch_steps == (1,)
    keys = enumerate_warmup_grid(config, sconfig)
    # scommit@1 appears in both the width-1 block and the per-step block:
    # deduplicated exactly like the engine's `if key in self._exec` skip
    assert len(keys) == len(set(keys))
    assert {k[0] for k in keys} == {"pair", "encode", "stream", "szero",
                                    "scommit", "sbatch"}
    assert ("spoison", *BUCKET, 1, "fixed") not in keys
    chaos_keys = enumerate_warmup_grid(config, sconfig, chaos=True)
    assert ("spoison", *BUCKET, 1, "fixed") in chaos_keys


def test_enumeration_policy_resolution(config):
    sconfig = small_serve(iters_policy="converge:1e-2")
    keys = enumerate_warmup_grid(config, sconfig)
    assert {k[4] for k in keys} == {"converge:1e-2"}


def test_grid_parity_with_live_warm_engine(config):
    """THE acceptance pin: analyzer enumeration == live warmup key set,
    zero missing, zero extra."""
    from raft_tpu.models.raft import init_raft
    from raft_tpu.serving.engine import InferenceEngine
    sconfig = small_serve(max_batch=1, max_sessions=2)
    params = init_raft(init_rng(0), config)
    eng = InferenceEngine(config, params, sconfig, stream=True)
    eng.warmup(verbose=False)
    expected = enumerate_warmup_grid(config, sconfig, stream=True,
                                     chaos=False)
    assert sorted(expected) == list(eng.keys())
    assert len(expected) == eng.executables


PALLAS = RAFTConfig.small_model(iters=2, corr_impl="pallas")


@pytest.fixture(scope="module")
def stream_engines():
    """A dense and a ragged stream engine of one configuration whose
    lookup is the Pallas kernel (its key-block counts ride out of the dense
    lookup kinds), nothing warmed."""
    from raft_tpu.models.raft import init_raft
    from raft_tpu.serving.engine import InferenceEngine
    params = init_raft(init_rng(0), PALLAS)
    return {ragged: InferenceEngine(PALLAS, params,
                                    small_serve(ragged=ragged), stream=True)
            for ragged in (False, True)}


def _avals(tree):
    return jax.tree.map(lambda a: (tuple(a.shape), np.dtype(a.dtype).name),
                        tree)


@pytest.mark.parametrize("ragged,kind", [
    (False, "pair"), (False, "encode"), (False, "stream"), (False, "szero"),
    (False, "scommit"), (False, "sbatch"), (False, "spoison"),
    (True, "pair"), (True, "stream"), (True, "sbatch")])
def test_budget_prices_what_the_engine_lowers(stream_engines, monkeypatch,
                                              ragged, kind):
    """The two readers of the engine's table of kinds, held to it: for every
    key of a kind in the warm-up grid, the argument and output trees
    ``kind_footprint`` prices (its ``jax.eval_shape``) are the ones
    ``_compile_traced`` lowers — the key-block counts of the dense lookup
    kinds and ``sizes`` of the ragged ones included."""
    eng = stream_engines[ragged]
    keys = [k for k in enumerate_warmup_grid(PALLAS, eng.sconfig,
                                             stream=True, chaos=True)
            if k[0] == kind]
    assert keys
    table = programs(PALLAS, budget.param_specs(PALLAS), ragged=ragged)
    priced = []
    real = jax.eval_shape

    def spy(fn, *specs):
        priced.append((specs, real(fn, *specs)))
        return priced[-1][1]
    monkeypatch.setattr(jax, "eval_shape", spy)
    # lowering is what is compared: leave the compile out
    monkeypatch.setattr(jax.stages.Lowered, "compile", lambda self: self)
    for key in keys:
        fp = budget.kind_footprint(table, key)
        specs, out = priced[-1]
        lowered = eng._compile_traced(key)
        assert _avals(lowered.in_avals) == _avals((specs, {}))
        assert _avals(lowered.out_info) == _avals(out)
        assert fp["output_bytes"] == budget.tree_bytes(lowered.out_info)
        names = eng.programs.named(kind, out)
        assert ("corr_keyblocks" in names) == (
            kind in ("pair", "stream", "sbatch") and not ragged)


# ------------------------------------------------------- kernel planning


def test_corr_level_plan_values():
    plan = kernel_plans.corr_level_plan(24, 4, 6, q_blk=128,
                                        p_blk_target=4096, radius=4,
                                        grid_w=6)
    assert (plan.t, plan.qp) == (24, 24)
    # six columns in sixteen lanes, eight map rows to a 128-lane row
    assert (plan.w2, plan.w2p, plan.pack) == (6, 16, 8)
    assert plan.h2_blk == 8 == plan.rows_padded and plan.n_pblocks == 1
    assert not plan.banded and plan.band_granules == 0
    # full-scale level 0 at 432x1024: Q = 54*128, map 54x128
    plan = kernel_plans.corr_level_plan(54 * 128, 54, 128, q_blk=128,
                                        p_blk_target=4096, radius=4,
                                        grid_w=128)
    assert plan.t == 128 and plan.w2p == 128
    assert plan.h2_blk == 32 and plan.rows_padded == 64
    assert plan.n_pblocks == 2
    # more rows than a band: a band of 16 rows from a multiple of
    # 4, four bands to the whole map, zero rows for a band that starts on
    # the map's last granule (row 52)
    assert plan.banded and plan.band_granules == 4
    assert (plan.band_granule, plan.band_rows, plan.n_bands,
            plan.band_rows_padded) == (4, 16, 4, 68)


def test_corr_level_plan_refuses_a_degenerate_level():
    # a map pooled away to nothing has no plan: the kernel returns zeros
    # for it before it asks for one
    with pytest.raises(ValueError, match="degenerate level 0x8"):
        kernel_plans.corr_level_plan(64, 0, 8, q_blk=128, p_blk_target=4096,
                                     radius=4, grid_w=8)


@pytest.mark.parametrize("w2,w2p", [(40, 64), (62, 64), (100, 128),
                                    (240, 256)])
def test_corr_level_plan_pads_rows_to_whole_lanes(w2, w2p):
    """A width that is no multiple of 128 (320, 496, 800 and 1920 pixels at
    the 1/8 grid) is stored padded to the lanes that hold it, and ``rows``
    is the map's rows: 100 and 240 columns to whole vector registers, 40
    and 62 to 64 lanes, two map rows to a 128-lane row of the planes
    (PR 43)."""
    h2 = 46
    plan = kernel_plans.corr_level_plan(h2 * w2, h2, w2, q_blk=128,
                                        p_blk_target=4096, radius=4,
                                        grid_w=w2)
    assert plan.w2p == w2p == kernel_plans.corr_row_lanes(w2)
    assert plan.w2p * plan.pack % kernel_plans.LANE == 0
    assert plan.rows == h2
    assert plan.h2_blk == min(h2, 4096 // w2p) and plan.h2_blk % plan.pack == 0
    assert plan.rows_padded == plan.n_pblocks * plan.h2_blk >= h2


def test_kernel_plans_imports_no_jax():
    """The leaf every layer reads: the linter's AST rules and a server that
    loads its executables from the AOT cache import it, and must not pay
    for (or need) jax to know a plan."""
    import subprocess
    code = ("import sys; import raft_tpu.kernel_plans; "
            "bad = [m for m in ('jax', 'jaxlib', 'numpy') "
            "if m in sys.modules]; assert not bad, bad")
    r = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr


def test_gru_row_plan_halo_arithmetic():
    plan = kernel_plans.gru_row_plan(30, 41, 8)
    assert (plan.hp, plan.wc, plan.wp, plan.n_rb) == (32, 48, 52, 4)
    with pytest.raises(ValueError):
        kernel_plans.gru_row_plan(30, 41, kernel_plans.GRU_HALO - 1)


def test_kernels_share_the_budget_plan_helpers():
    # identity, not equality: the analyzer must budget with the SAME
    # functions the kernels execute (lint rule B4's structural guarantee)
    from raft_tpu.ops import corr_pallas, gru_pallas
    assert (corr_pallas.corr_level_plan is budget.corr_level_plan
            is kernel_plans.corr_level_plan)
    assert (gru_pallas.gru_row_plan is budget.gru_row_plan
            is kernel_plans.gru_row_plan)
    assert gru_pallas._HALO == kernel_plans.GRU_HALO
    assert gru_pallas._K == kernel_plans.GRU_TAPS


def test_vmem_envelopes(config):
    corr = budget.corr_vmem_envelope(config, BUCKET)
    assert corr["fits"] and not corr["active"]    # small model: dense corr
    assert corr["worst_block_bytes"] > 0
    assert len(corr["levels"]) == config.corr_levels
    full = RAFTConfig.full()
    env = budget.corr_vmem_envelope(full, (432, 1024))
    assert env["fits"] and env["worst_block_bytes"] < kernel_plans.VMEM_BYTES
    # a huge Q-block makes the [T, Pblk] corr tile alone blow VMEM — the
    # envelope must overflow and say so
    fat = RAFTConfig.full(pallas_q_blk=4096, corr_impl="pallas")
    env = budget.corr_vmem_envelope(fat, (432, 1024))
    assert not env["fits"] and env["active"] and env["checks"]


@pytest.mark.parametrize("kw,planes,mib", [
    # float32 maps: one float32 plane a level; the window's float32 tap
    # scratch and lane-dense output block at the size of their tiles
    # (kernel_plans.corr_window_vmem: 0.25 MiB here since PR 32, whose body
    # gathers a window's taps through [T, 128] lane tiles where PRs 21-31
    # priced one-hot matrices of [T, 9, rows] and [T, 9, lanes])
    # (level 0, 55 rows, is banded: a step holds a band of 16 rows, as four
    # double-buffered blocks of 4, where PRs 21-35 held a block of 32; since
    # PR 43 the pooled levels' rows lie 2, 4 and 8 to a 128-lane row: level
    # 1 a band of 16 rows of 64 lanes where it held 27 rows of 128 (11.94 /
    # 15.13 / 11.88 MiB), levels 2 and 3 one block of 16 x 32 and 8 x 16
    # where they held 13 x 128 and 6 x 128 (6.56 / 8.0 / 6.5 and 3.88 / 4.44
    # / 3.81))
    (dict(compute_dtype="float32"), [[1, "float32"]] * 4,
     [7.69, 4.69, 3.19, 2.0]),
    # bfloat16 maps at 'highest': level 0 holds one bfloat16 plane (2 MB of
    # double-buffered f2 less), the pooled levels three
    (dict(compute_dtype="bfloat16"),
     [[1, "bfloat16"]] + [[3, "bfloat16"]] * 3, [5.5, 5.5, 3.5, 1.94]),
    # 'default' keeps the float32 blocks the MXU rounds itself; the output
    # block is bfloat16 all the same (the update block's dtype)
    (dict(compute_dtype="bfloat16", corr_precision="default"),
     [[1, "float32"]] * 4, [7.63, 4.63, 3.13, 1.94]),
])
def test_corr_envelope_prices_the_dtypes_the_kernel_holds(kw, planes, mib):
    full = RAFTConfig.full(corr_impl="pallas", **kw)
    env = budget.corr_vmem_envelope(full, (440, 1024))
    assert [lv["f2_planes"] for lv in env["levels"]] == planes
    got = [round(lv["block_bytes"] / 2 ** 20, 2) for lv in env["levels"]]
    assert got == mib
    assert env["fits"] and env["worst_block_bytes"] < kernel_plans.VMEM_BYTES


def test_gru_vmem_envelope_scales_with_block_rows():
    full = RAFTConfig.full()
    small_rows = budget.gru_vmem_envelope(full, (432, 1024), 128)
    fat = RAFTConfig.full(gru_block_rows=64)
    big_rows = budget.gru_vmem_envelope(fat, (432, 1024), 128)
    assert big_rows["block_bytes"] > small_rows["block_bytes"]
    assert big_rows["plan"]["n_rb"] < small_rows["plan"]["n_rb"]


# ------------------------------------------------------ memory model


def test_donation_accounting_scommit(config, pspecs):
    h, w = BUCKET
    key = ("scommit", h, w, 1, "fixed")
    donated = budget.kind_footprint(programs(config, pspecs, donate=True),
                                    key)
    copied = budget.kind_footprint(programs(config, pspecs, donate=False),
                                   key)
    pool_bytes = sum(budget.bytes_of(s) for s in
                     programs(config, pspecs).slot_specs(h, w))
    # donated outputs alias the input pool buffers; without donation the
    # scatter materializes a full second copy of the pool
    assert donated["donated_bytes"] == pool_bytes
    assert copied["donated_bytes"] == 0
    assert (copied["transient_bytes"] - donated["transient_bytes"]
            == pool_bytes)


def test_szero_builds_residents_not_transients(config, pspecs):
    h, w = BUCKET
    fp = budget.kind_footprint(programs(config, pspecs),
                               ("szero", h, w, 1, "fixed"))
    assert fp["transient_bytes"] == 0
    assert fp["output_bytes"] == fp["pool_bytes"] > 0


def test_pair_footprint_scales_with_batch(config, pspecs):
    h, w = BUCKET
    table = programs(config, pspecs, capacity=1)
    f1 = budget.kind_footprint(table, ("pair", h, w, 1, "fixed"))
    f2 = budget.kind_footprint(table, ("pair", h, w, 2, "fixed"))
    assert f2["input_bytes"] == 2 * f1["input_bytes"]
    assert f2["transient_bytes"] > f1["transient_bytes"]
    # the batcher keeps a second batch's inputs on the device beside the
    # running one, and the running one's flow beside the next one's
    for f in (f1, f2):
        assert f["staged_bytes"] == f["input_bytes"] + f["output_bytes"]
        assert f["transient_bytes"] == 2 * f["staged_bytes"]
    enc = budget.kind_footprint(table, ("encode", h, w, 1, "fixed"))
    assert enc["staged_bytes"] == 0       # only pair calls are pipelined


# ``peak_hbm_gb`` of the benchmark's two cells on the v5e with the batcher's
# second batch staged: memory_stats()'s peak_bytes_in_use +
# peak_bytes_reserved after the window, the medians of 6.544-6.581 over six
# runs and of 7.536-7.595 over five (my chip runs, PR 27: PERF.md section
# 5; the parent read 6.20-6.24 and 7.14-7.20).  The analyzer gives 6.58 and
# 7.57; peak_bytes_in_use alone read 0.917 and 1.061 GB where two sets of
# inputs and outputs are 0.923 and 1.062.
@pytest.mark.parametrize("name,measured_gb", [
    ("raft-things", 6.56), ("raft-things-1080p", 7.54)])
def test_analyzer_prices_the_benchmark_configurations(name, measured_gb):
    """The chip at its fullest under the pipelined batcher: params, the
    largest pair program's temporaries, its inputs and outputs and a second
    set of both — under the v5e's 16 GB and within 0.1 GB of what the chip
    reported."""
    from raft_tpu import cli
    from raft_tpu.serving.config import parse_buckets
    conf = json.loads((REPO / "benchmark" / "configs" / f"{name}.json")
                      .read_text())
    args = cli.parse_args(["-m", "serve"]
                          + [str(a) for a in conf["serve_args"]])
    sconfig = ServeConfig(buckets=parse_buckets(args.buckets),
                          max_batch=args.max_batch,
                          max_sessions=args.max_sessions)
    report = budget.analyze(cli._make_config(args), sconfig,
                            device_kind="tpu-v5e")
    assert not report["violations"]
    priced = report["totals"]["peak_with_pair_temps_bytes"]
    assert priced < report["totals"]["hbm_budget_bytes"] == 16 * 1024 ** 3
    assert abs(priced / 1e9 - measured_gb) < 0.1, priced


# ``memory_analysis().temp_size_in_bytes`` of RAFT-S's served pair program
# compiled for a described v5e (sandbox compiles, PR 31): the benchmark's
# batch, PR 30's batch of 16, Sintel's size at 32 and at 64 (where 128
# images fill the lanes and a pixel costs the 102 B of PERF.md's older note)
@pytest.mark.parametrize("b,h,w,compiled", [
    (8, 1080, 1920, 7_007_982_080), (4, 1080, 1920, 3_461_145_600),
    (16, 1080, 1920, 12_946_424_832), (8, 440, 1024, 1_528_796_160),
    (32, 440, 1024, 2_863_086_592), (48, 440, 1024, 2_904_888_832),
    (64, 440, 1024, 2_947_110_400)])
def test_pair_temp_bytes_prices_the_small_program(b, h, w, compiled):
    """From above, and by less than a tenth: the constant is the unpadded
    figure, the factor the lane padding of the half-resolution 32-channel
    maps (channels or images in the lanes, whichever pads less)."""
    small = RAFTConfig.small_model(iters=20, compute_dtype="bfloat16",
                                   corr_impl="pallas")
    priced = budget.pair_temp_bytes(small, h, w, b)
    assert compiled <= priced < 1.10 * compiled, priced / compiled
    assert priced == int(budget.SMALL_PAIR_TEMP_BYTES_PER_PIXEL
                         * budget.small_lane_padding(b) * b * h * w)


@pytest.mark.parametrize("b,want", [(1, 4.0), (8, 4.0), (16, 4.0),
                                    (32, 2.0), (48, 4 / 3), (64, 1.0),
                                    (65, 256 / 130)])
def test_small_lane_padding(b, want):
    assert budget.small_lane_padding(b) == pytest.approx(want)


@pytest.mark.parametrize("small,kw", [
    (True, {"compute_dtype": "float32"}),
    (True, {"corr_impl": "dense"}),
    (False, {"compute_dtype": "float32"}),
    (False, {"corr_impl": "dense"}),
    (False, {"gru_impl": "xla"})])
def test_pair_temp_bytes_prices_no_program_it_was_not_read_from(small, kw):
    """A float32 program, a lookup that stores its volume, the full model
    without its GRU kernel: None, for either model (not priced beats priced
    wrong); the two served programs have a price each."""
    make = RAFTConfig.small_model if small else RAFTConfig.full
    base = dict(compute_dtype="bfloat16", corr_impl="pallas",
                gru_impl="xla" if small else "pallas")
    assert budget.pair_temp_bytes(make(**base), 440, 1024, 4) is not None
    assert budget.pair_temp_bytes(make(**{**base, **kw}), 440, 1024, 4) is None


def test_analyze_report_shape_and_headroom_monotone(config):
    reports = [budget.analyze(config, small_serve(max_sessions=s),
                              device_kind="cpu")
               for s in (2, 8, 32)]
    heads = [r["totals"]["headroom_bytes"] for r in reports]
    assert heads[0] > heads[1] > heads[2]        # monotone in max_sessions
    rep = reports[0]
    assert rep["grid"]["size"] == len(rep["grid"]["keys"])
    assert rep["totals"]["peak_bytes"] == (
        rep["totals"]["resident_bytes"]
        + rep["totals"]["peak_transient_bytes"])
    assert rep["violations"] == []
    # the closed-form fit bound is consistent with its own model: the
    # fitted session count must itself pass, one more must not
    fit = rep["totals"]["max_sessions_fit"]
    per = rep["totals"]["per_session_bytes"]
    hbm = rep["totals"]["hbm_budget_bytes"]
    used_at_fit = (rep["params_bytes"] + (fit + 1) * per
                   + rep["totals"]["peak_transient_bytes"])
    assert used_at_fit <= hbm < used_at_fit + per


def test_analyze_flags_oversized_sessions(config):
    rep = budget.analyze(config, small_serve(max_sessions=10_000_000),
                         device_kind="cpu")
    assert any("does not fit" in v for v in rep["violations"])
    assert any("exceeds" in v for v in rep["violations"])


def test_analyze_cpu_disables_donation_by_default(config):
    cpu = budget.analyze(config, small_serve(), device_kind="cpu")
    tpu = budget.analyze(config, small_serve(), device_kind="tpu-v4")
    assert cpu["donation"] is False and tpu["donation"] is True
    # CPU commits copy the pool => strictly larger peak transients
    assert (cpu["totals"]["peak_transient_bytes"]
            >= tpu["totals"]["peak_transient_bytes"])


# ------------------------------------------------------------- CLI gate


def _budget_cli(tmp_path, *extra, serve="--small --buckets 32x48 "
                "--max-batch 1 --max-sessions 2"):
    import raftlint as rl
    out = tmp_path / "BUDGET.json"
    rc = rl.main(["--budget", "--device-kind", "cpu", "--serve-args",
                  serve, "--budget-out", str(out), *extra])
    return rc, (json.loads(out.read_text()) if out.exists() else None)


def test_budget_cli_json_report(tmp_path, capsys):
    rc, report = _budget_cli(tmp_path, "--json")
    assert rc == 0
    printed = json.loads(capsys.readouterr().out)
    assert printed["grid"]["size"] == report["grid"]["size"] == 6
    assert printed["violations"] == []
    assert {tuple(k)[0] for k in report["grid"]["keys"]} == {
        "pair", "encode", "stream", "szero", "scommit", "sbatch"}


def test_budget_cli_strict_fails_oversized(tmp_path, capsys):
    # the CI-gate acceptance: a config whose sessions blow the device
    # budget exits non-zero under --strict
    rc, report = _budget_cli(
        tmp_path, "--strict",
        serve="--small --buckets 32x48 --max-sessions 10000000")
    assert rc == 1
    assert report["strict_failures"]
    assert "FAIL" in capsys.readouterr().err


def test_budget_cli_strict_grid_regression(tmp_path, capsys):
    rc, report = _budget_cli(tmp_path)
    assert rc == 0
    # commit a baseline with a SMALLER grid but the same signature: the
    # current surface now reads as a cold-start regression
    base = dict(report)
    base["grid"] = dict(report["grid"], size=report["grid"]["size"] - 1)
    baseline = tmp_path / "BASE.json"
    baseline.write_text(json.dumps(base))
    import raftlint as rl
    rc = rl.main(["--budget", "--strict", "--device-kind", "cpu",
                  "--serve-args", "--small --buckets 32x48 --max-batch 1 "
                  "--max-sessions 2", "--budget-baseline", str(baseline)])
    assert rc == 1
    assert "compile surface grew" in capsys.readouterr().err
    # different signature => no comparison, strict passes
    rc = rl.main(["--budget", "--strict", "--device-kind", "cpu",
                  "--serve-args", "--small --buckets 32x48 --max-batch 2 "
                  "--max-sessions 2", "--budget-baseline", str(baseline)])
    assert rc == 0


def test_budget_cli_rejects_bad_serve_args(capsys):
    import raftlint as rl
    assert rl.main(["--budget", "--serve-args", "--frobnicate 3"]) == 2
    assert "unknown" in capsys.readouterr().err


def test_committed_budget_baseline_matches_default_config():
    """BUDGET.json at the repo root IS the default-config tpu-v4 report —
    regenerate with `tools/raftlint.py --budget --budget-out BUDGET.json`
    when the surface deliberately changes."""
    doc = json.loads((REPO / "BUDGET.json").read_text())
    rep = budget.analyze(RAFTConfig.full(), ServeConfig(),
                         device_kind="tpu-v4")
    assert doc["config_signature"] == rep["config_signature"]
    assert doc["grid"]["size"] == rep["grid"]["size"]
    assert doc["grid"]["keys"] == rep["grid"]["keys"]
    assert doc["violations"] == []
