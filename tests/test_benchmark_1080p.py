"""The cell ``things-1080p-closed`` as data: its files load through
``benchmark/run.py``'s own ``find``/``load_json``, say what ISSUE 26 asked
for, and are reported under the metrics that apply to it; the server its
configuration describes warms four executables, and the static analyzer
prices its chip within 15 % of what the chip read."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL = "things-1080p-closed"
NEW_METRICS = ("corr_keyblock_share", "corr_l0_ms", "corr_pooled_ms",
               "corr_window_roofline")


@pytest.fixture(scope="module")
def run():
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cell(run):
    bench = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = run.find(bench["workloads"], CELL, "workload")
    cfg_entry = run.find(bench["configs"], entry["config"], "configuration")
    return {
        "bench": bench, "entry": entry, "cfg_entry": cfg_entry,
        "config": run.load_json(os.path.join(REPO, cfg_entry["file"])),
        "traffic": run.load_json(os.path.join(BENCH, "traffic",
                                              entry["traffic"] + ".json")),
        "file": run.load_json(os.path.join(BENCH, "workloads",
                                           CELL + ".json")),
    }


def _serve(config):
    """(RAFTConfig, parsed argv) of the configuration's ``serve_args``."""
    from raft_tpu import cli
    args = cli.parse_args(["-m", "serve"]
                          + [str(a) for a in config["serve_args"]])
    return cli._make_config(args), args


def test_the_cell_is_the_traffic_the_issue_names(cell):
    assert cell["entry"] == {**cell["entry"], "config": "raft-things-1080p",
                             "traffic": "davis1080p-closed", "chips": 1}
    t, f = cell["traffic"], cell["file"]
    assert (t["loop"], t["endpoint"]) == ("closed", "/v1/flow")
    assert (t["height"], t["width"]) == (1080, 1920)
    assert (t["distinct_pairs"], t["max_shift"]) == (16, 12)
    assert f["clients"] == 16 and f["warm_total_seconds"] > 0
    assert f["trace_seconds"] <= 0.4 * cell["bench"]["run_seconds"]
    assert cell["cfg_entry"]["reduced"] == cell["config"]["reduced"] == []


def test_the_configuration_is_raft_things_on_one_more_bucket(cell, run):
    """Every width and the check of ``raft-things.json``; what differs is
    the bucket, the batch, the source and what was assumed."""
    other = run.load_json(os.path.join(BENCH, "configs", "raft-things.json"))
    cfg = cell["config"]
    for key in ("small", "fnet_dim", "hidden_dim", "context_dim",
                "corr_levels", "corr_radius", "iters", "parameters",
                "program", "weights", "check", "precision"):
        assert cfg[key] == other[key], key
    assert cfg["parameters"] == 5257536 and cfg["iters"] == 12
    assert cfg["check"]["ratio_limit"] == 3.0
    want = list(other["serve_args"])
    want[want.index("--buckets") + 1] = "1080x1920"
    want[want.index("--max-batch") + 1] = "8"
    assert cfg["serve_args"] == want
    rconfig, args = _serve(cfg)
    for key, value in cfg["program"].items():     # what system.start checks
        assert getattr(rconfig, key) == value, key
    assert (args.iters, args.max_batch) == (12, 8)
    assert not any(a.startswith("--pallas") for a in cfg["serve_args"])


@pytest.mark.parametrize("metric,reported", [
    ("pairs_per_s", True), ("setup_s", True), ("corr_lookup_roofline", False),
    ("corr_ms", True), ("gru_roofline", True), ("stage_unmapped_share", True),
    ("idle_unnamed_share", True), ("peak_hbm_gb", True),
] + [(m, True) for m in NEW_METRICS])
def test_listed_gives_the_cell_its_metrics(cell, run, metric, reported):
    bench = cell["bench"]
    reporting = {m["name"] for m in bench["end_to_end"]
                 if run.listed(m, CELL, set())}
    assert reporting == {"pairs_per_s", "setup_s"}
    entry = run.find(bench["end_to_end"] + bench["per_layer"], metric,
                     "metric")
    assert run.listed(entry, CELL, reporting) is reported
    if metric in NEW_METRICS:
        # new in PR 26: read only where the program runs the schedule (the
        # 1080p cells: PR 31 appended RAFT-S's), and each with a reader
        # beside the others
        assert entry["workloads"][0] == CELL and entry["layer"] == "kernels"
        assert "things-sintel-closed" not in entry["workloads"]
        assert entry["moves"] == "pairs_per_s"
        base = os.path.join(BENCH, "layer_metrics", metric)
        assert os.path.exists(base + ".json") and os.path.exists(base + ".py")


def test_new_readers_find_nothing_in_an_older_program(run):
    """Laid over the parent's checkout (no key-block counters, and in a
    ``--trace 0`` run no trace), the new readers return None, never raise."""
    import sys
    sys.path.insert(0, BENCH)
    try:
        import readers
        ctx = readers.RunContext(
            config={"iters": 12}, traffic={}, cell={}, records=[], summary={},
            prom_window={"raft_serving_device_calls_total": 3.0},
            max_batch=8, peak={}, memory_peak_bytes=0, shapes={}, trace=None)
        assert readers.read_metric(BENCH, "corr_keyblock_share", ctx) is None
        assert readers.read_metric(BENCH, "corr_window_roofline", ctx) is None
        ctx.prom_window.update(
            raft_serving_corr_keyblocks_visited_total=30.0,
            raft_serving_corr_keyblocks_possible_total=120.0)
        assert readers.read_metric(BENCH, "corr_keyblock_share", ctx) == 25.0
    finally:
        sys.path.remove(BENCH)


def test_window_roofline_counts_the_windowed_algorithm(run):
    """2 x q x (2r+2)^2 x C products and 8 x q x (2r+1)^2 interpolation
    operations a level, the bytes of ``costs.corr_lookup``: at 135x240 a
    324th of the all-pairs count's operations (32,400 keys against 100)."""
    import sys
    sys.path.insert(0, BENCH)
    try:
        import costs
        spec = importlib.util.spec_from_file_location(
            "corr_window_roofline", os.path.join(
                BENCH, "layer_metrics", "corr_window_roofline.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        s = costs.grid_shapes({"small": False, "hidden_dim": 128,
                               "corr_levels": 4, "corr_radius": 4},
                              1080, 1920)
        got, dense = mod.window_lookup(s), costs.corr_lookup(s)
        q = 135 * 240
        assert got["ops"] == 4 * (2 * q * 100 * 256 + 8 * q * 81)
        assert got["bytes"] == dense["bytes"]
        assert got["ops"] < dense["ops"] / 100
    finally:
        sys.path.remove(BENCH)


def test_serve_warms_four_executables_and_the_analyzer_prices_the_chip(cell):
    """``-m serve --buckets 1080x1920 --max-batch 8``: batch 1, 2, 4, 8 of
    one bucket (the engine's warm-up iterates this very list), and
    ``lint/budget.analyze``'s peak with the pair program's temporaries
    within 15 % of the chip's own ``peak_hbm_gb`` in the cell."""
    from raft_tpu.kernel_plans import VMEM_BYTES
    from raft_tpu.lint import budget
    from raft_tpu.serving.config import (ServeConfig, enumerate_warmup_grid,
                                         parse_buckets)

    rconfig, args = _serve(cell["config"])
    sconfig = ServeConfig(buckets=parse_buckets(args.buckets),
                          max_batch=args.max_batch,
                          max_sessions=args.max_sessions)
    keys = enumerate_warmup_grid(rconfig, sconfig)
    assert keys == [("pair", 1080, 1920, b, "fixed") for b in (1, 2, 4, 8)]
    report = budget.analyze(rconfig, sconfig, device_kind="tpu-v5e")
    assert report["grid"]["size"] == 4 and not report["violations"]
    gru = report["buckets"][0]["pallas"]["gru"]
    # 53.23M is what the chip's compiler asked for inside this program
    assert gru["fits"] and gru["vmem_limit"] > 53.23 * 2 ** 20
    assert gru["vmem_limit"] > VMEM_BYTES
    priced = report["totals"]["peak_with_pair_temps_bytes"] / 1e9
    assert abs(priced - PEAK_HBM_GB) / PEAK_HBM_GB < 0.15, priced


# ``peak_hbm_gb`` of things-1080p-closed on the v5e: memory_stats()'s
# peak_bytes_in_use + peak_bytes_reserved after the window, the median of
# 7.536-7.595 over five runs with the batcher's second batch staged (my
# chip runs, PR 27: PERF.md section 5; 7.136-7.197 before it, PR 26); the
# analyzer gives 7.57 (tests/test_budget.py holds it to 0.1 GB)
PEAK_HBM_GB = 7.54
