"""The cell ``small-1080p-b8-closed`` as data, and RAFT-S as a supported
configuration: the cell's files load through ``benchmark/run.py``'s own
``find``/``load_json`` and say what ISSUE 31 asked for; the configuration's
serve arguments parse to RAFT-S's published widths; the three metrics it
brings read the small program's trace and nothing of the full model's; the
server warms four executables and the analyzer prices its chip; and the
SERVED small program, fetched over HTTP from a ``FlowServer`` on the CPU,
agrees with ``benchmark/reference.py`` on seeded weights."""

import functools
import http.client
import importlib.util
import json
import os
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
DATA = os.path.join(REPO, "tests", "data")
CELL = "small-1080p-b8-closed"
CONFIG = "raft-small-1080p"
NEW_METRICS = ("convgru_ms", "update_rest_ms", "convgru_roofline")
# every per-layer metric that lists things-1080p-closed, but gru_roofline
SHARED_METRICS = (
    "batch_fill", "host_path_ms", "compile_misses", "update_ms",
    "outside_loop_ms", "device_idle_share", "peak_hbm_gb", "decode_ms",
    "admit_ms", "deliver_ms", "encode_ms", "batch_prep_ms", "h2d_ms",
    "fetch_ms", "host_unspanned_ms", "batcher_serial_ms", "idle_host_prep_ms",
    "idle_h2d_ms", "idle_fetch_ms", "idle_unnamed_share", "corr_ms",
    "encoders_ms", "upsample_ms", "stage_unmapped_share",
    "corr_keyblock_share", "corr_l0_ms", "corr_pooled_ms",
    "corr_window_roofline", "batch_staged_ahead_share")
# PR 37: the host stages' CPU seconds, the stalls, and PR 36's tiles counter;
# PR 38: the grid steps the lookup's launches took, over the same tiles
STAGE_METRICS = (
    "batcher_cpu_ms", "batcher_offcpu_ms", "deliver_sentinel_ms",
    "deliver_offcpu_ms", "handler_cpu_ms", "host_stall_s",
    "corr_bands_per_tile", "corr_steps_per_tile")
SHARED_METRICS += STAGE_METRICS
# PR 43: the share of the lookup's key lanes that held a key
SHARED_METRICS += ("corr_lane_fill",)


@pytest.fixture(scope="module")
def bench_modules():
    """The benchmark's own modules, importable by their bare names as
    ``run.py`` imports them."""
    sys.path.insert(0, BENCH)
    try:
        import check
        import costs
        import inputs
        import readers
        import reference
        import stage_cpu  # noqa: F401  (the readers' ``from stage_cpu import``)
        import stages
        import system
        import tracered
        import weights
    finally:
        sys.path.remove(BENCH)
    return {"check": check, "costs": costs, "inputs": inputs,
            "readers": readers, "reference": reference, "stages": stages,
            "system": system, "tracered": tracered, "weights": weights}


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


def _serve(config, **replace):
    """(RAFTConfig, parsed argv) of the configuration's ``serve_args``, with
    the values of the flags in ``replace`` (``max_batch="2"``) exchanged."""
    from raft_tpu import cli
    argv = [str(a) for a in config["serve_args"]]
    for flag, value in replace.items():
        argv[argv.index("--" + flag.replace("_", "-")) + 1] = value
    args = cli.parse_args(["-m", "serve"] + argv)
    return cli._make_config(args), args, argv


# ------------------------------------------------------------- the cell's data

@pytest.mark.parametrize("what,want", [
    ("config", CONFIG), ("traffic", "davis1080p-closed"), ("chips", 1)])
def test_the_cell_is_the_one_the_issue_names(cell, what, want):
    assert cell["entry"][what] == want


def test_the_traffic_is_full_hd_closed_loop_from_sixteen_clients(cell):
    t, f = cell["traffic"], cell["file"]
    assert (t["loop"], t["endpoint"]) == ("closed", "/v1/flow")
    assert (t["height"], t["width"]) == (1080, 1920)
    assert (t["distinct_pairs"], t["max_shift"]) == (16, 12)
    assert f["clients"] == 16 and f["warm_total_seconds"] == 20
    # at least four whole device batches of about 1.6 s in the capture, and
    # no more than run.py's share of the window
    assert 8 <= f["trace_seconds"] <= 0.4 * cell["bench"]["run_seconds"]
    assert cell["cfg_entry"]["reduced"] == cell["config"]["reduced"] == []
    assert len(cell["cfg_entry"]["source"]) <= 200
    assert len(cell["entry"]["why"]) <= 200


@pytest.mark.parametrize("key,want", [
    ("small", True), ("fnet_dim", 128), ("hidden_dim", 96),
    ("context_dim", 64), ("corr_levels", 4), ("corr_radius", 3),
    ("iters", 20), ("parameters", 990162)])
def test_the_configuration_states_raft_s_as_published(cell, key, want):
    assert cell["config"][key] == want


def test_serve_args_parse_to_the_published_widths_and_the_program(
        cell, bench_modules):
    cfg = cell["config"]
    assert cfg["serve_args"] == [
        "--small", "--buckets", "1080x1920", "--iters", "20", "--dtype",
        "bfloat16", "--max-batch", "8", "--max-wait-ms", "5",
        "--deadline-ms", "30000", "--max-sessions", "0", "--corr-impl",
        "pallas", "--gru-impl", "xla"]
    rconfig, args, _ = _serve(cfg)
    for key, value in cfg["program"].items():     # what system.start checks
        assert getattr(rconfig, key) == value, key
    assert cfg["program"]["gru_impl"] == "xla"
    assert (rconfig.hidden_dim, rconfig.context_dim, rconfig.corr_levels,
            rconfig.corr_radius) == (96, 64, 4, 3)
    assert (args.iters, args.max_batch, args.max_sessions) == (20, 8, 0)
    # the benchmark's weights are the program's parameters, all 990,162
    weights = bench_modules["weights"]
    assert weights.n_parameters(weights.model_cfg(cfg)) == cfg["parameters"]
    assert cfg["check"]["own_precision"] == "bfloat16"
    assert any("demo.py" in a and "20" in a for a in cfg["assumed"])


def test_the_parked_configuration_is_left_alone(run):
    """``raft-small.json`` (440x1024, ``max_batch`` 4) is no cell's file and
    was not edited: this PR's configuration is a file of its own."""
    bench = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    files = [c["file"] for c in bench["configs"]]
    assert "benchmark/configs/raft-small.json" not in files
    assert files.count("benchmark/configs/raft-small-1080p.json") == 1
    parked = run.load_json(os.path.join(BENCH, "configs", "raft-small.json"))
    assert parked["iters"] == 12 and "parked" in parked


@pytest.mark.parametrize("metric,reported", [
    ("pairs_per_s", True), ("setup_s", True), ("gru_roofline", False),
    ("corr_lookup_roofline", False)]
    + [(m, True) for m in SHARED_METRICS + NEW_METRICS])
def test_listed_gives_the_cell_its_metrics(cell, run, metric, reported):
    bench = cell["bench"]
    reporting = {m["name"] for m in bench["end_to_end"]
                 if run.listed(m, CELL, set())}
    assert reporting == {"pairs_per_s", "setup_s"}
    entry = run.find(bench["end_to_end"] + bench["per_layer"], metric,
                     "metric")
    assert run.listed(entry, CELL, reporting) is reported
    if metric in NEW_METRICS:
        # new in PR 31: read only where the program is the small model, each
        # with a reader beside the others
        assert entry["workloads"] == [CELL] and entry["layer"] == "model"
        assert entry["moves"] == "pairs_per_s"
        base = os.path.join(BENCH, "layer_metrics", metric)
        assert os.path.exists(base + ".json") and os.path.exists(base + ".py")


def test_every_metric_that_lists_the_1080p_things_cell_lists_this_one(cell):
    per_layer = cell["bench"]["per_layer"]
    shared = [m["name"] for m in per_layer
              if "things-1080p-closed" in m.get("workloads", ())
              and m["name"] != "gru_roofline"]
    assert sorted(shared) == sorted(SHARED_METRICS)
    for m in per_layer:
        if m["name"] in shared:
            # appended after the cell it shares the metric with (and cells
            # that later PRs append come after it in turn)
            cells = m["workloads"]
            assert cells.index(CELL) > cells.index("things-1080p-closed"), \
                m["name"]


# ------------------------------------------- the readers of the stage counters

def _stage_window(with_cpu: bool) -> dict:
    """A window of /metrics made by hand: ten device batches that answered
    eighty requests.  ``with_cpu`` False: what the parent of PR 37 exposes,
    the wall seconds alone and no sentinel, stall or tile counter."""
    wall = {"batch.take": 0.3, "batch.form": 0.1, "batch.pad": 0.8,
            "engine.h2d": 0.4, "engine.dispatch": 0.05, "engine.wait": 4.0,
            "engine.fetch": 0.4, "batch.deliver": 2.5, "http.decode": 8.0,
            "http.admit": 0.08, "http.encode": 1.6, "http.respond": 0.4}
    cpu = {"batch.take": 0.01, "batch.form": 0.05, "batch.pad": 0.5,
           "engine.h2d": 0.1, "engine.dispatch": 0.04, "engine.wait": 0.01,
           "engine.fetch": 0.3, "batch.deliver": 1.5, "http.decode": 4.0,
           "http.admit": 0.04, "http.encode": 0.8, "http.respond": 0.2,
           "batch.deliver.sentinel": 0.9}
    prom = {"raft_serving_device_calls_total": 10.0,
            'raft_serving_requests_total{status="ok"}': 80.0,
            'raft_serving_requests_total{status="timeout"}': 0.0,
            "raft_serving_corr_keyblocks_visited_total": 1200.0,
            "raft_serving_corr_keyblocks_possible_total": 5400.0}
    for stage, v in wall.items():
        prom[f'raft_serving_stage_seconds_total{{stage="{stage}"}}'] = v
    if with_cpu:
        prom['raft_serving_stage_seconds_total'
             '{stage="batch.deliver.sentinel"}'] = 1.0
        prom["raft_serving_corr_tiles_total"] = 1000.0
        prom["raft_serving_corr_grid_steps_total"] = 1500.0
        for stage, v in cpu.items():
            prom[f'raft_serving_stage_cpu_seconds_total{{stage="{stage}"}}'] \
                = v
            prom[f'raft_serving_stalled_seconds_total{{stage="{stage}"}}'] = (
                3.5 if stage == "engine.wait" else 0.0)
    return prom


def _read_counters(bench_modules, metric, prom):
    """``metric`` read from a window that holds counters alone."""
    readers = bench_modules["readers"]
    ctx = readers.RunContext(
        config={}, traffic={}, cell={}, records=[], summary={},
        prom_window=prom, max_batch=8, peak={}, memory_peak_bytes=0,
        shapes={})
    return readers.read_metric(BENCH, metric, ctx)


@pytest.mark.parametrize("metric,want", [
    ("batcher_cpu_ms", 249.0),         # (.05 + .5 + .1 + .04 + .3 + 1.5) / 10
    ("batcher_offcpu_ms", 136.0),      # (.05 + .3 + .01 + 1.0) / 10
    ("deliver_sentinel_ms", 100.0), ("deliver_offcpu_ms", 100.0),
    ("handler_cpu_ms", 63.0),          # (4 + .04 + .8 + .2) / 80
    ("host_stall_s", 3.5), ("corr_bands_per_tile", 1.2),
    ("corr_steps_per_tile", 1.5),
    # and what read the wall seconds before reads what it read: the
    # sentinel's label is not ``stage="batch.deliver"``
    ("batcher_serial_ms", 425.0)])
def test_stage_counter_readers(bench_modules, metric, want):
    read = functools.partial(_read_counters, bench_modules, metric)

    assert read(_stage_window(True)) == pytest.approx(want)
    # the parent's window: nothing to read, and the old reader unmoved
    assert read(_stage_window(False)) == (
        pytest.approx(want) if metric not in STAGE_METRICS else None)
    # a window in which nothing ran: no device call, no answer
    idle = dict.fromkeys(_stage_window(True), 0.0)
    assert read(idle) == (0.0 if metric == "host_stall_s" else None)


@pytest.mark.parametrize("program,want", [
    ("PR 38", 1.5),     # steps and tiles
    ("PR 37", None),    # tiles, which corr_bands_per_tile reads, no steps
    ("PR 35", None)])   # neither
def test_corr_steps_per_tile_needs_its_counter(bench_modules, program, want):
    """``corr_steps_per_tile`` is grid steps over tiles, and None (the
    harness leaves the metric out, it does not raise) on a program that
    exports no ``raft_serving_corr_grid_steps_total``: the parent, on which
    the driver runs this reader too."""
    prom = _stage_window(program != "PR 35")
    if program != "PR 38":
        prom.pop("raft_serving_corr_grid_steps_total", None)
    got = _read_counters(bench_modules, "corr_steps_per_tile", prom)
    assert got == (None if want is None else pytest.approx(want))
    if program == "PR 37":
        assert _read_counters(bench_modules, "corr_bands_per_tile",
                              prom) == pytest.approx(1.2)


@pytest.mark.parametrize("program,want", [
    ("PR 43", 93.75),       # stored and live key positions
    ("PR 42", None),        # no such counter: the parent
    ("stored-alone", 0.0),  # a window in which no live position was added
    ("idle", None)])        # the counter at rest: no lookup ran
def test_corr_lane_fill_reads_the_key_positions(bench_modules, program,
                                                want):
    """``corr_lane_fill`` (PR 43) is 100 x live / stored of
    ``raft_serving_corr_key_positions_total`` over the window, whatever
    other labels a series carries, and None (the harness leaves the metric
    out, it does not raise) on a program that exports no such counter: the
    parent, on which the driver runs this reader too."""
    prom = _stage_window(True)
    name = "raft_serving_corr_key_positions_total"
    if program != "PR 42":
        stored, live = {"PR 43": (2.56e9, 2.4e9), "stored-alone": (4096.0, 0.0),
                        "idle": (0.0, 0.0)}[program]
        prom[name + '{kind="stored"}'] = stored
        prom[name + '{kind="live"}'] = live
    got = _read_counters(bench_modules, "corr_lane_fill", prom)
    assert got == (None if want is None else pytest.approx(want))
    # the counters its neighbours read are untouched by it
    assert _read_counters(bench_modules, "corr_steps_per_tile",
                          prom) == pytest.approx(1.5)


def test_corr_lane_fill_is_listed_for_all_five_cells(cell):
    """(And for the sixth, which PR 45 appended; its five metrics come after
    this one.)"""
    entry = cell["bench"]["per_layer"][-6]
    assert entry == {
        "name": "corr_lane_fill", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "pairs_per_s",
        "workloads": [w["name"] for w in cell["bench"]["workloads"]]}
    assert len(entry["workloads"]) == 6


@pytest.mark.parametrize("metric", STAGE_METRICS)
def test_stage_metrics_are_listed_for_the_three_cells(cell, metric):
    entry = next(m for m in cell["bench"]["per_layer"] if m["name"] == metric)
    # the three cells of PR 37, in the order they were added; cells that
    # later PRs append come after them
    assert entry["workloads"][:3] == ["things-sintel-closed",
                                      "things-1080p-closed", CELL]
    assert (entry["source"], entry["moves"]) == ("program_counter",
                                                 "pairs_per_s")
    assert entry["layer"] == {"handler_cpu_ms": "server host path",
                              "corr_bands_per_tile": "kernels",
                              "corr_steps_per_tile": "kernels"}.get(
                                  metric, "server")
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
        spec = json.load(f)
    # the file says what is read: stage labels, or the counters' names
    assert spec["params"] and spec["note"]


# ---------------------------------------------------------- the three readers

def _window(bench_modules, ops_file, maps, small, iters, calls, rows):
    """A ``RunContext`` over a recorded window reduced to its operations:
    ``ops_file`` holds [label, total ns, events] of every operation the
    busiest device ran (``tracered.Trace.ops``), as a chip run left them;
    ``calls`` device batches carried ``rows`` real pairs in that run."""
    tracered, readers = bench_modules["tracered"], bench_modules["readers"]
    with open(ops_file) as f:
        doc = json.load(f)
    ops = {label: tracered.Op(label.split(" ", 1)[0], label, ns, n, ns)
           for label, ns, n in doc["ops"]}
    dev = {"busy_ns": sum(o.total_ns for o in ops.values()), "gaps": [],
           "ops": ops, "modules": []}
    trace = tracered.Trace(window_s=doc["window_s"], devices={0: dev},
                           host_events=[], clipped=True)
    prom = {"raft_serving_device_calls_total": float(calls),
            'raft_serving_device_rows_total{kind="real"}': float(rows)}
    costs = bench_modules["costs"]
    config = {"small": small, "iters": iters, "hidden_dim": 96 if small
              else 128, "corr_levels": 4, "corr_radius": 3 if small else 4}
    ctx = readers.RunContext(
        config=config, traffic={}, cell={}, records=[], summary={},
        prom_window=prom, max_batch=8,
        peak={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        memory_peak_bytes=0, shapes=costs.grid_shapes(config, 1080, 1920),
        trace=trace)
    return ctx, maps


SMALL_OPS = os.path.join(DATA, "small_1080p_b8.ops.json")
SMALL_MAP = os.path.join(DATA, "small_1080p_b8.stages.json")


@pytest.mark.parametrize("metric,want", [
    ("convgru_ms", 7.5500), ("update_rest_ms", 6.3801),
    ("convgru_roofline", 40.2018), ("corr_ms", 167.5647),
    ("encoders_ms", 18.1921), ("stage_unmapped_share", 0.6477)])
def test_readers_read_the_small_programs_window(bench_modules, metric, want,
                                                monkeypatch):
    """On the operations of the cell's traced window (a v5e, seed
    2100003117, PR 31: 27 device batches carried 209 pairs) and the stage map
    the engine wrote beside its batch-8 executable: the number that run's
    result line reported, for the three new metrics and for the stage readers
    the cell shares with the things cells."""
    stages, readers = bench_modules["stages"], bench_modules["readers"]
    ctx, maps = _window(bench_modules, SMALL_OPS, SMALL_MAP, True, 20, 27,
                        209)
    monkeypatch.setattr(stages, "STAGE_MAP_GLOB", maps)
    stages.load_stage_maps.cache_clear()
    try:
        got = readers.read_metric(BENCH, metric, ctx)
        assert got == pytest.approx(want, rel=1e-4)
        if metric == "convgru_roofline":
            ms = readers.read_metric(BENCH, "convgru_ms", ctx)
            # 20 updates of 0.1518 ms each at the v5e's 197 TFLOP/s
            assert got == pytest.approx(100 * 20 * 0.15176 / ms, rel=1e-3)
        # no trace (a --trace 0 run), or no map (an older program): nothing
        ctx.trace = None
        assert readers.read_metric(BENCH, metric, ctx) is None
    finally:
        stages.load_stage_maps.cache_clear()


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_find_nothing_in_a_things_program(bench_modules, metric,
                                                      monkeypatch):
    """The full model's SepConvGRU kernel runs under ``update/gru`` too, and
    its update block has other widths and a mask head: on the window the
    benchmark's own tests keep of ``things-sintel-closed`` (PR 24) the readers
    answer None, while the stage reader they share reads that window."""
    stages, readers = bench_modules["stages"], bench_modules["readers"]
    tracered = bench_modules["tracered"]
    data = os.path.join(BENCH, "tests", "data")
    maps = os.path.join(data, "things_closed_annotated.stages.json")
    monkeypatch.setattr(stages, "STAGE_MAP_GLOB", maps)
    stages.load_stage_maps.cache_clear()
    try:
        trace = tracered.reduce_trace(
            os.path.join(data, "things_closed_annotated.xplane.pb"))
        prom = {"raft_serving_device_calls_total": 18.0,
                'raft_serving_device_rows_total{kind="real"}': 545.0}
        ctx = readers.RunContext(
            config={"small": False, "iters": 12}, traffic={}, cell={},
            records=[], summary={}, prom_window=prom, max_batch=32,
            peak={"flops_per_s": 197e12, "bytes_per_s": 819e9},
            memory_peak_bytes=0, shapes={}, trace=trace)
        assert readers.read_metric(BENCH, metric, ctx) is None
        assert stages.stage_ms(ctx, {"stage": "(^|/)update/gru(/|$)"}) > 1.0
    finally:
        stages.load_stage_maps.cache_clear()


def test_convgru_roofline_counts_the_algorithm(bench_modules):
    """One 3x3 ConvGRU update with hoisted context terms at 135x240, by
    hand: 3 gates x 9 taps x (96 + 82) -> 96 channels a query, a multiply
    and an add each; bfloat16 state in and out, motion features in, three
    hoisted terms in, and the weights.  Compute-bound on a v5e."""
    costs = bench_modules["costs"]
    spec = importlib.util.spec_from_file_location(
        "convgru_roofline", os.path.join(BENCH, "layer_metrics",
                                         "convgru_roofline.py"))
    sys.path.insert(0, BENCH)
    try:
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(BENCH)
    s = costs.grid_shapes({"small": True, "hidden_dim": 96, "corr_levels": 4,
                           "corr_radius": 3}, 1080, 1920)
    q = 135 * 240
    got = mod.conv_gru(s)
    assert got["ops"] == 2 * 3 * 9 * q * 178 * 96 == 29_897_164_800
    assert got["bytes"] == (2 * q * (96 + 96 + 82 + 3 * 96)
                            + 2 * 3 * 9 * 178 * 96) == 37_340_352
    least = costs.min_seconds(got, {"flops_per_s": 197e12,
                                    "bytes_per_s": 819e9})
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(151.76e-6, rel=1e-3)
    # a third of the full model's SepConvGRU: one pass of 9 taps for two of 5
    full = costs.sep_conv_gru(dict(s, hidden=128, motion=128))
    assert got["ops"] < 0.5 * full["ops"]


# ------------------------------------------------- the server and the analyzer

# ``memory_analysis().temp_size_in_bytes`` of the served small program at
# 8 x 1080x1920 compiled for a described v5e (sandbox compile, PR 31;
# tests/test_tpu_compile.py holds the live compile to the same price)
SANDBOX_TEMP_BYTES = 7_007_982_080
# ``peak_hbm_gb`` of small-1080p-b8-closed on the v5e: 8.019 in twelve runs
# and 8.058-8.070 in six, parent and change alike (my chip runs, PR 31); the
# analyzer gives 8.17, the compiler's 7.008 GB of temporaries read 7.004 on
# the chip (``peak_bytes_reserved``)
PEAK_HBM_GB = 8.02


def test_serve_warms_four_executables_and_the_analyzer_prices_the_chip(cell):
    """``-m serve --small --buckets 1080x1920 --max-batch 8``: batch 1, 2, 4,
    8 of one bucket; ``lint/budget`` prices the batch-8 program's
    temporaries within 5 % of the chip compiler's own figure, and the chip
    at its fullest (a second batch's inputs and outputs staged) within 10 %
    of what the cell read."""
    from raft_tpu.lint import budget
    from raft_tpu.serving.config import (ServeConfig, enumerate_warmup_grid,
                                         parse_buckets)

    rconfig, args, _ = _serve(cell["config"])
    sconfig = ServeConfig(buckets=parse_buckets(args.buckets),
                          max_batch=args.max_batch,
                          max_sessions=args.max_sessions)
    keys = enumerate_warmup_grid(rconfig, sconfig)
    assert keys == [("pair", 1080, 1920, b, "fixed") for b in (1, 2, 4, 8)]
    temp = budget.pair_temp_bytes(rconfig, 1080, 1920, 8)
    assert abs(temp - SANDBOX_TEMP_BYTES) / SANDBOX_TEMP_BYTES < 0.05, temp
    report = budget.analyze(rconfig, sconfig, device_kind="tpu-v5e")
    assert report["grid"]["size"] == 4 and not report["violations"]
    assert not report["buckets"][0]["pallas"]["gru"]["active"]
    priced = report["totals"]["peak_with_pair_temps_bytes"]
    assert 4 * 2 ** 30 < priced < 12e9
    assert abs(priced / 1e9 - PEAK_HBM_GB) / PEAK_HBM_GB < 0.10, priced


# --------------------------- the served program against the plain reference

SEED, H, W, ITERS = 3_100_000_007, 64, 96, 20


@pytest.fixture(scope="module")
def seeded(cell, bench_modules):
    weights, inputs = bench_modules["weights"], bench_modules["inputs"]
    reference = bench_modules["reference"]
    mcfg = weights.model_cfg(cell["config"])
    wts = weights.make_weights(SEED, mcfg)
    pairs = inputs.make_pairs(SEED, 2, H, W, 2)
    flow = lambda p, precision: np.asarray(reference.flow(     # noqa: E731
        wts, p[0], p[1], mcfg, ITERS, precision))
    return {"weights": wts, "pairs": pairs,
            "refs": [flow(p, "float32") for p in pairs],
            "own": [flow(p, "bfloat16") for p in pairs],
            "low": [flow(p, "float8") for p in pairs]}


def _served_flows(cell, bench_modules, seeded, tmp_path, dtype):
    """The two pairs' flows as ``POST /v1/flow`` answered them from ONE
    device batch of 2: the server ``benchmark/system.py`` builds from the
    configuration's serve arguments, at a 64x96 bucket, ``--max-batch 2``
    and ``dtype``, a wait long enough that two requests sent together ride
    together."""
    inputs, system = bench_modules["inputs"], bench_modules["system"]
    config = dict(cell["config"])
    _, _, argv = _serve(config, buckets=f"{H}x{W}", max_batch="2",
                        dtype=dtype, max_wait_ms="2000")
    config["serve_args"] = argv
    config["program"] = dict(config["program"], compute_dtype=dtype)
    sut = system.start(config, seeded["weights"], str(tmp_path), "small-cpu")
    try:
        assert sut.executables == 2 and sut.config.small
        assert sut.config.corr_impl == "pallas" and sut.config.iters == ITERS
        before = sut.scrape()
        out = [None, None]

        def post(i):
            a, b = seeded["pairs"][i]
            conn = http.client.HTTPConnection(sut.host, sut.port, timeout=300)
            conn.request("POST", "/v1/flow",
                         body=inputs.npz_body(image1=a, image2=b),
                         headers={"Content-Type": "application/octet-stream",
                                  "Accept": "application/octet-stream"})
            resp = conn.getresponse()
            payload = resp.read()
            assert resp.status == 200, payload[:200]
            out[i] = inputs.npz_load(payload)["flow"]
            conn.close()

        threads = [threading.Thread(target=post, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        prom = system.diff_prom(before, sut.scrape())
        assert prom["raft_serving_device_calls_total"] == 1     # one batch
        assert prom["raft_serving_batch_size_sum"] == 2         # of two
        assert prom["raft_serving_compile_cache_misses_total"] == 0
        assert prom["raft_serving_corr_keyblocks_possible_total"] > 0
        # the four band counts ride together: tiles <= visited <= the grid
        # steps the launches took <= a walk of every band
        assert (0 < prom["raft_serving_corr_tiles_total"]
                <= prom["raft_serving_corr_keyblocks_visited_total"]
                <= prom["raft_serving_corr_grid_steps_total"]
                <= prom["raft_serving_corr_keyblocks_possible_total"])
        return [np.asarray(f).reshape(H, W, 2) for f in out]
    finally:
        sut.stop()


def test_served_float32_program_is_the_reference(cell, bench_modules, seeded,
                                                 tmp_path):
    """20 updates of the radius-3 Pallas lookup (interpret mode), the 3x3
    ConvGRU and ``upflow8`` in float32, through the server: float32
    round-off apart from the reference (1e-4 of the mean flow, the tolerance
    ``benchmark/tests/test_reference.py`` states for the dense forward)."""
    check = bench_modules["check"]
    flows = _served_flows(cell, bench_modules, seeded, tmp_path, "float32")
    for flow, ref in zip(flows, seeded["refs"]):
        assert np.isfinite(flow).all()
        assert np.linalg.norm(ref, axis=-1).mean() > 0.5     # a real field
        assert check.rel_epe(flow, ref) < 1e-4


def test_served_bfloat16_program_is_under_the_cells_limit(
        cell, bench_modules, seeded, tmp_path):
    """The program the cell times, at the precision the configuration
    states: ``precision_ratio`` (check.py) under ``check.ratio_limit``."""
    check = bench_modules["check"]
    limit = float(cell["config"]["check"]["ratio_limit"])
    flows = _served_flows(cell, bench_modules, seeded, tmp_path, "bfloat16")
    answers = [(i, i, f) for i, f in enumerate(flows)]
    verdict = check.compare(answers, dict(enumerate(seeded["refs"])),
                            dict(enumerate(seeded["own"])), limit,
                            out=lambda _m: None)
    assert verdict["correct"] and verdict["worst"] < limit, verdict


def test_the_e4m3_control_is_over_the_cells_limit(cell, bench_modules,
                                                  seeded):
    """The reference itself one precision step below the stated one, put in
    the program's place: over the limit on every pair, so the check would
    catch a program that computed in less than it states."""
    check = bench_modules["check"]
    limit = float(cell["config"]["check"]["ratio_limit"])
    for ref, own, low in zip(seeded["refs"], seeded["own"], seeded["low"]):
        ratio = check.rel_epe(low, ref) / check.rel_epe(own, ref)
        assert ratio > limit, ratio
