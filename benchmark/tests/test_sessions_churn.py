"""The driver ``sessions_churn``, its mix and the reference ``warm_restart``
under ``run.py`` on the CPU: the cell ``things-stream-churn`` at a 64x96
bucket in float32 against a ``FlowServer`` with FOUR slots for eight live
sessions, three of them playing.  (``tests/test_benchmark_churn.py`` holds the
cell's files against ISSUE 41's table and the program against the reference's
restart; this is the harness's own rehearsal of the cell.)"""

import importlib.util
import json
import os
import shutil

import numpy as np
import pytest

from drivers import sessions_churn
from references import warm_restart

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL, TINY = "things-stream-churn", "tiny-churn-cell"


@pytest.fixture(scope="module")
def run():
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def tiny(tmp_path, run):
    """A copy of the benchmark with the cell at a tiny size beside it."""
    manifest = run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = run.find(manifest["workloads"], CELL, "workload")
    cfg_entry = run.find(manifest["configs"], entry["config"],
                         "configuration")
    config = run.load_json(os.path.join(REPO, cfg_entry["file"]))
    traffic = run.load_json(os.path.join(BENCH, "traffic",
                                         entry["traffic"] + ".json"))
    argv = [str(a) for a in config["serve_args"]]
    for flag, value in (("--buckets", "64x96"), ("--iters", "3"),
                        ("--dtype", "float32"), ("--max-batch", "2"),
                        ("--gru-impl", "xla"), ("--max-sessions", "4")):
        argv[argv.index(flag) + 1] = value
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", "tests"))
    (bench / "configs" / "tiny-churn.json").write_text(json.dumps(dict(
        config, name="tiny-churn", serve_args=argv, iters=3,
        program={"small": False, "compute_dtype": "float32"})))
    (bench / "traffic" / "tiny-churn.json").write_text(json.dumps(dict(
        traffic, height=64, width=96, clips=2, max_shift=2,
        session_frames=[6, 8], burst_frames=[2, 4], check_park_place=2)))
    (bench / "workloads" / (TINY + ".json")).write_text(json.dumps(
        {"clients": 3, "live_sessions": 8, "why": "rehearsal"}))
    manifest["configs"].append({
        "name": "tiny-churn", "source": "rehearsal", "reduced": [],
        "file": "benchmark/configs/tiny-churn.json", "why": "x"})
    manifest["workloads"].append({
        "name": TINY, "config": "tiny-churn", "traffic": "tiny-churn",
        "chips": 1, "why": "x"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(bench), str(tmp_path / "BENCHMARK.json")


def test_the_cell_under_the_harness_is_correct(run, tiny, capsys):
    """Sessions are parked, resumed, demoted and restarted, ended and
    renewed; A is warm, B the restart at frame 3 and C seeded from a
    restart, each within the configuration's limit of the reference's walk;
    nothing fails and nothing compiles in the window."""
    bench, manifest = tiny
    rc = run.main(["--workload", TINY, "--seed", "4100000023", "--seconds",
                   "10", "--trace", "0"], bench_dir=bench, manifest=manifest,
                  require_tpu=False)
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert rc == 0 and result["correct"] is True, lines[-12:]
    assert result["failed"] == 0
    assert result["checks"]["answers_compared"]["value"] == 3
    assert result["checks"]["compile_misses"]["value"] == 0
    window = json.loads(next(ln for ln in lines
                             if ln.startswith("window: "))[8:])
    assert window["cold_advances"] >= window["resumes_longest"] >= 3
    assert window["resumes"] > window["resumes_longest"] > 0
    # (clip, frame, frames answered cold) of each answer compared
    kept = [ln.split("(pair ")[1].split("): precision")[0]
            for ln in lines if ln.startswith("check: request")]
    assert sorted(k.split(", ", 2)[1:] for k in kept) == [
        ["2", "())"], ["3", "(3,))"], ["4", "(3,))"]]


def test_a_walk_restarts_where_it_is_told_and_goes_on_from_the_restart():
    """``walk`` hands the forward no seed at the open's advance, the
    projection of the answer before at every other, and ``restart`` at the
    cold frames alone."""
    calls = []

    def forward(a, b, flow_init=None, restart=False):
        calls.append((int(a[0, 0, 0]), int(b[0, 0, 0]),
                      flow_init is not None, restart))
        return np.zeros((16, 16, 2), np.float32), \
            np.zeros((2, 2, 2), np.float32)

    frames = [np.full((16, 16, 3), k, np.uint8) for k in range(5)]
    out = warm_restart.walk(forward, frames, 4, cold=(3,))
    assert sorted(out) == [1, 2, 3, 4]
    assert calls == [(0, 1, False, False), (1, 2, True, False),
                     (2, 3, True, True), (3, 4, True, False)]


def test_a_windows_draws_are_even_over_the_seed_and_the_tables_one_by_one():
    """A :class:`Spread`'s numbers: the same for the same seed, uniform over
    the seeds, even along a run."""
    run_a = sessions_churn.Spread(4100000023, "burst", first=24)
    run_b = sessions_churn.Spread(4100000023, "burst", first=24)
    assert [run_a.next() for _ in range(5)] == [run_b.nth(n)
                                                for n in range(24, 29)]
    firsts = [sessions_churn.Spread(seed, "pick").nth(3)
              for seed in range(2000)]
    assert 0.22 < sum(1 for u in firsts if u < 0.25) / 2000 < 0.28
    picks = [sessions_churn.Spread(7, "pick").nth(n) < 0.25
             for n in range(60)]
    assert 13 <= sum(picks) <= 17
