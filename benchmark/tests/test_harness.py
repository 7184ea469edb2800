"""The harness end to end on the CPU at a tiny size.

* a cell, a configuration, a traffic mix and two per-layer metrics that exist
  only as NEWLY ADDED FILES (and appended ``BENCHMARK.json`` entries) are
  picked up with no edit to a file that was there;
* the rest of a run with the chip look-up skipped and the timed path BROKEN
  underneath (an answer altered where the engine produces it) comes out
  ``correct: false``;
* without a TPU, ``run.py`` exits non-zero and prints no result line.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

import run  # noqa: E402  (conftest put the benchmark on sys.path)


@pytest.fixture()
def added_files(tmp_path):
    """A copy of the benchmark's DATA with one new cell, configuration, mix
    and two new per-layer metrics added beside what is there."""
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "*.py", ".cache", "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.loads((bench / "configs" / "raft-small.json").read_text())
    cfg.update(
        serve_args=["--small", "--buckets", "64x96", "--iters", "3",
                    "--dtype", "float32", "--max-batch", "2",
                    "--max-wait-ms", "5", "--max-sessions", "0",
                    "--corr-impl", "pallas", "--gru-impl", "xla"],
        program={"small": True, "compute_dtype": "float32"}, iters=3,
        check={"own_precision": "bfloat16", "ratio_limit": 0.2, "sample": 2})
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny-mix.json").write_text(json.dumps({
        "loop": "closed", "endpoint": "/v1/flow", "height": 60, "width": 96,
        "distinct_pairs": 3}))
    (bench / "workloads" / "tiny-cell.json").write_text(json.dumps(
        {"clients": 4, "why": "rehearsal"}))
    (bench / "layer_metrics" / "answered.json").write_text(json.dumps(
        {"reader": "loadgen", "params": {"field": "ok"}}))
    (bench / "layer_metrics" / "first_body.py").write_text(
        "def read(ctx, params):\n"
        "    return float(min(r.body for r in ctx.records))\n")
    manifest = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    manifest["configs"].append({
        "name": "tiny", "source": "rehearsal",
        "file": "benchmark/configs/tiny.json", "reduced": [], "why": "x"})
    manifest["workloads"].append({
        "name": "tiny-cell", "config": "tiny", "traffic": "tiny-mix",
        "chips": 1, "why": "x"})
    for m in manifest["end_to_end"]:
        if m["name"] == "pairs_per_s":
            m["workloads"].append("tiny-cell")
    for name in ("answered", "first_body"):
        manifest["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "host_clock", "layer": "load generator",
            "moves": "pairs_per_s", "workloads": ["tiny-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return bench, tmp_path / "BENCHMARK.json", before


def drive(added, capsys, trace):
    bench, manifest, _ = added
    rc = run.main(["--workload", "tiny-cell", "--seed", "3000000019",
                   "--seconds", "2", "--trace", str(trace)],
                  bench_dir=str(bench), manifest=str(manifest),
                  require_tpu=False)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1]), lines


def test_new_cell_config_mix_and_metrics_are_files_only(added_files, capsys):
    rc, result, lines = drive(added_files, capsys, trace=1)
    assert rc == 0
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert result["correct"] is True and result["failed"] == 0
    m = result["metrics"]
    # the two metrics that exist only as new files, one .json one .py
    assert m["answered"]["value"] == result["attempted"] > 0
    assert m["first_body"] == {"value": 0.0, "unit": "count"}
    # readers with nothing to read (no device plane in a CPU trace) are
    # left out; none of the other cells' metrics leak into this cell
    assert set(m) == {"answered", "first_body"}
    assert any(ln.startswith("check: request") and " limit " in ln
               for ln in lines)
    # nothing that was there was edited
    bench, _, before = added_files
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_broken_timed_path_is_not_correct(added_files, capsys, monkeypatch):
    from raft_tpu.serving.engine import InferenceEngine
    sound = InferenceEngine.run

    def altered(self, *a, **kw):        # every answer off by a quarter pixel
        return sound(self, *a, **kw) + 0.25

    monkeypatch.setattr(InferenceEngine, "run", altered)
    rc, result, lines = drive(added_files, capsys, trace=0)
    assert rc == 0
    assert result["correct"] is False
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"pairs_per_s", "setup_s"}
    assert any(" OVER" in ln for ln in lines if ln.startswith("check:"))


def test_without_a_tpu_there_is_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "things-sintel-closed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout
    assert "need 1 TPU" in p.stderr
