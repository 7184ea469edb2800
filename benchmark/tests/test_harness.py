"""The harness end to end on the CPU at a tiny size.

* a cell, a configuration, a traffic mix and two per-layer metrics that exist
  only as NEWLY ADDED FILES (and appended ``BENCHMARK.json`` entries) are
  picked up with no edit to a file that was there;
* so are a load driver whose every request depends on the answer before it
  and a reference under another name, which exist nowhere but in this file;
  a mix or a configuration that names one that does not exist ends with one
  line and no result;
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
    """A copy of the benchmark, code and data, with one new cell,
    configuration, mix and two new per-layer metrics added beside what is
    there.  (The harness's modules run from the checkout, by their bare
    names; what is found by NAME is found in the copy.)"""
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", "tests"))
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
    captured = capsys.readouterr()
    lines = captured.out.strip().splitlines()
    result = json.loads(lines[-1])
    # every number compared, beside its limit: the result's last key and
    # the last lines on standard error
    assert list(result)[-1] == "checks"
    err = captured.err.strip().splitlines()[-len(result["checks"]):]
    for ln, (name, c) in zip(err, result["checks"].items()):
        assert ln.startswith(f"check: {name} {c['value']} limit {c['limit']}")
    return rc, result, lines


def unedited(added):
    """Nothing that was there was edited: run.py, loadgen.py, check.py,
    reference.py, inputs.py, system.py and every data file of the copy are
    the checkout's, byte for byte."""
    bench, _, before = added
    assert {"run.py", "loadgen.py", "check.py", "reference.py", "inputs.py",
            "system.py", "drivers/pairs.py", "references/dense.py"} <= {
        str(p.relative_to(bench)) for p in before}
    for p, data in before.items():
        assert p.read_bytes() == data, p


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
    ratios = [c for name, c in result["checks"].items()
              if name.startswith("precision_ratio.r")]
    assert len(ratios) == 2 and all(0 < c["value"] <= c["limit"] == 0.2
                                    and c["ok"] for c in ratios)
    assert result["checks"]["answers_compared"] == {
        "value": 2, "limit": 2, "ok": True}
    assert result["checks"]["compile_misses"] == {
        "value": 0, "limit": 0, "ok": True}
    unedited(added_files)


# A load driver and a reference that exist nowhere but here.  Each client
# walks a sequence of frames of its own: request k posts (frame k-1, frame k),
# encoded only once request k-1 has been answered, and names that request in
# what it keeps; the reference walks a kept answer's sequence from its first
# frame.
CHAIN_DRIVER = '''
import dataclasses, math, random, threading, time

import numpy as np

import inputs
import loadgen


@dataclasses.dataclass
class Window:
    records: list
    t0: float
    t1: float
    keep: set


def make_inputs(seed, traffic):
    return inputs.make_pairs(seed, int(traffic["sequences"]),
                             int(traffic["height"]), int(traffic["width"]), 2)


def frame(made, c, k):          # a sequence goes to and fro between two frames
    return made[c % len(made)][k % 2]


def walk(sut, made, traffic, cell, seconds, keep):
    n = int(cell["clients"])
    records, lock, t_end = [], threading.Lock(), [math.inf]
    barrier = threading.Barrier(n + 1)

    def client(c, conn):
        barrier.wait()
        k, prev = 1, None
        while time.monotonic() < t_end[0]:
            with lock:
                rec = loadgen.Record(len(records), k, time.monotonic())
                records.append(rec)
            rec.chain = (c, k, prev)
            body = inputs.npz_body(image1=frame(made, c, k - 1),
                                   image2=frame(made, c, k))
            conn.one(rec, body, (c, k) in keep)
            if rec.status != 200:
                break               # a chain does not go on past a lost link
            k, prev = k + 1, rec.ordinal
        conn.close()

    threads = [threading.Thread(target=client, args=(c, conn), daemon=True)
               for c, conn in enumerate(loadgen.Client.connected(
                   n, sut.host, sut.port, traffic["endpoint"], 60.0))]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.monotonic()
    t_end[0] = t0 + seconds
    for t in threads:
        t.join()
    return Window(records, t0, t0 + seconds, keep)


def warm_up(sut, made, seed, traffic, cell, seconds):
    walk(sut, made, traffic, cell, seconds, set())


def run_window(sut, made, seed, traffic, cell, seconds, n_keep):
    links = [(c, k) for c in range(int(cell["clients"])) for k in (2, 3)]
    return walk(sut, made, traffic, cell, seconds,
                set(random.Random(seed).sample(links, n_keep)))


def summarize(win):
    return loadgen.summarize(win.records, win.t0, win.t1, "closed")


def kept_answers(win):
    return [(r.ordinal, r.chain,
             inputs.npz_load(r.payload)["flow"] if r.payload else None)
            for r in win.records if r.chain[:2] in win.keep]


def reference_answers(forward, made, which):
    out = {}
    for c, k, prev in which:
        assert prev is not None     # a kept answer names the one before it
        for j in range(1, k + 1):   # from the sequence's first frame
            flow = forward(frame(made, c, j - 1), frame(made, c, j))
        out[c, k, prev] = np.asarray(flow)
    return out
'''

OTHER_REFERENCE = '''
import reference

CALLS = []


def flow(weights, image1, image2, cfg, iters, precision="float32"):
    CALLS.append(precision)
    return reference.flow(weights, image1, image2, cfg, iters, precision)
'''

# how many requests were sent only after the one they name had been answered
CHAINED_METRIC = '''
def read(ctx, params):
    by = {r.ordinal: r for r in ctx.records}
    return float(sum(1 for r in ctx.records if r.chain[2] is not None
                     and by[r.chain[2]].chain[:2] == (r.chain[0], r.chain[1] - 1)
                     and by[r.chain[2]].status == 200
                     and by[r.chain[2]].done <= r.sent))
'''


def rewrite(path, **changes):
    """Change keys of a JSON file that the fixture itself added."""
    path.write_text(json.dumps(dict(json.loads(path.read_text()), **changes)))


def name_reference(bench, name):
    tiny = json.loads((bench / "configs" / "tiny.json").read_text())
    rewrite(bench / "configs" / "tiny.json",
            check=dict(tiny["check"], reference=name))


def test_new_driver_and_reference_are_files_only(added_files, capsys):
    bench, manifest, _ = added_files
    for folder, name, text in (("drivers", "chain", CHAIN_DRIVER),
                               ("references", "other", OTHER_REFERENCE),
                               ("layer_metrics", "chained", CHAINED_METRIC)):
        assert not os.path.exists(os.path.join(BENCH, folder, name + ".py"))
        (bench / folder / (name + ".py")).write_text(text)
    rewrite(bench / "traffic" / "tiny-mix.json", driver="chain", sequences=3)
    name_reference(bench, "other")
    listing = json.loads(manifest.read_text())
    listing["per_layer"].append(dict(listing["per_layer"][-1], name="chained"))
    manifest.write_text(json.dumps(listing))

    rc, result, lines = drive(added_files, capsys, trace=1)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    m = result["metrics"]
    assert m["answered"]["value"] == result["attempted"] > 4
    # every request but each client's first waited for the answer before it
    assert m["chained"]["value"] == result["attempted"] - 4
    assert m["first_body"]["value"] == 1.0      # Record.body is the link's k
    # the reference that answered is the one the configuration names
    calls = sys.modules["references_other"].CALLS
    assert set(calls) == {"float32", "bfloat16"} and len(calls) >= 8
    assert sum(name.startswith("precision_ratio.r")
               for name in result["checks"]) == 2
    unedited(added_files)


@pytest.mark.parametrize("what", ["driver", "reference"])
def test_a_name_with_no_file_ends_in_one_line(added_files, capsys, what):
    bench, manifest, _ = added_files
    if what == "driver":
        rewrite(bench / "traffic" / "tiny-mix.json", driver="nowhere")
    else:
        name_reference(bench, "nowhere")
    with pytest.raises(SystemExit) as stop:
        run.main(["--workload", "tiny-cell", "--seed", "5", "--seconds", "1",
                  "--trace", "0"], bench_dir=str(bench),
                 manifest=str(manifest), require_tpu=False)
    message = str(stop.value)
    assert "'nowhere'" in message and what in message
    assert f"{what}s/nowhere.py" in message and "\n" not in message
    assert '"metrics"' not in capsys.readouterr().out


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
    # the number that was over stands beside its limit in the result line
    over = [c for c in result["checks"].values() if not c["ok"]]
    assert over and all(c["value"] > c["limit"] == 0.2 for c in over)


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
