"""Telemetry spine tests (OBSERVABILITY.md): registry primitives + default
registry, run manifests + event logs, named-stage tracing, the trace
window, the watchdogs (NaN sentinel + recompile counter, both with stage
provenance), the training loop's metrics.jsonl provenance, and tools/tlm.

Acceptance-criteria anchors:
* a deliberately-injected NaN is surfaced with the stage that produced it;
* a deliberately-triggered recompile is surfaced with the stage active at
  compile time;
* train metrics.jsonl carries a manifest (git sha, jax version, device
  kind, config hash);
* tlm summary/compare work end-to-end on real run logs.
"""

from __future__ import annotations

import importlib.util
import json
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from raft_tpu.telemetry import (Counter, Registry, RunLog,  # noqa: E402
                                config_hash, default_registry, read_events,
                                run_manifest)
from raft_tpu.telemetry import events as tlm_events  # noqa: E402
from raft_tpu.telemetry import watchdogs as wd  # noqa: E402
from raft_tpu.telemetry.trace import (TraceWindow, current_stage,  # noqa: E402
                                      stage)


# ------------------------------------------------------------- registry --

def test_registry_snapshot_plain_and_labeled():
    reg = Registry()
    c = reg.counter("jobs_total", "jobs")
    g = reg.gauge("depth", "queue depth")
    h = reg.histogram("lat", "latency", buckets=(0.1, 1.0))
    lab = reg.counter("by_status", "statuses", labelnames=("status",))
    c.inc(3)
    g.set(2.5)
    h.observe(0.05)
    h.observe(5.0)
    lab.labels("ok").inc(2)
    lab.labels("shed").inc()
    snap = reg.snapshot()
    assert snap["jobs_total"] == 3.0
    assert snap["depth"] == 2.5
    # histogram snapshots carry the cumulative bucket counts (keyed by
    # their le bound) so the time-series layer can diff two snapshots
    # into windowed percentiles (telemetry/timeseries.py)
    assert snap["lat"] == {"count": 2, "sum": 5.05, "mean": 2.525,
                           "buckets": {"0.1": 1, "1": 1, "+Inf": 2}}
    assert snap["by_status"] == {"ok": 2.0, "shed": 1.0}
    # the scrape timestamp makes rate math well-defined between snapshots;
    # private (underscore) keys are skipped by printing/diffing consumers
    assert isinstance(snap["_scrape_time"], float)


def test_default_registry_is_shared_and_get_or_create_works():
    reg = default_registry()
    assert default_registry() is reg
    name = "test_default_reg_counter"
    c = reg.get_or_counter(name, "test")
    assert reg.get_or_counter(name, "test") is c
    assert isinstance(c, Counter)

    # atomicity under contention: concurrent first-creation must never hit
    # the duplicate-metric ValueError (the mp_loader shared-counter path)
    import threading
    results, errors = [], []

    def create(i):
        try:
            results.append(reg.get_or_counter("test_contended_counter", "t"))
        except ValueError as e:   # pragma: no cover — the bug this guards
            errors.append(e)

    threads = [threading.Thread(target=create, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(set(map(id, results))) == 1


def test_serving_shim_reexports_telemetry_classes():
    # the compat contract: serving imports ARE the telemetry classes, so
    # /metrics rendering and tlm snapshots share one implementation
    from raft_tpu.serving import metrics as serving_metrics
    from raft_tpu.telemetry import registry as tel
    assert serving_metrics.Counter is tel.Counter
    assert serving_metrics.Histogram is tel.Histogram
    assert serving_metrics.Registry is tel.Registry


# ---------------------------------------------------- manifests / events --

def test_config_hash_stable_and_sensitive():
    from raft_tpu.config import RAFTConfig
    a = RAFTConfig.full()
    assert config_hash(a) == config_hash(RAFTConfig.full())
    assert config_hash(a) != config_hash(RAFTConfig.full(iters=7))
    assert config_hash(None) is None
    assert config_hash({"k": 1}) == config_hash({"k": 1})


def test_run_manifest_provenance_fields():
    from raft_tpu.config import RAFTConfig
    man = run_manifest(config=RAFTConfig.small_model(), mode="test",
                      extra={"note": "x"})
    sha = subprocess.run(["git", "rev-parse", "HEAD"], cwd=REPO,
                         capture_output=True, text=True).stdout.strip()
    assert man["git_sha"] == sha
    import jax
    assert man["jax_version"] == jax.__version__
    assert man["device_kind"] == jax.devices()[0].device_kind
    assert man["device_count"] == len(jax.devices())
    assert len(man["config_hash"]) == 16
    assert man["mode"] == "test" and man["note"] == "x"
    assert man["schema"] == 1 and man["argv"]


def test_run_manifest_does_not_hide_a_failed_device_query(monkeypatch):
    """A backend that fails to come up raises out of the manifest: there
    is no device-less stamp to carry on with."""
    import jax

    def down():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", down)
    with pytest.raises(RuntimeError, match="initialize backend"):
        run_manifest(mode="bench")


def test_runlog_roundtrip_and_partial_line_tolerance(tmp_path):
    log = RunLog(tmp_path / "run", manifest=run_manifest(mode="t"))
    log.event("custom", value=3)
    log.close()
    path = tmp_path / "run" / "events.jsonl"
    assert path.exists()
    # simulate a crash mid-append: partial trailing line
    with open(path, "a") as f:
        f.write('{"t": 1, "event": "trunc')
    recs = read_events(tmp_path / "run")
    assert [r["event"] for r in recs] == ["manifest", "custom"]
    assert recs[1]["value"] == 3
    assert all("t" in r for r in recs)


def test_events_current_is_settable(tmp_path):
    assert tlm_events.current() is None or True   # whatever prior state
    log = RunLog(tmp_path)
    tlm_events.set_current(log)
    try:
        assert tlm_events.current() is log
    finally:
        tlm_events.set_current(None)
        log.close()


# ------------------------------------------------------------- tracing ---

def test_stage_stack_nesting_and_thread_locality():
    assert current_stage() is None
    with stage("a"):
        assert current_stage() == "a"
        with stage("a/b"):
            assert current_stage() == "a/b"
        assert current_stage() == "a"
    assert current_stage() is None

    import threading
    seen = []

    def other():
        seen.append(current_stage())

    with stage("main-only"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen == [None]           # the stack is per-thread


def test_stage_under_jit_and_as_decorator():
    import jax
    import jax.numpy as jnp

    @stage("decorated")
    def double(x):
        assert current_stage() == "decorated"
        return x * 2

    @jax.jit
    def f(x):
        with stage("inner"):
            y = double(x)
        return y

    np.testing.assert_allclose(np.asarray(f(jnp.ones(3))), 2.0)


def test_trace_window_none_dir_is_noop_and_window_fires(monkeypatch):
    calls = []
    import jax
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))

    noop = TraceWindow(None, first=0, steps=2)
    for i in range(5):
        assert noop.on_step(i) is False
    noop.stop()
    assert calls == []

    msgs = []
    tw = TraceWindow("/tmp/tracedir", first=2, steps=2, log_fn=msgs.append)
    assert tw.on_step(0) is False and tw.on_step(1) is False
    assert tw.on_step(2) is True and tw.on_step(3) is True
    assert tw.on_step(4) is False          # window closed itself
    tw.stop()                              # idempotent
    assert calls == [("start", "/tmp/tracedir"), ("stop", None)]
    assert any("trace" in m for m in msgs)


# ------------------------------------------------------------ watchdogs --

@pytest.fixture
def nan_sentinel():
    wd.enable_nan_sentinel(True)
    yield
    wd.enable_nan_sentinel(False)


def test_nan_guard_free_when_disabled():
    wd.enable_nan_sentinel(False)
    x = object()                      # not even an array: guard must be id
    assert wd.nan_guard(x) is x


def test_nan_sentinel_reports_stage_provenance(nan_sentinel, tmp_path):
    import jax
    import jax.numpy as jnp

    log = RunLog(tmp_path)
    wd.enable_nan_sentinel(True, run_log=log)

    @jax.jit
    def f(x):
        with stage("demo/fused"):
            y = wd.nan_guard(x * 2)
        return y

    f(jnp.array([1.0, jnp.inf, jnp.nan])).block_until_ready()
    jax.effects_barrier()
    evs = wd.nan_events()
    assert evs and evs[-1]["stage"] == "demo/fused"
    assert evs[-1]["bad_values"] == 2
    log.close()
    recs = read_events(tmp_path)
    assert any(r.get("event") == "nonfinite"
               and r.get("stage") == "demo/fused" for r in recs)
    # clean input -> no new events
    before = len(wd.nan_events())
    f(jnp.ones(3)).block_until_ready()
    jax.effects_barrier()
    assert len(wd.nan_events()) == before


def test_model_level_nan_carries_model_stage(nan_sentinel):
    """ACCEPTANCE: a deliberately-injected NaN in the model input is
    surfaced with the model stage that first produced non-finite values
    (raft/fnet — the guard threaded through models/raft.py)."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.config import RAFTConfig
    from raft_tpu.models import init_raft
    from raft_tpu.models.raft import make_inference_fn

    config = RAFTConfig.small_model(iters=1)
    params = init_raft(jax.random.PRNGKey(0), config)
    fn = jax.jit(make_inference_fn(config))
    im = jnp.zeros((1, 32, 48, 3), jnp.float32)
    bad = im.at[0, 0, 0, 0].set(jnp.nan)
    wd.nan_events().clear()
    fn(params, bad, im).block_until_ready()
    jax.effects_barrier()
    stages = [e["stage"] for e in wd.nan_events()]
    assert stages and stages[0] == "raft/fnet", stages


def test_recompile_watch_counts_and_attributes_stage(tmp_path):
    """ACCEPTANCE: a deliberately-triggered recompile (new input shape
    after arm()) is surfaced with the host-side stage active at compile
    time, while warmup compiles are counted separately."""
    import jax
    import jax.numpy as jnp

    log = RunLog(tmp_path)
    watch = wd.RecompileWatch(run_log=log, log_fn=lambda m: None).install()
    try:
        f = jax.jit(lambda x: (x * 3).sum())
        f(jnp.ones((4,))).block_until_ready()      # expected warmup compile
        assert watch.recompiles == 0
        assert watch.warmup_compiles >= 1
        watch.arm()
        with stage("eval/forward"):
            f(jnp.ones((9,))).block_until_ready()  # new shape -> recompile
        assert watch.recompiles >= 1
        assert watch.events[0]["stage"] == "eval/forward"
        assert watch.events[0]["duration_s"] >= 0
        # cache hit: no new recompile
        n = watch.recompiles
        f(jnp.ones((9,))).block_until_ready()
        assert watch.recompiles == n
    finally:
        watch.remove()
        log.close()
    recs = read_events(tmp_path)
    assert any(r.get("event") == "recompile"
               and r.get("stage") == "eval/forward" for r in recs)


def test_lock_validator_clean_nesting_is_zero_violations():
    """A consistently ordered drill records edges, holds, and NOTHING
    else — the chaos smoke's zero-violation assertion in unit form."""
    import threading
    v = wd.LockOrderValidator(hold_budget_s=1.0, log_fn=lambda m: None)
    a = wd.WatchedLock("A", threading.Lock(), v)
    b = wd.WatchedLock("B", threading.Lock(), v)

    def worker():
        for _ in range(20):
            with a:
                with b:
                    pass
    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    counts = v.counts()
    assert counts["order_violations"] == 0
    assert counts["hold_violations"] == 0
    assert counts["edges"] == 1             # A->B, deduped


def test_lock_validator_forced_inversion_fires_once():
    import threading
    v = wd.LockOrderValidator(log_fn=lambda m: None)
    a = wd.WatchedLock("A", threading.Lock(), v)
    b = wd.WatchedLock("B", threading.Lock(), v)
    with a:
        with b:
            pass
    for _ in range(3):                      # the cycle edge is deduped:
        with b:                             # counted once, not per hit
            with a:
                pass
    assert v.counts()["order_violations"] == 1
    assert v.violations[0]["kind"] == "order"
    assert "cycle" in v.violations[0]["msg"]


def test_lock_validator_declared_hierarchy_catches_first_inversion():
    """With the serving hierarchy declared, the FIRST wrong-way edge is a
    violation — no need to wait for the matching opposite edge to land in
    a later PR and close an actual deadlock."""
    import threading
    v = wd.LockOrderValidator(log_fn=lambda m: None)
    v.declare_order(("outer", "inner"))
    outer = wd.WatchedLock("outer", threading.Lock(), v)
    inner = wd.WatchedLock("inner", threading.Lock(), v)
    with inner:
        with outer:
            pass
    assert v.counts()["order_violations"] == 1
    assert "inversion" in v.violations[0]["msg"]
    # reentry of a non-reentrant lock is also a (deadlock-shaped) violation
    v2 = wd.LockOrderValidator(log_fn=lambda m: None)
    r = wd.WatchedLock("R", threading.Lock(), v2)
    v2.on_acquired("R")                     # simulate: a real Lock would
    v2.on_acquired("R")                     # already be deadlocked here
    assert v2.violations[0]["kind"] == "reentry"


def test_lock_validator_hold_budget_and_condition_wait_exempt():
    import threading
    t = [0.0]
    v = wd.LockOrderValidator(clock=lambda: t[0], hold_budget_s=0.5,
                              log_fn=lambda m: None)
    lk = wd.WatchedLock("L", threading.Lock(), v)
    lk.acquire()
    t[0] += 2.0
    lk.release()
    assert v.counts()["hold_violations"] == 1
    v.set_budget("L", None)                 # per-lock opt-out (Session.lock)
    lk.acquire()
    t[0] += 10.0
    lk.release()
    assert v.counts()["hold_violations"] == 1
    # Condition.wait releases the wrapped lock: a long wait is NOT a hold
    v2 = wd.LockOrderValidator(hold_budget_s=0.2, log_fn=lambda m: None)
    wl = wd.WatchedLock("C", threading.Lock(), v2)
    cond = threading.Condition(wl)
    ready = []

    def waiter():
        with cond:
            while not ready:
                cond.wait(timeout=5)
    th = threading.Thread(target=waiter)
    th.start()
    import time as _time
    _time.sleep(0.4)                        # waiter parked > budget
    with cond:
        ready.append(1)
        cond.notify()
    th.join()
    assert v2.counts()["hold_violations"] == 0
    assert v2.counts()["order_violations"] == 0


def test_watched_lock_env_gate_and_metrics_export(monkeypatch):
    import threading
    monkeypatch.delenv("RAFT_TPU_LOCK_WATCH", raising=False)
    assert isinstance(wd.watched_lock("X"), type(threading.Lock()))
    monkeypatch.setenv("RAFT_TPU_LOCK_WATCH", "1")
    assert isinstance(wd.watched_lock("X"), wd.WatchedLock)
    # export: live families on a registry, backed by the validator
    v = wd.LockOrderValidator(log_fn=lambda m: None)
    reg = Registry()
    wd.export_lock_metrics(reg, validator=v)
    a = wd.WatchedLock("A", threading.Lock(), v)
    b = wd.WatchedLock("B", threading.Lock(), v)
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    text = reg.render()
    assert "raft_lock_order_violations_total 1" in text
    assert "raft_lock_hold_seconds_count 4" in text


def test_stream_open_failure_path_respects_lock_hierarchy(monkeypatch):
    """Regression: a failed session open (queue full) used to close the
    session record while still holding Session.lock — store.close takes
    the store lock, inverting the declared hierarchy.  The close now runs
    after the session lock is released: zero violations, and the
    half-open record is still cleaned up."""
    import threading  # noqa: F401 — locks built via watched_lock below
    monkeypatch.setenv("RAFT_TPU_LOCK_WATCH", "1")
    fresh = wd.LockOrderValidator(log_fn=lambda m: None)
    monkeypatch.setattr(wd, "_validator", fresh)
    from raft_tpu.lint.concurrency import SERVING_LOCK_HIERARCHY
    fresh.declare_order(SERVING_LOCK_HIERARCHY)
    from raft_tpu.serving.queue import QueueFull
    from raft_tpu.serving.session import SessionStore
    from raft_tpu.serving.stream import StreamCoordinator

    class FullQueue:
        def submit(self, req):
            raise QueueFull("full")

    class SConfig:
        session_ttl_s = 60.0
        default_deadline_ms = 100.0

        def route(self, h, w):
            return (32, 48)

    statuses = []
    store = SessionStore(2, 60.0)
    coord = StreamCoordinator(store, SConfig(), FullQueue(), {},
                              statuses.append)
    with pytest.raises(QueueFull):
        coord.open(np.zeros((24, 40, 3), np.float32), None)
    assert statuses == ["shed"]
    assert store.resident_count() == 0      # no half-open session leaked
    assert fresh.counts()["order_violations"] == 0, fresh.violations


def test_hbm_gauges_none_safe():
    reg = Registry()
    gauges = wd.hbm_gauges(reg)
    # CPU backend: memory_stats() is None -> gauges read 0, never raise
    assert gauges["bytes_in_use"].value >= 0
    assert "raft_hbm_bytes_in_use" in reg.render()


def test_transfer_watch_levels():
    with wd.transfer_watch("log"):
        pass
    with pytest.raises(ValueError, match="log.*disallow|disallow.*log"):
        wd.transfer_watch("everything")


# ------------------------------------------- train-loop integration ------

@pytest.mark.slow
def test_train_metrics_jsonl_carries_manifest_and_snapshot(tmp_path):
    """ACCEPTANCE: metrics.jsonl written by the training loop starts with a
    manifest record (git sha, jax version, device kind, config hash) and
    ends with the registry snapshot."""
    import jax

    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.training.loop import train

    config = RAFTConfig.small_model(iters=2)
    tconfig = TrainConfig(num_steps=2, batch_size=1, lr=1e-4,
                          schedule="constant", log_every=1, ckpt_every=100)
    rng = np.random.RandomState(0)
    B, H, W = 1, 32, 48

    def batches():
        while True:
            yield (rng.rand(B, H, W, 3).astype(np.float32),
                   rng.rand(B, H, W, 3).astype(np.float32),
                   (rng.randn(B, H, W, 2) * 2).astype(np.float32),
                   np.ones((B, H, W), np.float32))

    train(config, tconfig, batches(), ckpt_dir=str(tmp_path),
          data_parallel=False, log_fn=lambda m: None)

    recs = [json.loads(ln) for ln in
            (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert recs[0]["event"] == "manifest"
    man = recs[0]
    assert man["git_sha"] and man["jax_version"] == jax.__version__
    assert man["device_kind"] == jax.devices()[0].device_kind
    assert len(man["config_hash"]) == 16
    assert man["mode"] == "train" and man["tconfig_hash"]
    steps = [r for r in recs if "step" in r and "event" not in r]
    assert [r["step"] for r in steps] == [0, 1]
    end = recs[-1]
    assert end["event"] == "run_end" and end["final_step"] == 2
    assert end["metrics"]["raft_train_steps_total"] == 2.0
    assert end["metrics"]["raft_train_nonfinite_total"] == 0.0


# ------------------------------------------------------------- tlm -------

def _load_tlm():
    spec = importlib.util.spec_from_file_location(
        "tlm", REPO / "tools" / "tlm.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fake_run(tmp_path, name, sha, epe):
    d = tmp_path / name
    d.mkdir()
    man = run_manifest(mode="train")
    man["git_sha"] = sha
    man["config_hash"] = "cafe" * 4
    lines = [
        {"t": 1.0, "event": "manifest", **man},
        {"step": 0, "loss": 10.0, "epe": epe + 1.0},
        {"step": 1, "loss": 5.0, "epe": epe},
        {"t": 2.0, "event": "run_end", "final_step": 2,
         "metrics": {"raft_train_steps_total": 2.0}},
    ]
    (d / "events.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in lines))
    return d


def test_tlm_summary_reports_provenance_and_trajectory(tmp_path):
    tlm = _load_tlm()
    a = _fake_run(tmp_path, "a", "a" * 40, epe=2.0)
    out = "\n".join(tlm.summary_lines(a))
    assert "a" * 40 in out
    assert "cafecafecafecafe" in out
    assert "steps 0 -> 1" in out
    assert "raft_train_steps_total" in out


def test_tlm_compare_diffs_provenance_and_numbers(tmp_path):
    tlm = _load_tlm()
    a = _fake_run(tmp_path, "a", "a" * 40, epe=2.0)
    b = _fake_run(tmp_path, "b", "b" * 40, epe=1.0)
    lines, comparable = tlm.compare_lines(a, b)
    out = "\n".join(lines)
    assert comparable
    assert "git_sha" in out and "a" * 40 in out and "b" * 40 in out
    assert "final.epe" in out and "-50.0%" in out
    assert "(same)" in out          # identical values reported as such


def test_tlm_handles_bench_json_and_missing_manifest(tmp_path):
    tlm = _load_tlm()
    bench = tmp_path / "BENCH_test.json"
    bench.write_text(json.dumps({
        "metric": "inference throughput", "value": 3.25,
        "unit": "pairs/sec/chip",
        "manifest": run_manifest(mode="bench")}))
    out = "\n".join(tlm.summary_lines(bench))
    assert "3.25" in out and "git_sha" in " ".join(tlm.MANIFEST_FIELDS) \
        or "git_sha" in out
    legacy = tmp_path / "BENCH_old.json"
    legacy.write_text(json.dumps({"metric": "x", "value": 1.0}))
    lines, comparable = tlm.compare_lines(bench, legacy)
    assert not comparable           # provenance unknown on one side
    assert any("manifest missing" in ln for ln in lines)


def test_tlm_cli_roundtrip(tmp_path):
    a = _fake_run(tmp_path, "a", "1" * 40, epe=3.0)
    b = _fake_run(tmp_path, "b", "2" * 40, epe=2.0)
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "tlm.py"), "compare",
         str(a), str(b)], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "git_sha" in out.stdout
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "tlm.py"), "tail",
         str(a), "-n", "2"], capture_output=True, text=True)
    assert out.returncode == 0
    assert "run_end" in out.stdout


# ----------------------------------------------------------- tlm top -----

def test_tlm_sparkline_scaling_and_gaps():
    tlm = _load_tlm()
    assert tlm.sparkline([]) == ""
    assert tlm.sparkline([None, None]) == "  "     # all-gap, width kept
    line = tlm.sparkline([0.0, None, 10.0])
    assert line[0] == tlm.SPARK_CHARS[0]
    assert line[1] == " "                          # None is a gap, not a 0
    assert line[2] == tlm.SPARK_CHARS[-1]
    assert len(tlm.sparkline(list(range(100)), width=40)) == 40
    # constant series renders (span-0 guard), at the low block
    assert set(tlm.sparkline([3.0, 3.0, 3.0])) == {tlm.SPARK_CHARS[0]}


def test_tlm_top_frame_replica_and_fleet_forms():
    tlm = _load_tlm()
    series = {"t": [1.0, 2.0], "pairs_per_s": [5.0, 7.0],
              "p95_ms": [None, None]}
    clean = {"interval_s": 1.0, "retained": 3, "span_s": 2.0,
             "series": series, "anomalies_active": {}}
    out = "\n".join(tlm.top_frame(clean, "replica"))
    assert "pairs_per_s" in out
    assert re.search(r"pairs_per_s\s+7\b", out)
    assert "anomalies: none active" in out
    assert "—" in out                              # all-None series last value
    firing = dict(clean, anomalies_active={"p95_drift": "p95 900ms > 2x"})
    out = "\n".join(tlm.top_frame(firing, "replica"))
    assert "ANOMALY p95_drift: p95 900ms > 2x" in out
    # fleet-router form: numeric source order, skew tag on the verdict
    fleet = {"sources": {"0": series, "10": series, "2": series},
             "skewed": [2]}
    lines = tlm.top_frame(fleet, "router")
    order = [ln for ln in lines if ln.startswith("  replica ")]
    assert [ln.split()[1] for ln in order] == ["0", "2", "10"]
    assert "SKEWED" in order[1] and "SKEWED" not in order[0]
    assert tlm.top_frame({"sources": {}}, "router")[-1] \
        == "  (no replica scrapes ingested yet)"


def _write_spill(path, t0, n, rate, manifest=None):
    """n samples, 10s apart, pairs counter advancing ``rate``/s."""
    recs = []
    if manifest:
        recs.append({"kind": "manifest", **manifest})
    for i in range(n):
        t = t0 + 10.0 * i
        recs.append({"kind": "sample", "t": t,
                     "snap": {"_scrape_time": t,
                              "raft_serving_pairs_total": rate * 10.0 * i}})
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))


def test_tlm_top_replay_file_dir_and_window(tmp_path):
    tlm = _load_tlm()
    spill = tmp_path / "metrics_ts.jsonl"
    _write_spill(spill, 100.0, 4, rate=7.0, manifest={"mode": "serve"})
    payload = tlm._replay_payload(str(spill))
    assert payload["retained"] == 4
    assert payload["interval_s"] == 10.0
    assert payload["series"]["pairs_per_s"] == [7.0, 7.0, 7.0]
    assert payload["manifest"]["mode"] == "serve"
    # window clips to the trailing seconds of the spill
    assert tlm._replay_payload(str(spill), window=15.0)["retained"] == 2
    out = "\n".join(tlm.top_lines(str(spill)))
    assert "pairs_per_s" in out and "(replay)" in out
    # a run dir with ONE spill replays as that replica
    assert tlm._replay_payload(str(tmp_path))["retained"] == 4
    # a fleet out-dir (replica-N subdirs) merges as sources
    fleet = tmp_path / "fleet"
    for i in range(2):
        sub = fleet / f"replica-{i}"
        sub.mkdir(parents=True)
        _write_spill(sub / "metrics_ts.jsonl", 100.0, 3, rate=float(i + 1))
    payload = tlm._replay_payload(str(fleet))
    assert set(payload["sources"]) == {"replica-0", "replica-1"}
    assert payload["sources"]["replica-1"]["pairs_per_s"] == [2.0, 2.0]
    out = "\n".join(tlm.top_lines(str(fleet)))
    assert "replica replica-0" in out and "replica replica-1" in out
    with pytest.raises(FileNotFoundError):
        tlm._replay_payload(str(tmp_path / "empty-nothing"))


def test_tlm_top_cli_once_and_bad_target(tmp_path):
    spill = tmp_path / "metrics_ts.jsonl"
    _write_spill(spill, 100.0, 3, rate=4.0)
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "tlm.py"), "top",
         str(spill), "--once"], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "tlm top" in out.stdout and "pairs_per_s" in out.stdout
    # a missing path / unreachable URL is rc=2 with a message, not a crash
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "tlm.py"), "top",
         str(tmp_path / "nope"), "--once"], capture_output=True, text=True)
    assert out.returncode == 2
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "tlm.py"), "top",
         "http://127.0.0.1:9", "--once"], capture_output=True, text=True)
    assert out.returncode == 2


def test_tlm_summary_highlights_fleet_cache_and_anomalies(tmp_path):
    tlm = _load_tlm()
    d = tmp_path / "run"
    d.mkdir()
    man = run_manifest(mode="serve")
    lines = [
        {"t": 1.0, "event": "manifest", **man},
        {"t": 2.0, "event": "anomaly", "rule": "p95_drift", "edge": "fire",
         "reason": "p95 900ms > 2x baseline"},
        {"t": 3.0, "event": "anomaly", "rule": "p95_drift", "edge": "clear"},
        {"t": 4.0, "event": "run_end", "final_step": 0,
         "metrics": {"raft_fleet_replicas_ready": 3.0,
                     "raft_fleet_replica_skew": 1.0,
                     "raft_engine_cache_hits_total": 7.0,
                     "raft_engine_cache_misses_total": 2.0,
                     "raft_anomaly_fires_total": {"p95_drift": 1.0,
                                                  "queue_growth": 0.0},
                     "_scrape_time": 123.0}},
    ]
    (d / "events.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in lines))
    out = "\n".join(tlm.summary_lines(d))
    assert "ANOMALIES: 1 sentinel fire(s)" in out and "p95_drift" in out
    assert "engine cache" in out and "7" in out
    assert "fleet:" in out and "replicas_ready" in out
    assert "anomaly sentinels fired: p95_drift x1" in out
    assert "_scrape_time" not in out               # private keys stay hidden
