"""Request-scoped tracing tests (tier-1, CPU): the span/tracer/flight-
recorder/SLO primitives (telemetry/spans.py), the serving integration on
stub engines (no compiles, deterministic failures), and the tlm trace
renderer.  The live-HTTP tracing path is covered in test_serving.py; the
chaos-drill correlation in test_chaos.py.
"""

import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from raft_tpu.serving import (BreakerOpen, DeadlineExceeded, FlowServer,
                              PoisonedRequest, QueueFull, Registry,
                              ServeConfig)
from raft_tpu.serving.batcher import BatcherCrashed
from raft_tpu.serving.metrics import make_slo_metrics
from raft_tpu.telemetry import spans

from test_serving import (BUCKET, PhasedEngine, StubEngine,  # noqa: F401
                          make_request)


# ------------------------------------------------------ span primitives --

def test_trace_records_spans_and_closes_once():
    tracer = spans.Tracer(sample=1.0)
    tr = tracer.start("pair", trace_id=None)
    assert tracer.open_traces == 1
    t = time.monotonic()
    eid = tr.span("execute", t, t + 0.010, batch_real=2)
    tr.span("execute_block", t + 0.002, t + 0.010, parent=eid)
    rec = tr.finish()
    assert tracer.open_traces == 0 and tracer.finished == 1
    assert rec["status"] == "ok" and rec["kind"] == "pair"
    names = [s["name"] for s in rec["spans"]]
    assert names[0] == "request"                      # synthesized root
    root = rec["spans"][0]
    assert root["parent"] is None and rec["dur_ms"] == root["dur_ms"]
    by_name = {s["name"]: s for s in rec["spans"]}
    # parentless spans were re-parented onto the root; explicit parents kept
    assert by_name["execute"]["parent"] == root["span"]
    assert by_name["execute_block"]["parent"] == eid
    assert by_name["execute"]["batch_real"] == 2
    assert abs(by_name["execute"]["dur_ms"] - 10.0) < 2.0
    # closed: further spans/finishes are no-ops
    assert tr.finish() is None
    assert tr.span("late", t, t + 1.0) is None
    assert tr.timings_ms()["execute"] > 0


def test_span_ids_are_unique_hex_and_cost_no_system_call(monkeypatch):
    """A span id is the process's prefix and a counter: the batcher mints
    several per row while every handler it woke wants the GIL, and a uuid4
    (os.urandom, GIL released) per span cost 5 % of the cell's pairs/s."""
    import os
    import uuid
    monkeypatch.setattr(os, "urandom", lambda n: 1 / 0)
    monkeypatch.setattr(uuid, "uuid4", lambda: 1 / 0)
    ids = [spans.new_span_id() for _ in range(1000)]
    assert len(set(ids)) == 1000
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)
    assert len({i[:8] for i in ids}) == 1          # one prefix per process


def test_status_escalation_and_exception_mapping():
    tracer = spans.Tracer(sample=1.0)
    tr = tracer.start("stream")
    tr.set_status(spans.DEGRADED)
    tr.set_status(spans.OK)                # cannot de-escalate
    assert tr.finish()["status"] == "degraded"
    # exception -> status classification (the classes carry trace_status)
    assert spans.status_of(QueueFull("x")) == "shed"
    assert spans.status_of(BreakerOpen("x")) == "shed"
    assert spans.status_of(DeadlineExceeded("x")) == "timeout"
    assert spans.status_of(PoisonedRequest("x")) == "poisoned"
    assert spans.status_of(BatcherCrashed("x")) == "error"
    assert spans.status_of(ValueError("x")) == "error"


def test_clean_trace_id():
    assert spans.clean_trace_id("ABCDEF-123") == "abcdef-123"
    minted = spans.clean_trace_id(None)
    assert len(minted) == 32 and spans.clean_trace_id(minted) == minted
    # junk (too long / bad chars) is replaced, never echoed into logs
    assert spans.clean_trace_id("x" * 100) != "x" * 100
    assert "<" not in spans.clean_trace_id("<script>")


def test_systematic_sampling_retains_errors():
    fr = spans.FlightRecorder(capacity=64)
    tracer = spans.Tracer(sample=0.25, recorder=fr)
    for _ in range(16):
        tracer.start("pair").finish()
    ok, err = fr.counts()
    assert ok == 4 and err == 0            # exact-rate systematic sampling
    # error traces are retained regardless of the sampling decision
    for _ in range(8):
        tracer.start("pair").finish(spans.POISONED)
    ok, err = fr.counts()
    assert ok == 4 and err == 8
    assert tracer.open_traces == 0


def test_sample_zero_disables_tracing():
    tracer = spans.Tracer(sample=0.0)
    assert tracer.start("pair") is None
    assert tracer.open_traces == 0


def test_flight_recorder_rings_and_dump(tmp_path):
    path = tmp_path / "flightrec.jsonl"
    fr = spans.FlightRecorder(capacity=4, path=path)
    for i in range(10):
        fr.add({"trace_id": f"ok{i}", "status": "ok", "t": float(i)})
    fr.add({"trace_id": "bad", "status": "error", "t": 99.0})
    ok, err = fr.counts()
    assert ok == 4 and err == 1            # ring bounded; errors separate
    snap = fr.snapshot()
    assert [r["trace_id"] for r in snap] == ["ok6", "ok7", "ok8", "ok9",
                                             "bad"]
    out = fr.dump("unit_test")
    assert out == str(path) and fr.dumps == 1
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert recs[0]["event"] == "flightrec_dump"
    assert recs[0]["reason"] == "unit_test" and recs[0]["traces"] == 5
    assert len(recs) == 6
    # an error storm cannot evict its own evidence
    for i in range(10):
        fr.add({"trace_id": f"e{i}", "status": "error", "t": 200.0 + i})
    ok, err = fr.counts()
    assert ok == 4 and err == 4            # error ring bounded too
    # ...and neither can a SHED storm: breaker-open sheds are one trace
    # per rejected request — they ride the recency ring, never the
    # evidence ring holding the errors that explain the open
    for i in range(10):
        fr.add({"trace_id": f"s{i}", "status": "shed", "t": 300.0 + i})
    ok, err = fr.counts()
    assert ok == 4 and err == 4
    assert all(r["status"] == "error"      # evidence intact
               for r in fr.snapshot() if r["trace_id"].startswith("e"))
    # no path configured -> dump is a no-op, not an error
    assert spans.FlightRecorder(capacity=2).dump("x") is None


def test_slo_tracker_burn_rate_and_metrics():
    slo = spans.SLOTracker(objectives={"pair": 0.100, "stream": 0.050},
                           budget=0.1, window=10)
    reg = Registry()
    make_slo_metrics(reg, slo)
    for _ in range(8):
        slo.observe("pair", spans.OK, 0.010)         # fast + ok: no burn
    slo.observe("pair", spans.OK, 0.500)             # slow: burns
    slo.observe("pair", spans.POISONED, 0.010)       # failed: burns
    slo.observe("pair", spans.DEGRADED, 0.010)       # degraded+fast: ok
    slo.observe("pair", spans.BAD_REQUEST, 9.9)      # client junk: ignored
    slo.observe("other", spans.OK, 9.9)              # unknown class: ignored
    # window of 10 holds the last 10: 2 violations / 10 / budget 0.1 = 2.0
    assert abs(slo.burn_rate("pair") - 2.0) < 1e-9
    assert slo.burn_rate("stream") == 0.0            # nothing observed
    text = reg.render()
    assert 'raft_slo_burn_rate{class="pair"} 2' in text
    assert 'raft_slo_violations_total{class="pair"} 2' in text
    assert 'raft_slo_violations_total{class="stream"} 0' in text


def test_device_slot_and_ambient_trace_ids():
    from raft_tpu.telemetry.trace import host_stage
    assert spans.take_device_slot() is None
    sink = lambda st: spans.record_device_stage("pair", st)  # noqa: E731
    with host_stage("raft.engine.dispatch", sink):   # no slot: dropped
        pass
    spans.set_device_slot([])
    with host_stage("raft.engine.dispatch", sink) as a:
        pass
    with host_stage("raft.engine.wait", sink, holds=True) as b:
        pass
    # (the last member: the stage holds the stages opened inside it)
    assert spans.take_device_slot() == [
        ("pair", "execute_dispatch", "engine.dispatch", a.t0, a.t1, a.cpu,
         False),
        ("pair", "execute_block", "engine.wait", b.t0, b.t1, b.cpu, True)]
    assert a.t0 <= a.t1 <= b.t0 <= b.t1
    assert a.c0 <= a.c1 <= b.c0 <= b.c1          # the thread's CPU clock
    assert spans.take_device_slot() is None          # take clears
    assert spans.current_trace_ids() == ()
    spans.set_current_trace_ids(("a", "b"))
    assert spans.current_trace_ids() == ("a", "b")
    spans.set_current_trace_ids(())
    assert spans.current_trace_ids() == ()


def _sleeps():
    time.sleep(0.05)


def _spins():
    a = np.ones((64, 64), np.float32)
    end = time.thread_time() + 0.05         # 50 ms of this thread's CPU
    while time.thread_time() < end:
        np.isfinite(a + a).all()


def _raises():
    time.sleep(0.05)
    raise RuntimeError("the body failed")


@pytest.mark.parametrize("body,cpu_lo,cpu_hi", [
    (_sleeps, 0.0, 0.010),            # asleep: the CPU clock stands still
    (_spins, 0.05, None),             # at work: it runs, under the wall clock
    (_raises, 0.0, 0.010)])           # stamped when the body raises, too
def test_host_stage_stamps_cpu_seconds_beside_wall(body, cpu_lo, cpu_hi):
    from raft_tpu.telemetry.trace import host_stage
    seen = []
    try:
        with host_stage("raft.batch.pad", seen.append) as st:
            body()
    except RuntimeError:
        assert body is _raises
    assert seen == [st]
    assert st.wall == st.t1 - st.t0 >= 0.05
    assert st.cpu == st.c1 - st.c0
    assert cpu_lo <= st.cpu <= (cpu_hi if cpu_hi is not None
                                else st.wall + CPU_SLACK_MS / 1e3)


# ----------------------------------------- serving integration (stubs) --

def _server(engine, **cfg):
    defaults = dict(buckets=(BUCKET,), max_batch=4, batch_steps=(1, 2, 4),
                    max_wait_ms=5.0, queue_depth=16, port=0, max_sessions=0,
                    retry_backoff_ms=1.0, default_deadline_ms=10_000.0)
    defaults.update(cfg)
    server = FlowServer(None, None, ServeConfig(**defaults), engine=engine)
    server.start()
    return server


# what a stage's CPU seconds may read over its wall seconds, in ms: the two
# clocks are read one after the other
CPU_SLACK_MS = 5.0

# what the top-level spans of an ok request may leave unaccounted, in ms:
# the few statements between one stage's end and the next one's start, and
# a thread switch or two.  Absolute, because a stub request lasts about 1 ms
# and no ratio of that means anything.
TILE_SLACK_MS = 5.0


def _tiles(rec):
    """(root duration, sum of its children's durations), ms."""
    root = rec["spans"][0]
    assert root["name"] == "request" and root["parent"] is None
    return root["dur_ms"], sum(s["dur_ms"] for s in rec["spans"]
                               if s.get("parent") == root["span"])


def test_ok_request_trace_accounts_for_its_latency():
    server = _server(StubEngine())
    try:
        im = np.zeros((32, 48, 3), np.float32)
        req = server.infer(im, im)
        assert req.trace is not None and req.trace.closed
        assert server.tracer.open_traces == 0
        [rec] = server.flightrec.snapshot()
        assert rec["status"] == "ok"
        names = [s["name"] for s in rec["spans"]]
        # a direct caller: no body to decode or encode, no socket; the
        # wake-up after resolve is its respond
        assert names[1:] == ["admit", "queue_wait", "batch_form", "pad",
                             "execute", "deliver", "respond"]
        root_ms, top_ms = _tiles(rec)
        assert abs(root_ms - top_ms) < TILE_SLACK_MS
        # and they tile in order: each starts where the one before ended
        tops = rec["spans"][1:]
        for a, b in zip(tops, tops[1:]):
            assert abs(a["start_ms"] + a["dur_ms"] - b["start_ms"]) \
                < TILE_SLACK_MS, (a["name"], b["name"])
    finally:
        server.stop()


class DeviceStubEngine(StubEngine):
    """A stub that times its device call as the real engine does: the
    four host stages, handed to the batcher's device slot."""

    def run(self, bucket, im1, im2):
        from raft_tpu.telemetry.trace import host_stage
        sink = lambda st: spans.record_device_stage("pair", st)  # noqa: E731
        for name in ("h2d", "dispatch", "wait"):
            with host_stage(f"raft.engine.{name}", sink, call="pair"):
                time.sleep(0.002)
        with host_stage("raft.engine.fetch", sink, call="pair"):
            return super().run(bucket, im1, im2)


def _post_npz(server, im, trace_id=None):
    import io
    import urllib.request
    buf = io.BytesIO()
    np.savez(buf, image1=im, image2=im)
    headers = {"Content-Type": "application/octet-stream",
               "Accept": "application/octet-stream"}
    if trace_id:
        headers["X-Raft-Trace-Id"] = trace_id
    req = urllib.request.Request(server.url + "/v1/flow",
                                 data=buf.getvalue(), headers=headers)
    with urllib.request.urlopen(req) as r:
        timings = json.loads(r.headers["X-Raft-Timings"]) \
            if r.headers.get("X-Raft-Timings") else None
        return r.status, timings, r.read()


def _finished_trace(server, trace_id, timeout=5.0):
    """The handler finishes a trace AFTER the body went out: poll."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        recs = [r for r in server.flightrec.snapshot()
                if r["trace_id"] == trace_id]
        if recs:
            return recs[0]
        time.sleep(0.01)
    raise AssertionError(f"trace {trace_id} never finished")


TOP_LEVEL = ("decode", "admit", "queue_wait", "batch_form", "pad", "execute",
             "deliver", "respond", "encode", "respond")
DEVICE_CHILDREN = ("execute_h2d", "execute_dispatch", "execute_block",
                   "execute_fetch")


@pytest.mark.parametrize("span,parent", [
    *[(name, "request") for name in sorted(set(TOP_LEVEL))],
    *[(name, "execute") for name in DEVICE_CHILDREN]])
def test_http_request_spans_have_the_right_parents(span, parent):
    server = _server(DeviceStubEngine())
    try:
        im = (np.arange(32 * 48 * 3) % 255).astype(np.uint8).reshape(32, 48, 3)
        status, _, _ = _post_npz(server, im, "abc0")
        assert status == 200
        rec = _finished_trace(server, "abc0")
        by_id = {s["span"]: s for s in rec["spans"]}
        found = [s for s in rec["spans"] if s["name"] == span]
        assert found, span
        for s in found:
            assert by_id[s["parent"]]["name"] == parent
    finally:
        server.stop()


def test_http_request_spans_tile_it_and_encode_reaches_the_header():
    server = _server(DeviceStubEngine())
    try:
        im = np.zeros((32, 48, 3), np.float32)
        status, timings, _ = _post_npz(server, im, "abc1")
        assert status == 200
        rec = _finished_trace(server, "abc1")
        assert tuple(s["name"] for s in rec["spans"]
                     if s["name"] not in DEVICE_CHILDREN)[1:] == TOP_LEVEL
        root_ms, top_ms = _tiles(rec)
        assert abs(root_ms - top_ms) < TILE_SLACK_MS
        # the header holds everything up to the socket write, encode
        # included (it is done before the snapshot); of respond, the wake-up
        assert set(timings) == set(TOP_LEVEL) | set(DEVICE_CHILDREN)
        wake = [s for s in rec["spans"] if s.get("part") == "wake"]
        assert timings["respond"] == wake[0]["dur_ms"]
        encode = [s for s in rec["spans"] if s["name"] == "encode"]
        assert timings["encode"] == encode[0]["dur_ms"] > 0
        # the device call's children lie inside execute and tile it
        [ex] = [s for s in rec["spans"] if s["name"] == "execute"]
        kids = [s for s in rec["spans"] if s["parent"] == ex["span"]]
        assert [s["name"] for s in kids] == list(DEVICE_CHILDREN)
        assert abs(ex["dur_ms"] - sum(s["dur_ms"] for s in kids)) \
            < TILE_SLACK_MS
        assert all(s["dur_ms"] >= 2.0 for s in kids[:3])
    finally:
        server.stop()


def _raft_annotations(trace_dir):
    """{annotation name: [batch ordinal of each event]} of a capture."""
    import glob
    from jax.profiler import ProfileData
    [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("raft."):
                    found.setdefault(ev.name, []).append(
                        dict(ev.stats).get("batch"))
    return found


def _prom(server):
    out = {}
    for ln in server.registry.render().splitlines():
        if ln and not ln.startswith("#"):
            k, v = ln.rsplit(" ", 1)
            out[k] = float(v)
    return out


@pytest.mark.parametrize("sample", [1.0, 0.0])
def test_profiler_capture_holds_every_host_stage_with_its_batch(
        sample, tmp_path):
    """One call site per stage yields the annotation (on the profiler's
    clock, with the device batch's ordinal), the stage seconds and — when
    the request is traced — the span.  With tracing sampled out no span is
    recorded, and the annotations and the counters still are."""
    import jax
    from raft_tpu.telemetry.trace import HOST_STAGES, profile_options
    server = _server(DeviceStubEngine(), trace_sample=sample)
    try:
        im = np.zeros((32, 48, 3), np.float32)
        jax.profiler.start_trace(str(tmp_path),
                                 profiler_options=profile_options())
        try:
            for tid in ("aa01", "aa02"):          # one after another: two
                status, timings, _ = _post_npz(server, im, tid)   # batches
                assert status == 200
                assert (timings is None) == (sample == 0.0)
        finally:
            jax.profiler.stop_trace()
        ann = _raft_annotations(str(tmp_path))
        # (raft.stream.*: a batched /v1/stream advance's, not a pair's)
        pair_stages = [name for name in HOST_STAGES
                       if not name.startswith("raft.stream.")]
        assert set(ann) == set(pair_stages)
        for name in pair_stages:
            if name.startswith(("raft.batch.", "raft.engine.")):
                # the batcher's stages of batch 1 and batch 2; the take
                # that waited for batch 1 began before the capture, and
                # the one waiting for a batch 3 has not ended.  A take
                # that finds the batch before it finished returns at once
                # to have it delivered, and is then made again (this stub's
                # call is over when its dispatch returns).  form is
                # three annotations a batch: everything between take and
                # pad, in the loop, in _execute and in _form
                want = {"raft.batch.take": [2, 2, 3],
                        "raft.batch.form": [1, 1, 1, 2, 2, 2]}.get(
                            name, [1, 2])
                assert ann[name] == want, (name, ann[name])
        prom = _prom(server)
        for name in pair_stages:
            label = name[len("raft."):]
            key = f'raft_serving_stage_seconds_total{{stage="{label}"}}'
            assert prom[key] > 0.0, key
        assert prom["raft_serving_device_calls_total"] == 2
        if sample == 0.0:
            assert server.flightrec is None
            assert server.tracer.start("pair") is None
        else:
            assert _finished_trace(server, "aa02")["status"] == "ok"
    finally:
        server.stop()


class PhasedDeviceStub(PhasedEngine):
    """The phased fake timing its stages as the real engine does, behind a
    ``run`` that composes them, so that a FlowServer pipelines it.  The
    h2d of the first call takes 10 ms and of the second 60: a span's length
    says whose it is; every annotation says it too (``index``)."""

    def _stage(self, name, index):
        from raft_tpu.telemetry.trace import host_stage
        sink = lambda st: spans.record_device_stage("pair", st)  # noqa: E731
        return host_stage(f"raft.engine.{name}", sink, call="pair",
                          index=index)

    def place(self, *args):
        index = len(self.calls) + 1
        with self._stage("h2d", index):
            time.sleep(0.01 + 0.05 * (index - 1))
            return super().place(*args)

    def dispatch(self, call):
        with self._stage("dispatch", call.i + 1):
            super().dispatch(call)

    def wait(self, call):
        with self._stage("wait", call.i + 1):
            super().wait(call)

    def fetch(self, call):
        with self._stage("fetch", call.i + 1):
            return super().fetch(call)

    def run(self, *args):
        call = self.place(*args)
        self.dispatch(call)
        self.wait(call)
        return self.fetch(call)

    run.composes_phases = True


def test_pipelined_batches_keep_their_spans_and_their_ordinals(tmp_path):
    """Two requests in each of two consecutive device batches, the second
    placed while the first runs: every request's spans still tile it, the
    co-batched share one execute span, the engine's stages under an
    execute are those of ITS batch, and every annotation of the batcher
    thread carries the ordinal of its own batch although the stages of
    the two interleave."""
    import jax
    from raft_tpu.telemetry.trace import profile_options
    eng = PhasedDeviceStub(hold=(0,))
    server = _server(eng, max_batch=2, batch_steps=(1, 2),
                     max_wait_ms=10_000.0)
    try:
        im = np.zeros((32, 48, 3), np.float32)
        timings = {}

        def post(tid):
            timings[tid] = _post_npz(server, im, tid)[1]

        jax.profiler.start_trace(str(tmp_path),
                                 profiler_options=profile_options())
        try:
            ts = [threading.Thread(target=post, args=(t,))
                  for t in ("a1", "a2", "b1", "b2")]
            for t in ts[:2]:
                t.start()
            eng.saw("dispatch", 0)
            for t in ts[2:]:
                t.start()
            eng.saw("h2d", 1)               # placed under call 0's run
            eng.finish(0)
            for t in ts:
                t.join(10)
        finally:
            jax.profiler.stop_trace()
        assert [e[:2] for e in eng.log] == [
            ("h2d", 0), ("dispatch", 0), ("h2d", 1), ("wait", 0),
            ("dispatch", 1), ("fetch", 0), ("wait", 1), ("fetch", 1)]
        exec_ids = {}
        for tid in timings:
            rec = _finished_trace(server, tid)
            assert rec["status"] == "ok"
            root_ms, top_ms = _tiles(rec)     # four handlers and a capture
            assert abs(root_ms - top_ms) < 2 * TILE_SLACK_MS, tid
            assert set(timings[tid]) == set(TOP_LEVEL) | set(DEVICE_CHILDREN)
            [ex] = [s for s in rec["spans"] if s["name"] == "execute"]
            exec_ids.setdefault(ex["span"], set()).add(tid[0])
            kids = [s for s in rec["spans"] if s["parent"] == ex["span"]]
            assert [s["name"] for s in kids] == list(DEVICE_CHILDREN)
            for s in kids:                  # inside their execute
                assert ex["start_ms"] - 0.01 <= s["start_ms"] and \
                    s["start_ms"] + s["dur_ms"] \
                    <= ex["start_ms"] + ex["dur_ms"] + 0.01
                # the engine's stages came through the batcher's slot with
                # their CPU seconds: an h2d that sleeps used next to none
                assert 0.0 <= s["cpu_ms"] <= s["dur_ms"] + CPU_SLACK_MS
            assert kids[0]["cpu_ms"] < 10.0 <= kids[0]["dur_ms"], tid
            if tid[0] == "a":
                assert 10.0 <= kids[0]["dur_ms"] < 60.0, tid
            else:
                assert 60.0 <= kids[0]["dur_ms"], tid
        assert sorted(map(sorted, exec_ids.values())) == [["a"], ["b"]]
        found = {}
        import glob
        from jax.profiler import ProfileData
        [path] = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                           recursive=True)
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines if plane.name.startswith("/host:") \
                    else ():
                for ev in line.events:
                    if ev.name.startswith(("raft.batch.", "raft.engine.")):
                        stats = dict(ev.stats)
                        found.setdefault(ev.name, []).append(
                            (ev.start_ns, stats["batch"],
                             stats.get("index")))
        for name, evs in found.items():
            ordinals = [b for _, b, _ in sorted(evs)]
            if name.startswith("raft.engine."):
                assert all(b == i for _, b, i in evs), (name, evs)
                assert ordinals == [1, 2], (name, ordinals)
        assert [b for _, b, _ in sorted(found["raft.batch.pad"])] == [1, 2]
        assert [b for _, b, _ in sorted(found["raft.batch.deliver"])] \
            == [1, 2]
        # batch 2's take and form lie between batch 1's dispatch and its
        # deliver, under their own ordinal
        t_deliver1 = min(found["raft.batch.deliver"])[0]
        early = sorted(e for e in found["raft.batch.form"]
                       + found["raft.batch.take"] if e[0] < t_deliver1)
        assert [b for _, b, _ in early][-4:] == [2, 2, 2, 2], early
        prom = _prom(server)
        for stage in ("h2d", "dispatch", "wait", "fetch"):
            wall, cpu = (prom[f'raft_serving_stage{c}_seconds_total'
                              f'{{stage="engine.{stage}"}}']
                         for c in ("", "_cpu"))
            assert 0.0 <= cpu <= wall + CPU_SLACK_MS / 1e3, stage
        assert prom['raft_serving_stage_seconds_total{stage="engine.h2d"}'] \
            >= 0.07                         # 10 ms + 60 ms of sleep
    finally:
        server.stop()


def _stage_family(prom, name):
    """{stage label: value} of one of the stage families."""
    return {k.split('stage="', 1)[1][:-2]: v for k, v in prom.items()
            if k.startswith(name + "{")}


def test_stage_families_share_their_labels_from_start_up():
    """The exposition of a fresh server: wall, CPU and stalled seconds hold a
    child for every host stage and for the sentinel inside deliver, at 0."""
    from raft_tpu.telemetry.trace import HOST_STAGES, SENTINEL
    server = _server(StubEngine())
    try:
        prom = _prom(server)
        want = {name[len("raft."):] for name in HOST_STAGES} | {SENTINEL}
        for family in ("raft_serving_stage_seconds_total",
                       "raft_serving_stage_cpu_seconds_total",
                       "raft_serving_stalled_seconds_total"):
            assert set(_stage_family(prom, family)) == want, family
        assert not any(
            _stage_family(prom, "raft_serving_stalled_seconds_total").values())
    finally:
        server.stop()


def test_sentinel_is_counted_inside_deliver_once_a_batch():
    """The non-finite pass has a label of its own in both families, once a
    device batch and inside ``batch.deliver``, whose counter, span and
    ``X-Raft-Timings`` key stay what they were."""
    from raft_tpu.telemetry.trace import SENTINEL
    server = _server(DeviceStubEngine())
    seen = []
    record = server.stages.record

    def spy(label, wall, cpu):
        seen.append((label, wall, cpu))
        record(label, wall, cpu)

    server.stages.record = spy
    try:
        im = np.zeros((32, 48, 3), np.float32)
        for tid in ("cc01", "cc02"):              # two device batches
            status, timings, _ = _post_npz(server, im, tid)
            assert status == 200
            assert set(timings) == set(TOP_LEVEL) | set(DEVICE_CHILDREN)
            rec = _finished_trace(server, tid)
            names = [s["name"] for s in rec["spans"]]
            assert names.count("deliver") == 1 and SENTINEL not in names
            [deliver] = [s for s in rec["spans"] if s["name"] == "deliver"]
            assert 0.0 <= deliver["cpu_ms"] <= deliver["dur_ms"] + CPU_SLACK_MS
        sentinel = [(w, c) for label, w, c in seen if label == SENTINEL]
        deliver = [(w, c) for label, w, c in seen if label == "batch.deliver"]
        assert len(sentinel) == len(deliver) == 2
        for (sw, sc), (dw, dc) in zip(sentinel, deliver):
            assert 0.0 < sw <= dw and 0.0 <= sc <= dc + CPU_SLACK_MS / 1e3
        prom = _prom(server)
        for family, i in (("raft_serving_stage_seconds_total", 0),
                          ("raft_serving_stage_cpu_seconds_total", 1)):
            got = _stage_family(prom, family)
            assert got[SENTINEL] == pytest.approx(sum(s[i] for s in sentinel))
            assert got["batch.deliver"] == pytest.approx(
                sum(d[i] for d in deliver))
    finally:
        server.stop()


@pytest.fixture
def run_log(tmp_path):
    from raft_tpu.telemetry import events as tlm_events
    log = tlm_events.RunLog(tmp_path)
    tlm_events.set_current(log)
    try:
        yield lambda: [r for r in tlm_events.read_events(tmp_path)
                       if r["event"] == "host_stall"]
    finally:
        tlm_events.set_current(None)
        log.close()


def _stalled(server):
    return _stage_family(_prom(server), "raft_serving_stalled_seconds_total")


def test_a_stage_that_stood_still_says_so(run_log, monkeypatch):
    """One stage over the threshold: one ``host_stall`` event with the
    stage, its wall and CPU seconds, the batch and the thread, its wall
    seconds in ``raft_serving_stalled_seconds_total``, one log line."""
    from raft_tpu.telemetry import trace
    server = _server(DeviceStubEngine())       # h2d, dispatch, wait: 2 ms
    lines = []
    server.stages.log_fn = lines.append
    try:
        im = np.zeros((32, 48, 3), np.float32)
        server.infer(im, im)
        assert run_log() == [] and not any(_stalled(server).values())
        monkeypatch.setattr(trace, "STALL_SECONDS", 0.0015)
        server.infer(im, im)
        monkeypatch.setattr(trace, "STALL_SECONDS", 2.0)
        events = [e for e in run_log() if e["stage"] == "engine.wait"]
        assert len(events) == 1
        [ev] = events
        assert ev["wall_s"] >= 0.002 and 0.0 <= ev["cpu_s"] <= ev["wall_s"]
        assert ev["batch"] == 2 and ev["thread"] == "raft-serving-batcher"
        assert "open_traces" not in ev
        stalled = _stalled(server)
        assert stalled["engine.wait"] == pytest.approx(ev["wall_s"], abs=1e-3)
        for e in run_log():        # whatever else was slow enough, once each
            assert stalled[e["stage"]] > 0.0
        assert sum(ln.startswith("host stage stood still: stage=engine.wait")
                   for ln in lines) == 1
    finally:
        server.stop()


@pytest.mark.parametrize("sample,held_after,stalls", [
    (1.0, None, 0),    # idle, then a request: open only as the take ends
    (0.0, 0.1, 0),     # tracing off: nothing says a request was held
    (1.0, 0.1, 1)])    # a handler held a request through the take's end
def test_a_long_take_is_a_stall_only_while_a_request_is_held(
        sample, held_after, stalls, run_log, monkeypatch):
    """``batch.take`` with no request open waited for traffic: an idle
    server, not a stall.  What counts of it is the end through which
    requests were open without a break."""
    from raft_tpu.telemetry import trace
    monkeypatch.setattr(trace, "STALL_SECONDS", 0.05)
    server = _server(StubEngine(), trace_sample=sample)
    try:
        assert server.tracer.held_s() == 0.0
        held = None
        if held_after is not None:
            time.sleep(held_after)              # the batcher sits in take
            held = server.tracer.start("pair")  # a handler reads a body ...
        time.sleep(0.15)
        im = np.zeros((32, 48, 3), np.float32)
        server.infer(im, im)                    # ... the next reaches the queue
        takes = [e for e in run_log() if e["stage"] == "batch.take"]
        assert len(takes) == stalls
        for ev in takes:
            assert ev["open_traces"] == 2 and ev["batch"] == 1
            assert ev["wall_s"] >= 0.25 and ev["cpu_s"] < 0.05
            assert 0.15 <= ev["held_s"] <= ev["wall_s"] - 0.09
            assert _stalled(server)["batch.take"] == pytest.approx(
                ev["held_s"], abs=1e-3)
        if not stalls:
            assert _stalled(server)["batch.take"] == 0.0
        if held is not None:
            held.finish()
        assert server.tracer.held_s() == 0.0
    finally:
        server.stop()


@pytest.mark.parametrize("clients,steps", [(1, (1, 2, 4)), (3, (1, 2, 4)),
                                           (3, (4,))])
def test_stage_seconds_and_device_rows_add_up(clients, steps):
    """The counters against the two records they must agree with: the
    stage seconds are the sum of the spans the same sites recorded, and the
    device rows are the batch-size histogram's."""
    gate = threading.Event()
    eng = DeviceStubEngine(gate=gate)
    server = _server(eng, max_wait_ms=100.0, batch_steps=steps)
    try:
        im = np.zeros((32, 48, 3), np.float32)
        ts = [threading.Thread(target=server.infer, args=(im, im))
              for _ in range(clients)]
        for t in ts:
            t.start()
        time.sleep(0.3)                  # the first is at the gate, the
        gate.set()                       # others queued behind it
        for t in ts:
            t.join(10)
        prom = _prom(server)
        recs = server.flightrec.snapshot()
        assert len(recs) == clients and all(r["status"] == "ok"
                                            for r in recs)
        # per device batch (the execute span id joins co-batched traces):
        # each stage's span once
        batches = {}
        for rec in recs:
            [ex] = [s for s in rec["spans"] if s["name"] == "execute"]
            batches.setdefault(ex["span"], []).append(rec)
        calls = prom["raft_serving_device_calls_total"]
        assert calls == len(batches) == eng.calls.__len__()
        for label, span in [("batch.pad", "pad"), ("engine.h2d", "execute_h2d"),
                            ("engine.dispatch", "execute_dispatch"),
                            ("engine.wait", "execute_block"),
                            ("engine.fetch", "execute_fetch")]:
            from_spans = sum(
                next(s["dur_ms"] for s in group[0]["spans"]
                     if s["name"] == span) for group in batches.values())
            key = f'raft_serving_stage_seconds_total{{stage="{label}"}}'
            assert prom[key] * 1e3 == pytest.approx(from_spans, abs=0.01), label
        # a request's admit is its own
        admit = sum(s["dur_ms"] for r in recs for s in r["spans"]
                    if s["name"] == "admit")
        assert prom['raft_serving_stage_seconds_total{stage="http.admit"}'] \
            * 1e3 == pytest.approx(admit, abs=0.01 * clients)
        # deliver: the stage is per batch, the span per request, so the
        # last row's span covers the stage
        for group in batches.values():
            assert max(s["dur_ms"] for r in group for s in r["spans"]
                       if s["name"] == "deliver") > 0
        # every stage's CPU seconds lie under its wall seconds
        wall = _stage_family(prom, "raft_serving_stage_seconds_total")
        cpu = _stage_family(prom, "raft_serving_stage_cpu_seconds_total")
        assert set(cpu) == set(wall)
        for label in wall:
            assert 0.0 <= cpu[label] <= wall[label] + CPU_SLACK_MS / 1e3, label
        # the stub's h2d, dispatch and wait sleep: their thread was not running
        assert cpu["engine.h2d"] < 0.5 * wall["engine.h2d"]
        real = prom['raft_serving_device_rows_total{kind="real"}']
        padded = prom['raft_serving_device_rows_total{kind="padded"}']
        assert real == clients == prom["raft_serving_batch_size_sum"]
        assert calls == prom["raft_serving_batch_size_count"]
        assert padded == sum(n for _, n in eng.calls)
        assert padded == sum(
            next(s["batch_padded"] for s in group[0]["spans"]
                 if s["name"] == "execute") for group in batches.values())
    finally:
        gate.set()
        server.stop()


def test_client_trace_id_adopted():
    server = _server(StubEngine())
    try:
        im = np.zeros((32, 48, 3), np.float32)
        req = server.infer(im, im, trace_id="FEEDFACE-01")
        assert req.trace.trace_id == "feedface-01"
        assert any(t["trace_id"] == "feedface-01"
                   for t in server.flightrec.snapshot())
    finally:
        server.stop()


def test_cobatched_requests_share_one_execute_span():
    gate = threading.Event()
    eng = StubEngine(gate=gate)
    server = _server(eng, max_wait_ms=200.0)
    try:
        im = np.zeros((32, 48, 3), np.float32)
        # occupy the engine so the next two coalesce into one batch
        warm = threading.Thread(target=server.infer, args=(im, im))
        warm.start()
        assert eng.entered.wait(10)
        done = []
        ts = [threading.Thread(target=lambda: done.append(
            server.infer(im, im))) for _ in range(2)]
        for t in ts:
            t.start()
        time.sleep(0.3)                     # both queued behind the gate
        gate.set()
        for t in ts:
            t.join(10)
        warm.join(10)
        recs = [r for r in server.flightrec.snapshot()
                if any(s.get("batch_real") == 2 for s in r["spans"])]
        assert len(recs) == 2
        exec_ids = set()
        for rec in recs:
            [ex] = [s for s in rec["spans"] if s["name"] == "execute"]
            assert ex["batch_real"] == 2
            exec_ids.add(ex["span"])
        assert len(exec_ids) == 1           # ONE device span, two traces
    finally:
        gate.set()
        server.stop()


def test_failure_paths_close_traces_with_the_right_status():
    """Poisoned (single-request bisection terminus), shed (breaker), and
    timeout (queue purge) each close their trace with the classification status
    — and no trace leaks open."""
    eng = StubEngine(fail=True)
    server = _server(eng, breaker_window=8, breaker_threshold=0.5,
                     breaker_min_volume=2, breaker_cooldown_s=30.0,
                     engine_retries=0)
    try:
        im = np.zeros((32, 48, 3), np.float32)
        for _ in range(2):
            with pytest.raises(PoisonedRequest) as ei:
                server.infer(im, im)
        assert ei.value.trace_id            # the 500 carries its trace id
        assert server.breaker.state == "open"
        with pytest.raises(BreakerOpen) as eb:
            server.infer(im, im)
        assert eb.value.trace_id
        statuses = [r["status"] for r in server.flightrec.snapshot()]
        assert statuses.count("poisoned") == 2
        assert statuses.count("shed") == 1
        assert server.tracer.open_traces == 0
    finally:
        server.stop()


def test_timeout_trace_closed_by_queue_purge():
    gate = threading.Event()
    eng = StubEngine(gate=gate)
    server = _server(eng, max_batch=1, batch_steps=(1,))
    try:
        im = np.zeros((32, 48, 3), np.float32)
        blocker = threading.Thread(target=server.infer, args=(im, im))
        blocker.start()
        assert eng.entered.wait(10)
        # release the engine shortly: the batcher's next take_batch pass
        # purges the expired request long before the handler's margin
        threading.Timer(0.3, gate.set).start()
        with pytest.raises(DeadlineExceeded):
            server.infer(im, im, deadline_ms=50.0)   # purged in queue
        blocker.join(10)
        timeouts = [r for r in server.flightrec.snapshot()
                    if r["status"] == "timeout"]
        assert len(timeouts) == 1
        names = [s["name"] for s in timeouts[0]["spans"]]
        assert "queue_wait" in names        # its life WAS queue wait
        assert "execute" not in names       # never reached the device
        assert server.tracer.open_traces == 0
    finally:
        gate.set()
        server.stop()


def test_batcher_crash_closes_trace_and_dumps_flightrec(tmp_path):
    path = tmp_path / "flightrec.jsonl"
    server = _server(StubEngine(), chaos="seed=1", degraded_window_s=0.2,
                     flightrec_path=str(path))
    try:
        server.faults.force("kill", [1])
        im = np.zeros((32, 48, 3), np.float32)
        with pytest.raises(BatcherCrashed):
            server.infer(im, im)
        assert server.tracer.open_traces == 0
        assert any(r["status"] == "error"
                   for r in server.flightrec.snapshot())
        # the crash auto-dumps an artifact — on the DYING batcher thread,
        # which races this (already-woken) one: poll briefly
        deadline = time.monotonic() + 5.0
        while not path.exists() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert path.exists()
        # the file exists from the moment the dump opens it: read it only
        # once that dump has let go of its lock (an empty file otherwise)
        with server.flightrec._dump_lock:
            recs = [json.loads(ln) for ln in path.read_text().splitlines()]
        assert recs[0]["event"] == "flightrec_dump"
        assert recs[0]["reason"] == "batcher_crash"
        assert any(r.get("event") == "trace" and r["status"] == "error"
                   for r in recs)
    finally:
        server.stop()


def test_bad_request_burns_no_budget_and_keeps_error_ring_clean():
    """A client's 400 closes its trace as ``bad_request``: the trace id
    still comes back on the exception (debuggable), but no SLO budget
    burns and the error ring stays reserved for real failures."""
    from raft_tpu.serving.http import BadRequest
    server = _server(StubEngine())
    try:
        big = np.zeros((256, 256, 3), np.float32)    # routes to no bucket
        with pytest.raises(BadRequest) as ei:
            server.infer(big, big)
        assert ei.value.trace_id                     # findable afterwards
        assert server.tracer.open_traces == 0
        _, err = server.flightrec.counts()
        assert err == 0                              # not incident evidence
        assert any(t["status"] == "bad_request"
                   for t in server.flightrec.snapshot())
        assert server.slo.burn_rate("pair") == 0.0   # no budget burned
    finally:
        server.stop()


def test_trace_sample_zero_is_off_everywhere():
    import urllib.error
    import urllib.request
    server = _server(StubEngine(), trace_sample=0.0)
    try:
        im = np.zeros((32, 48, 3), np.float32)
        req = server.infer(im, im)
        assert req.trace is None
        assert server.flightrec is None and server.slo is None
        text = server.registry.render()
        assert "raft_slo" not in text       # no tracing families at all
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(server.url + "/debug/traces")
        assert ei.value.code == 404
    finally:
        server.stop()


# ------------------------------------------------------------ tlm trace --

def _load_tlm():
    spec = importlib.util.spec_from_file_location(
        "tlm_under_test", os.path.join(os.path.dirname(__file__), "..",
                                       "tools", "tlm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _sample_trace_records():
    """Two realistic trace records via the real tracer."""
    fr = spans.FlightRecorder(capacity=8)
    tracer = spans.Tracer(sample=1.0, recorder=fr)
    for status in (None, spans.POISONED):
        tr = tracer.start("pair")
        t = tr.t0
        tr.span("admit", t, t + 0.001)
        tr.span("queue_wait", t + 0.001, t + 0.004)
        eid = tr.span("execute", t + 0.004, t + 0.020)
        tr.span("execute_dispatch", t + 0.004, t + 0.006, parent=eid)
        tr.span("execute_block", t + 0.006, t + 0.020, parent=eid)
        tr.span("respond", t + 0.020, t + 0.021)
        tr.finish(status)
    return fr.snapshot()


def test_tlm_trace_list_render_and_attribution(tmp_path):
    tlm = _load_tlm()
    recs = _sample_trace_records()
    log = tmp_path / "flightrec.jsonl"
    log.write_text("\n".join(json.dumps(r) for r in recs) + "\n")

    records = tlm.load_records(log)
    assert len(tlm.trace_records(records)) == 2
    listing = "\n".join(tlm.trace_list_lines(records))
    assert "2 trace(s)" in listing and "poisoned" in listing
    # non-ok traces list first
    assert listing.splitlines()[1].split()[1].startswith("[pair")

    rendered = "\n".join(tlm.render_trace(tlm.trace_records(records)[0]))
    for name in ("request", "admit", "queue_wait", "execute",
                 "execute_dispatch", "execute_block", "respond"):
        assert name in rendered, name
    assert "█" in rendered                  # the waterfall bars
    # children indent under their parent
    exec_line = next(ln for ln in rendered.splitlines()
                     if "execute_block" in ln)
    assert exec_line.lstrip().startswith("execute_block") is False \
        or "  execute_block" in rendered

    att = "\n".join(tlm.attribution_lines(records))
    assert "latency attribution over 2 trace(s)" in att
    assert "queue_wait" in att and "% of e2e" in att
    # summary integrates the table
    summary = "\n".join(tlm.summary_lines(log))
    assert "latency attribution" in summary

    # the CLI: list (exit 0), render by prefix, miss (exit 1)
    assert tlm.main(["trace", str(log)]) == 0
    tid = tlm.trace_records(records)[0]["trace_id"]
    assert tlm.main(["trace", str(log), tid[:8]]) == 0
    assert tlm.main(["trace", str(log), "zzzz"]) == 1


def test_tlm_joins_fleet_multi_hop_traces(tmp_path):
    """A fleet request leaves one trace record per hop — the router's
    route/forward view and the replica's admit/execute view, sharing the
    propagated trace id.  tlm must join them into ONE waterfall: replica
    spans offset onto the router's timeline (wall-clock aligned), the
    replica root re-rooted as `replica:request`, and the attribution
    table drawing from both hops without counting roots as buckets."""
    tlm = _load_tlm()
    tracer = spans.Tracer(sample=1.0)
    rtr = tracer.start("pair")
    t = rtr.t0
    time.sleep(0.005)                   # the forward leaves the router...
    rep = tracer.start("pair", rtr.trace_id)   # ...and lands on a replica
    tr0 = rep.t0
    rep.span("admit", tr0, tr0 + 0.001)
    rep.span("execute", tr0 + 0.001, tr0 + 0.010)
    rep_rec = rep.finish()
    rtr.span("route", t, t + 0.0005, replica=0)
    rtr.span("forward", t + 0.0005, t + 0.020, replica=0)
    rtr_rec = rtr.finish()

    (tmp_path / "events.jsonl").write_text(json.dumps(rtr_rec) + "\n")
    (tmp_path / "replica-0").mkdir()
    (tmp_path / "replica-0" / "events.jsonl").write_text(
        json.dumps(rep_rec) + "\n")

    records = tlm.load_records(tmp_path)    # fleet run dir layout
    traces = tlm.trace_records(records)
    assert len(traces) == 1                 # one request, joined
    joined = traces[0]
    assert joined["hops"] == 2
    names = [s["name"] for s in joined["spans"]]
    assert "route" in names and "forward" in names
    assert "admit" in names and "replica:request" in names
    rep_root = next(s for s in joined["spans"]
                    if s["name"] == "replica:request")
    assert rep_root["start_ms"] >= 3.0      # offset by the hop gap
    rendered = "\n".join(tlm.render_trace(joined))
    assert "forward" in rendered and "replica:request" in rendered

    att = "\n".join(tlm.attribution_lines(records))
    assert "forward" in att and "admit" in att
    assert "replica:request" not in att     # roots are covers, not buckets

    # identical duplicates (events.jsonl + flightrec) still collapse to
    # a single un-joined record
    dup = [rtr_rec, dict(rtr_rec)]
    only = tlm.trace_records(dup)
    assert len(only) == 1 and "hops" not in only[0]


def test_tlm_trace_reads_run_dir_with_flightrec(tmp_path):
    tlm = _load_tlm()
    recs = _sample_trace_records()
    (tmp_path / "flightrec.jsonl").write_text(
        "\n".join(json.dumps(r) for r in recs) + "\n")
    (tmp_path / "events.jsonl").write_text(
        json.dumps({"t": 0, "event": "manifest", "mode": "serve"}) + "\n")
    records = tlm.load_records(tmp_path)    # dir: events + flightrec merge
    assert len(tlm.trace_records(records)) == 2
    assert tlm.manifest_of(records) is not None
