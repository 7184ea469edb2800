"""Serving-stack tests (tier-1, CPU): batching policy on a stub engine
(deterministic — the engine blocks on events, no timing races), the live
warm-engine + HTTP surface on a tiny model, and the backpressure/deadline/
drain contracts the ISSUE acceptance criteria name.

The stub-engine tests never compile anything; the live-server fixture is
module-scoped so its warmup grid (2 buckets x 1 batch step) compiles once.
"""

import io
import json
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from raft_tpu.serving import (DeadlineExceeded, Draining, FlowServer,
                              MicroBatcher, QueueFull, Registry, Request,
                              RequestQueue, ServeConfig, default_batch_steps,
                              parse_buckets)
from raft_tpu.serving.metrics import Counter, Gauge, Histogram


# ---------------------------------------------------------------- config --

def test_parse_buckets():
    assert parse_buckets("432x1024") == ((432, 1024),)
    assert parse_buckets("32x48, 64x96") == ((32, 48), (64, 96))
    with pytest.raises(ValueError):
        parse_buckets("33x48")          # not /8
    with pytest.raises(ValueError):
        parse_buckets("nonsense")
    with pytest.raises(ValueError):
        parse_buckets("")


def test_default_batch_steps():
    assert default_batch_steps(1) == (1,)
    assert default_batch_steps(4) == (1, 2, 4)
    assert default_batch_steps(6) == (1, 2, 4, 6)


def test_route_smallest_fitting_bucket():
    sc = ServeConfig(buckets=((64, 96), (32, 48), (128, 128)), max_batch=2)
    assert sc.route(30, 44) == (32, 48)       # smallest fit wins
    assert sc.route(32, 48) == (32, 48)       # exact fit
    assert sc.route(33, 48) == (64, 96)
    assert sc.route(100, 100) == (128, 128)
    assert sc.route(200, 48) is None          # taller than every bucket


def test_serve_config_validation():
    with pytest.raises(ValueError):
        ServeConfig(buckets=((30, 48),))           # not /8
    with pytest.raises(ValueError):
        ServeConfig(buckets=())
    with pytest.raises(ValueError):
        ServeConfig(max_batch=4, batch_steps=(1, 2))   # can't fit a full batch
    sc = ServeConfig(max_batch=4, dp_devices=2, batch_steps=(1, 2, 4))
    assert sc.batch_steps == (2, 4)           # rounded up to multiples, dedup
    sc = ServeConfig(max_batch=4, dp_devices=3, batch_steps=(1, 2, 4))
    assert sc.batch_steps == (3, 6)           # every step divisible by N
    assert ServeConfig(max_batch=3).pad_batch_to(2) == 2
    assert ServeConfig(max_batch=3).pad_batch_to(3) == 3


# --------------------------------------------------------------- metrics --

def test_metrics_exposition_format():
    reg = Registry()
    c = reg.counter("t_requests_total", "requests", labelnames=("status",))
    c.labels("ok").inc()
    c.labels("ok").inc(2)
    c.labels("shed").inc()
    g = reg.gauge("t_depth", "depth")
    g.set(7)
    h = reg.histogram("t_lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.render()
    assert '# TYPE t_requests_total counter' in text
    assert 't_requests_total{status="ok"} 3' in text
    assert 't_requests_total{status="shed"} 1' in text
    assert 't_depth 7' in text
    assert 't_lat_seconds_bucket{le="0.1"} 1' in text
    assert 't_lat_seconds_bucket{le="1"} 2' in text
    assert 't_lat_seconds_bucket{le="+Inf"} 3' in text
    assert 't_lat_seconds_count 3' in text
    assert abs(h.mean() - (0.05 + 0.5 + 5.0) / 3) < 1e-9
    with pytest.raises(ValueError):
        reg.counter("t_depth", "dup name")
    with pytest.raises(ValueError):
        Counter("c", "x").inc(-1)
    cb = Gauge("g", "callback", fn=lambda: 42)
    assert cb.value == 42


# ------------------------------------------------- batching policy (stub) --

BUCKET = (32, 48)


def make_request(deadline_s=30.0, bucket=BUCKET):
    h, w = bucket
    im = np.zeros((1, h, w, 3), np.float32)
    return Request(im, im, bucket, (0, 0, 0, 0),
                   deadline=time.monotonic() + deadline_s)


class StubEngine:
    """Counts calls; optionally blocks each call on a gate event."""

    def __init__(self, gate=None, fail=False):
        self.calls = []               # (bucket, batch_size)
        self.gate = gate
        self.fail = fail
        self.entered = threading.Event()

    def run(self, bucket, im1, im2):
        self.entered.set()
        if self.gate is not None:
            assert self.gate.wait(30)
        self.calls.append((bucket, im1.shape[0]))
        if self.fail:
            raise RuntimeError("engine exploded")
        return np.zeros(im1.shape[:3] + (2,), np.float32)


def make_stub_stack(engine, max_batch=4, max_wait_ms=30.0, depth=16,
                    batch_steps=None):
    q = RequestQueue(depth)
    steps = batch_steps or default_batch_steps(max_batch)
    pad = lambda n: next(s for s in steps if s >= n)
    b = MicroBatcher(q, engine.run, pad, max_batch, max_wait_ms)
    b.start()
    return q, b


def test_batcher_coalesces_full_batch():
    """4 requests arriving within max_wait -> ONE device call of 4 (the
    full-batch pop fires on the 4th submission, not on aging)."""
    eng = StubEngine()
    q, b = make_stub_stack(eng, max_batch=4, max_wait_ms=10_000.0)
    reqs = [make_request() for _ in range(4)]
    t0 = time.monotonic()
    for r in reqs:
        q.submit(r)
    flows = [r.wait(timeout=10) for r in reqs]
    assert eng.calls == [(BUCKET, 4)]           # coalesced, one call
    assert time.monotonic() - t0 < 5            # did NOT age out max_wait
    assert all(f.shape == (32, 48, 2) for f in flows)
    assert all(r.batch_real == 4 and r.batch_padded == 4 for r in reqs)
    q.close()
    b.join(5)


def test_max_wait_partial_flush_pads_to_step():
    """A lone request flushes after max_wait, padded up to the next declared
    batch step (occupancy 1/2)."""
    eng = StubEngine()
    q, b = make_stub_stack(eng, max_batch=4, max_wait_ms=20.0,
                           batch_steps=(2, 4))
    r = make_request()
    t0 = time.monotonic()
    q.submit(r)
    r.wait(timeout=10)
    assert time.monotonic() - t0 >= 0.015       # really waited for mates
    assert eng.calls == [(BUCKET, 2)]           # padded 1 -> step 2
    assert (r.batch_real, r.batch_padded) == (1, 2)
    q.close()
    b.join(5)


def test_bucket_fifo_no_cross_bucket_mixing():
    """Same-bucket requests coalesce; a different bucket rides a separate
    batch — shapes never mix inside one device call."""
    gate = threading.Event()
    eng = StubEngine(gate=gate)
    q, b = make_stub_stack(eng, max_batch=4, max_wait_ms=15.0)
    warm = make_request()
    q.submit(warm)
    assert eng.entered.wait(10)
    small = [make_request() for _ in range(2)]
    big = [make_request(bucket=(64, 96)) for _ in range(2)]
    for r in (small[0], big[0], small[1], big[1]):   # interleaved arrival
        q.submit(r)
    gate.set()
    for r in small + big + [warm]:
        r.wait(timeout=10)
    assert sorted(eng.calls[1:]) == [((32, 48), 2), ((64, 96), 2)]
    q.close()
    b.join(5)


def test_deadline_timeout_while_queued():
    """A request whose deadline passes in the queue gets DeadlineExceeded
    and never reaches the device."""
    gate = threading.Event()
    eng = StubEngine(gate=gate)
    q, b = make_stub_stack(eng, max_batch=2, max_wait_ms=5.0)
    first = make_request()
    q.submit(first)                    # engine blocks on the gate
    assert eng.entered.wait(10)
    doomed = make_request(deadline_s=0.05)
    q.submit(doomed)
    time.sleep(0.15)                   # deadline passes while queued
    gate.set()
    first.wait(timeout=10)
    with pytest.raises(DeadlineExceeded):
        doomed.wait(timeout=10)
    assert all(n == 1 for _, n in eng.calls)    # doomed never executed
    assert b.timed_out == 1
    q.close()
    b.join(5)


def test_overload_sheds_with_queue_full():
    """Submissions past queue_depth raise QueueFull immediately — bounded
    memory, 429 at the HTTP layer — and queued work still completes."""
    gate = threading.Event()
    eng = StubEngine(gate=gate)
    q, b = make_stub_stack(eng, max_batch=1, max_wait_ms=5.0, depth=2)
    inflight = make_request()
    q.submit(inflight)
    assert eng.entered.wait(10)        # engine busy; queue now empty
    queued = [make_request() for _ in range(2)]
    for r in queued:
        q.submit(r)                    # fills the depth-2 queue
    with pytest.raises(QueueFull):
        q.submit(make_request())
    gate.set()
    inflight.wait(timeout=10)
    for r in queued:
        r.wait(timeout=10)
    q.close()
    b.join(5)


def test_graceful_drain_completes_queued_work():
    """close() lets the batcher flush everything already admitted — without
    waiting out max_wait — then exit; later submissions are refused."""
    eng = StubEngine()
    q, b = make_stub_stack(eng, max_batch=4, max_wait_ms=10_000.0)
    reqs = [make_request() for _ in range(3)]
    for r in reqs:
        q.submit(r)                    # 3 < max_batch: would age 10s
    q.close()                          # drain: flush immediately instead
    with pytest.raises(Draining):
        q.submit(make_request())
    t0 = time.monotonic()
    for r in reqs:
        assert r.wait(timeout=10).shape == (32, 48, 2)
    assert time.monotonic() - t0 < 5   # drained, did not age out max_wait
    assert eng.calls == [(BUCKET, 4)]  # one partial batch, padded 3 -> 4
    assert all(r.batch_real == 3 and r.batch_padded == 4 for r in reqs)
    b.join(10)
    assert not b.alive                 # batcher exited after the drain
    assert b.served == 3


def test_engine_failure_fails_the_batch_not_the_server():
    eng = StubEngine(fail=True)
    q, b = make_stub_stack(eng, max_batch=2, max_wait_ms=5.0)
    r = make_request()
    q.submit(r)
    with pytest.raises(RuntimeError, match="engine exploded"):
        r.wait(timeout=10)
    # batcher survives and serves the next request
    eng.fail = False
    r2 = make_request()
    q.submit(r2)
    assert r2.wait(timeout=10).shape == (32, 48, 2)
    q.close()
    b.join(5)


# ------------------------------------------- live server (warm engine) ----

@pytest.fixture(scope="module")
def live_server():
    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models import init_raft

    config = RAFTConfig.small_model(iters=1)
    params = init_raft(init_rng(), config)
    # max_wait 150ms: wide enough that two concurrent posts always coalesce,
    # short enough that lone-request tests stay fast.  max_sessions=0:
    # this fixture pins the PAIRWISE warmup grid exactly (the streaming
    # fixture below has its own server)
    sconfig = ServeConfig(buckets=((32, 48), (64, 96)), max_batch=2,
                          batch_steps=(2,), max_wait_ms=150.0,
                          queue_depth=16, default_deadline_ms=30_000.0,
                          port=0, max_sessions=0)
    server = FlowServer(config, params, sconfig)
    server.start()
    yield server, config, params
    server.stop()


def _post_json(server, im1, im2, deadline_ms=None):
    payload = {"image1": im1.tolist(), "image2": im2.tolist()}
    if deadline_ms is not None:
        payload["deadline_ms"] = deadline_ms
    req = urllib.request.Request(
        server.url + "/v1/flow", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_live_warmup_compiled_one_executable_per_bucket(live_server):
    server, _, _ = live_server
    eng = server.engine
    # 2 buckets x 1 batch step: exactly one warm executable per bucket;
    # the kind + iters policy ride in the cache key (an executable can
    # never be reused under a different compute policy than it was warmed
    # with, and stream/encode executables never collide with pairwise)
    assert eng.executables == 2
    assert eng.keys() == [("pair", 32, 48, 2, "fixed"),
                          ("pair", 64, 96, 2, "fixed")]
    assert eng.compile_misses == 0


def test_live_http_flow_matches_direct_inference(live_server):
    """The full HTTP -> queue -> batcher -> warm engine -> unpad path must
    agree with a direct jitted call on the same padded input."""
    import jax
    from raft_tpu.data.pipeline import pad_to_shape, unpad
    from raft_tpu.models.raft import make_inference_fn

    server, config, params = live_server
    rng = np.random.RandomState(3)
    im1 = rng.rand(30, 44, 3).astype(np.float32)       # pads to 32x48
    im2 = rng.rand(30, 44, 3).astype(np.float32)
    resp = _post_json(server, im1, im2)
    flow = np.asarray(resp["flow"], np.float32)
    assert flow.shape == (30, 44, 2)
    assert resp["meta"]["bucket"] == [32, 48]

    fn = jax.jit(make_inference_fn(config, iters=1))
    im1p, pads = pad_to_shape(im1[None], (32, 48))
    im2p, _ = pad_to_shape(im2[None], (32, 48))
    want = unpad(np.asarray(fn(params, im1p, im2p)), pads)[0]
    np.testing.assert_allclose(flow, want, atol=1e-4, rtol=1e-4)


def test_live_concurrent_requests_coalesce_and_reuse_cache(live_server):
    """Two concurrent posts ride ONE device batch (occupancy 2/2), routed
    to the small bucket, with zero compile misses — the no-recompile-storm
    guarantee, asserted via the engine's own trace counters."""
    server, _, _ = live_server
    eng = server.engine
    misses_before = eng.compile_misses
    hits_before = eng.compile_hits
    rng = np.random.RandomState(4)
    ims = [rng.rand(32, 48, 3).astype(np.float32) for _ in range(4)]
    with ThreadPoolExecutor(max_workers=2) as pool:
        futs = [pool.submit(_post_json, server, ims[2 * i], ims[2 * i + 1])
                for i in range(2)]
        resps = [f.result() for f in futs]
    assert all(r["meta"]["bucket"] == [32, 48] for r in resps)
    # batch occupancy > 1: both requests shared one padded-2 device call
    assert all(r["meta"]["batch_padded"] == 2 for r in resps)
    assert any(r["meta"]["batch_real"] == 2 for r in resps)
    assert eng.compile_misses == misses_before       # nothing recompiled
    assert eng.compile_hits > hits_before


def test_live_bucket_routing_second_bucket(live_server):
    server, _, _ = live_server
    rng = np.random.RandomState(5)
    im = rng.rand(50, 60, 3).astype(np.float32)       # only 64x96 fits
    resp = _post_json(server, im, im)
    assert resp["meta"]["bucket"] == [64, 96]
    assert np.asarray(resp["flow"]).shape == (50, 60, 2)
    assert server.engine.compile_misses == 0


def test_live_npz_round_trip(live_server):
    server, _, _ = live_server
    rng = np.random.RandomState(6)
    im = rng.rand(32, 48, 3).astype(np.float32)
    buf = io.BytesIO()
    np.savez(buf, image1=im, image2=im)
    req = urllib.request.Request(
        server.url + "/v1/flow", data=buf.getvalue(),
        headers={"Content-Type": "application/octet-stream",
                 "Accept": "application/octet-stream"})
    with urllib.request.urlopen(req) as r:
        assert r.status == 200
        with np.load(io.BytesIO(r.read())) as z:
            assert z["flow"].shape == (32, 48, 2)
            assert np.isfinite(z["flow"]).all()


def test_live_http_error_statuses(live_server):
    server, _, _ = live_server

    def post_raw(body, ct="application/json"):
        req = urllib.request.Request(server.url + "/v1/flow", data=body,
                                     headers={"Content-Type": ct})
        try:
            with urllib.request.urlopen(req) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    st, body = post_raw(b"not json")
    assert st == 400 and "JSON" in body["error"]
    st, body = post_raw(json.dumps({"image1": [[[0.0] * 3]]}).encode())
    assert st == 400 and "image2" in body["error"]
    # shape mismatch between the pair
    im_a = np.zeros((8, 8, 3)).tolist()
    im_b = np.zeros((8, 16, 3)).tolist()
    st, body = post_raw(json.dumps(
        {"image1": im_a, "image2": im_b}).encode())
    assert st == 400 and "differ" in body["error"]
    # larger than every declared bucket -> unroutable
    big = np.zeros((72, 104, 3)).tolist()
    st, body = post_raw(json.dumps(
        {"image1": big, "image2": big}).encode())
    assert st == 400 and "bucket" in body["error"]
    # unknown path
    try:
        with urllib.request.urlopen(server.url + "/nope") as r:
            st = r.status
    except urllib.error.HTTPError as e:
        st = e.code
    assert st == 404


def test_live_engine_is_driven_through_its_phases(live_server, monkeypatch):
    """The server pipelines the real engine through place .. fetch, whose
    composition ``run`` is (the same flow either way, no compile); an
    engine whose ``run`` was replaced is called through that ``run``."""
    from raft_tpu.serving.batcher import _BlockingCall
    from raft_tpu.serving.engine import InferenceEngine
    server, _, _ = live_server
    eng = server.batcher.engine
    assert not isinstance(eng, _BlockingCall)
    assert eng.ready == server.engine.ready
    rng = np.random.RandomState(3)
    im1, im2 = (rng.rand(2, 32, 48, 3).astype(np.float32) for _ in "12")
    misses, calls = server.engine.compile_misses, server.engine.pair_calls
    whole = server.engine.run((32, 48), im1, im2)
    call = eng.place((32, 48), im1.copy(), im2.copy())
    eng.dispatch(call)
    eng.wait(call)
    assert eng.ready(call)
    np.testing.assert_array_equal(eng.fetch(call), whole)
    assert server.engine.pair_calls == calls + 2
    assert server.engine.compile_misses == misses
    sound = InferenceEngine.run
    monkeypatch.setattr(InferenceEngine, "run",
                        lambda self, *a, **kw: sound(self, *a, **kw) + 0.25)
    replaced = server._pair_engine()
    assert replaced == server._run_engine
    np.testing.assert_array_equal(replaced((32, 48), im1, im2), whole + 0.25)


def test_live_healthz_and_metrics(live_server):
    server, _, _ = live_server
    with urllib.request.urlopen(server.url + "/healthz") as r:
        assert r.status == 200
        h = json.loads(r.read())
    assert h["status"] == "ok"
    assert h["buckets"] == [[32, 48], [64, 96]]
    assert h["executables"] == 2
    assert h["batcher"]["alive"] is True and h["batcher"]["restarts"] == 0
    assert h["breaker"]["state"] == "closed"
    with urllib.request.urlopen(server.url + "/metrics") as r:
        assert r.status == 200
        assert "text/plain" in r.headers["Content-Type"]
        text = r.read().decode()
    # non-trivial exposition: the families SERVING.md documents are live
    for name in ("raft_serving_requests_total",
                 "raft_serving_queue_depth",
                 "raft_serving_batch_occupancy_bucket",
                 "raft_serving_request_latency_seconds_bucket",
                 "raft_serving_compile_cache_misses_total",
                 "raft_serving_compile_cache_entries",
                 "raft_serving_queue_limit",
                 "raft_nonfinite_outputs_total",
                 "raft_batcher_restarts_total",
                 "raft_breaker_state"):
        assert name in text, name
    # chaos families absent on an un-drilled server
    assert "raft_fault_injected_total" not in text
    assert 'raft_serving_requests_total{status="ok"}' in text
    assert "raft_serving_compile_cache_misses_total 0" in text


@pytest.mark.parametrize("kw,terms", [
    (dict(corr_impl="pallas", compute_dtype="bfloat16"), (1, 3, 3, 3)),
    (dict(corr_impl="pallas", compute_dtype="float32"), (6, 6, 6, 6)),
    (dict(corr_impl="pallas", compute_dtype="bfloat16",
          corr_precision="default"), (1, 1, 1, 1)),
    (dict(corr_impl="dense", compute_dtype="bfloat16"), None),
])
def test_engine_reports_corr_mxu_terms(kw, terms):
    """The counter that says the kernel's exact-terms form engaged: per
    pyramid level, the MXU passes of the correlation matmul in the engine's
    executables (None off the Pallas kernel) — in the per-executable
    warm-up log line and as ``raft_serving_corr_mxu_terms{level=}``."""
    import logging

    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models import init_raft
    from raft_tpu.serving.engine import InferenceEngine

    config = RAFTConfig.small_model(iters=1, **kw)
    params = init_raft(init_rng(), config)
    sconfig = ServeConfig(buckets=((32, 48),), max_batch=1, port=0,
                          max_sessions=0)
    engine = InferenceEngine(config, params, sconfig)
    assert engine.corr_mxu_terms == terms

    lines = []
    handler = logging.Handler()
    handler.emit = lambda rec: lines.append(rec.getMessage())
    logging.getLogger("raft.serve").addHandler(handler)
    try:
        server = FlowServer(None, None, sconfig, engine=engine,
                            verbose=True)
        server.start()
    finally:
        logging.getLogger("raft.serve").removeHandler(handler)
    try:
        with urllib.request.urlopen(server.url + "/metrics") as r:
            text = r.read().decode()
    finally:
        server.stop()
    warmed = [ln for ln in lines if ln.startswith("warmed pair bucket")]
    assert len(warmed) == 1, lines
    if terms is None:
        assert "corr terms" not in warmed[0]
        assert "raft_serving_corr_mxu_terms" not in text
    else:
        want = "/".join(map(str, terms))
        assert f"batch 1 corr terms {want} (" in warmed[0], warmed
        for level, n in enumerate(terms):
            assert (f'raft_serving_corr_mxu_terms{{level="{level}"}} {n}'
                    in text), text


def test_engine_start_does_not_import_pallas():
    """A server that loads its executables from the AOT cache traces
    nothing, so it must not pay the Pallas import (1.0-1.2 s of the
    benchmark's ``setup_s`` on the chip's host) just to report the
    kernel's pass counts: they come from ops/corr.py."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from raft_tpu.config import RAFTConfig, init_rng\n"
        "from raft_tpu.models import init_raft\n"
        "from raft_tpu.serving import ServeConfig\n"
        "from raft_tpu.serving.engine import InferenceEngine\n"
        "config = RAFTConfig.small_model(iters=1, corr_impl='pallas',\n"
        "                                compute_dtype='bfloat16')\n"
        "engine = InferenceEngine(config, init_raft(init_rng(), config),\n"
        "                         ServeConfig(buckets=((32, 48),),\n"
        "                                     max_batch=1, max_sessions=0))\n"
        "assert engine.corr_mxu_terms == (1, 3, 3, 3)\n"
        "assert 'jax.experimental.pallas' not in sys.modules\n"
        "assert 'raft_tpu.ops.corr_pallas' not in sys.modules\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]


def test_http_engine_failure_returns_500_not_dropped_socket():
    """A persistent engine exception must surface as HTTP 500 JSON — a
    lone request is its own bisection terminus, so it is counted as
    status=poisoned — not a reset connection; and the queue-depth gauge
    is a live callback, not a stale snapshot."""
    eng = StubEngine(fail=True)
    sconfig = ServeConfig(buckets=((32, 48),), max_batch=2,
                          max_wait_ms=5.0, queue_depth=4, port=0)
    server = FlowServer(None, None, sconfig, engine=eng)
    server.start()
    try:
        im = np.zeros((32, 48, 3)).tolist()
        req = urllib.request.Request(
            server.url + "/v1/flow",
            data=json.dumps({"image1": im, "image2": im}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 500
        assert "engine exploded" in json.loads(ei.value.read())["error"]
        with urllib.request.urlopen(server.url + "/metrics") as r:
            text = r.read().decode()
        assert 'raft_serving_requests_total{status="poisoned"} 1' in text
        assert "raft_serving_queue_depth 0" in text   # live callback gauge
    finally:
        server.stop()


# ------------------------------------------- adaptive-compute (converge) --

class CountingStubEngine(StubEngine):
    """Converge-policy engine shape: returns (flows, per-row iters_used)."""

    iters_policy = "converge:1e-2"

    def run(self, bucket, im1, im2):
        flows = super().run(bucket, im1, im2)
        n = im1.shape[0]
        # per-row counts 3, 4, 5, ... — distinct so slicing bugs show
        return flows, np.arange(3, 3 + n, dtype=np.int32)


def test_batcher_passes_iters_used_through():
    """A (flows, iters_used) engine return lands per-REQUEST counts on the
    request objects and in the raft_iters_used histogram — padding rows
    are never observed."""
    from raft_tpu.serving.metrics import make_serving_metrics

    eng = CountingStubEngine()
    q = RequestQueue(16)
    reg = Registry()
    sc = ServeConfig(buckets=(BUCKET,), max_batch=4, batch_steps=(4,),
                     max_wait_ms=20.0)
    metrics = make_serving_metrics(reg, sc)
    b = MicroBatcher(q, eng.run, sc.pad_batch_to, 4, 20.0, metrics=metrics)
    b.start()
    reqs = [make_request() for _ in range(3)]      # 3 real rows, padded to 4
    for r in reqs:
        q.submit(r)
    for r in reqs:
        r.wait(timeout=10)
    assert [r.iters_used for r in reqs] == [3, 4, 5]
    hist = reg.get("raft_iters_used")
    assert hist.count == 3                          # padding row NOT counted
    assert hist.sum == 3 + 4 + 5
    # the mean gauge is live (sum/count of the histogram)
    assert abs(reg.get("raft_iters_mean").value - 4.0) < 1e-9
    q.close()
    b.join(5)


def test_plain_engine_leaves_iters_used_unset():
    eng = StubEngine()
    q, b = make_stub_stack(eng, max_batch=2, max_wait_ms=5.0)
    r = make_request()
    q.submit(r)
    r.wait(timeout=10)
    assert r.iters_used is None
    q.close()
    b.join(5)


def test_serve_config_iters_policy_validated():
    with pytest.raises(ValueError, match="iters_policy"):
        ServeConfig(iters_policy="convrge:1e-2")
    sc = ServeConfig(iters_policy="converge:1e-2:3")
    assert sc.iters_policy == "converge:1e-2:3"


def test_live_converge_policy_end_to_end():
    """A live server under --iters-policy converge:*: warmup pins the
    policy-keyed executables, a request reports its iterations in the
    response meta and the raft_iters_used/raft_iters_mean families, and
    nothing recompiles."""
    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models import init_raft

    config = RAFTConfig.small_model(iters=3)
    params = init_raft(init_rng(), config)
    # eps=1e9: every sample converges right after min_iters=2 — the
    # deterministic early exit (random weights never reach a small eps)
    sconfig = ServeConfig(buckets=((32, 48),), max_batch=1,
                          batch_steps=(1,), max_wait_ms=5.0, queue_depth=8,
                          port=0, iters_policy="converge:1e9:2",
                          max_sessions=0)
    server = FlowServer(config, params, sconfig)
    server.start()
    try:
        assert server.engine.keys() == [("pair", 32, 48, 1,
                                         "converge:1e9:2")]
        rng = np.random.RandomState(7)
        im = rng.rand(32, 48, 3).astype(np.float32)
        resp = _post_json(server, im, im)
        assert resp["meta"]["iters_used"] == 2          # exited at min_iters
        with urllib.request.urlopen(server.url + "/healthz") as r:
            assert json.loads(r.read())["iters_policy"] == "converge:1e9:2"
        with urllib.request.urlopen(server.url + "/metrics") as r:
            text = r.read().decode()
        assert "raft_iters_used_count 1" in text
        assert "raft_iters_mean 2" in text
        assert server.engine.compile_misses == 0
    finally:
        server.stop()


# ----------------------------------- the two-deep pipeline (fake phases) --

class PhasedEngine:
    """A recording fake of the engine's phases of a pair call (place,
    dispatch, ready, wait, fetch: serving/engine.py), no device: a call
    "runs" from its dispatch for ``run_s`` seconds, or, if its index (the
    order of the places) is in ``hold``, until the test ``finish``es it.
    ``log`` is the (phase, call index, time) record the order assertions
    read.  A row whose first pixel is >= 1.0 fails every call it is in
    (at ``wait``, where a device error surfaces)."""

    def __init__(self, run_s=0.0, hold=()):
        self.run_s, self.hold = run_s, set(hold)
        self.calls = []                   # (bucket, rows) per place
        self.issued = []
        self.log = []
        self.cv = threading.Condition()

    def _note(self, phase, i):
        with self.cv:
            self.log.append((phase, i, time.monotonic()))
            self.cv.notify_all()

    def saw(self, phase, i, timeout=10.0):
        """Block until ``phase`` of call ``i`` has ended; its time."""
        with self.cv:
            assert self.cv.wait_for(lambda: any(
                e[:2] == (phase, i) for e in self.log), timeout), (phase, i)
            return next(e[2] for e in self.log if e[:2] == (phase, i))

    def has(self, phase, i):
        with self.cv:
            return any(e[:2] == (phase, i) for e in self.log)

    def place(self, bucket, im1, im2, sizes=None):
        import types
        call = types.SimpleNamespace(
            i=len(self.calls), shape=im1.shape, done=threading.Event(),
            poisoned=bool((im1[:, 0, 0, 0] >= 1.0).any()))
        self.calls.append((bucket, im1.shape[0]))
        self.issued.append(call)
        self._note("h2d", call.i)
        return call

    def dispatch(self, call):
        if call.i not in self.hold:
            if self.run_s:
                threading.Timer(self.run_s, call.done.set).start()
            else:
                call.done.set()
        self._note("dispatch", call.i)

    def ready(self, call):
        return call.done.is_set()

    def wait(self, call):
        assert call.done.wait(30)
        self._note("wait", call.i)
        if call.poisoned:
            raise RuntimeError("device rejected the poisoned row")

    def fetch(self, call):
        self._note("fetch", call.i)
        return np.zeros(call.shape[:3] + (2,), np.float32)

    def finish(self, i):
        self.issued[i].done.set()


def make_phased_stack(eng, max_batch=2, max_wait_ms=5.0, **kw):
    from raft_tpu.serving.metrics import make_serving_metrics
    q = RequestQueue(64)
    reg = Registry()
    sc = ServeConfig(buckets=(BUCKET,), max_batch=max_batch,
                     max_wait_ms=max_wait_ms)
    b = MicroBatcher(q, eng, sc.pad_batch_to, max_batch, max_wait_ms,
                     metrics=make_serving_metrics(reg, sc), **kw)
    b.start()
    return q, b, reg


def _staged(reg, when):
    return reg.get("raft_serving_batches_staged_total").labels(when).value


def _submit(q, n):
    reqs = [make_request() for _ in range(n)]
    for r in reqs:
        q.submit(r)
    return reqs


def _pipeline_stages_ahead():
    """With n running: h2d of n+1 ends before wait of n returns, dispatch
    of n+1 precedes fetch and deliver of n, and with nothing staged behind
    it n+1 is delivered the moment it is ready, without a take."""
    eng = PhasedEngine(hold=(0, 1))
    q, b, reg = make_phased_stack(eng, max_batch=2, max_wait_ms=10_000.0)
    first = _submit(q, 2)
    eng.saw("dispatch", 0)
    second = _submit(q, 2)
    t_h2d1 = eng.saw("h2d", 1)              # placed while call 0 "runs"
    assert not eng.has("wait", 0) and not any(r.done for r in first)
    eng.finish(0)
    for r in first:
        r.wait(timeout=10)
    order = [e[:2] for e in eng.log]
    assert order == [("h2d", 0), ("dispatch", 0), ("h2d", 1), ("wait", 0),
                     ("dispatch", 1), ("fetch", 0)]
    assert t_h2d1 < eng.saw("wait", 0) <= eng.saw("dispatch", 1) \
        <= min(r.finished_at for r in first)
    # nothing is queued and max_wait is 10 s: only "the running batch is
    # ready" can end the take that is waiting now
    assert not any(r.done for r in second)
    eng.finish(1)
    for r in second:
        assert r.wait(timeout=5).shape == (32, 48, 2)
    assert (_staged(reg, "late"), _staged(reg, "ahead")) == (1, 1)
    assert eng.calls == [(BUCKET, 2), (BUCKET, 2)]
    return q, b


def _pipeline_part_batch_waits_for_its_mates():
    """A take during a run does not return a part batch before the run is
    ready, and returns at once when the bucket fills."""
    eng = PhasedEngine(hold=(0,))
    q, b, reg = make_phased_stack(eng, max_batch=4, max_wait_ms=5.0)
    first = _submit(q, 4)
    eng.saw("dispatch", 0)
    part = _submit(q, 1)
    time.sleep(0.1)                         # twenty times max_wait
    assert not eng.has("h2d", 1) and len(q) == 1
    t0 = time.monotonic()
    part += _submit(q, 3)                   # the bucket fills
    assert eng.saw("h2d", 1) - t0 < 0.5 and not eng.has("wait", 0)
    eng.finish(0)
    for r in first + part:
        r.wait(timeout=10)
    assert eng.calls == [(BUCKET, 4), (BUCKET, 4)]
    assert all(r.batch_real == 4 for r in part)
    return q, b


def _pipeline_ready_run_releases_the_part_batch():
    """When the running batch comes ready before the bucket fills it is
    delivered at once, and the part batch goes under the idle rule."""
    eng = PhasedEngine(hold=(0,))
    q, b, reg = make_phased_stack(eng, max_batch=4, max_wait_ms=5.0)
    first = _submit(q, 4)
    eng.saw("dispatch", 0)
    part = _submit(q, 1)
    time.sleep(0.05)
    eng.finish(0)
    for r in first:
        r.wait(timeout=5)
    assert part[0].wait(timeout=5).shape == (32, 48, 2)
    assert first[0].finished_at < eng.saw("h2d", 1)    # not held for it
    assert (part[0].batch_real, part[0].batch_padded) == (1, 1)
    assert (_staged(reg, "late"), _staged(reg, "ahead")) == (2, 0)
    return q, b


def _pipeline_idle_request_pays_nothing():
    """On an idle batcher one request is answered within max_wait + the
    run: the pipeline adds no latency where there is nothing to overlap."""
    eng = PhasedEngine(run_s=0.1)
    q, b, reg = make_phased_stack(eng, max_batch=4, max_wait_ms=50.0)
    for _ in range(3):
        t0 = time.monotonic()
        [r] = _submit(q, 1)
        r.wait(timeout=5)
        took = r.finished_at - t0
        assert 0.05 + 0.1 <= took < 0.05 + 0.1 + 0.1, took
    assert (_staged(reg, "late"), _staged(reg, "ahead")) == (3, 0)
    return q, b


def _pipeline_every_client_in_one_batch():
    """32 closed-loop clients against max_batch 32: there is never a second
    batch to stage, and the loop makes progress all the same."""
    eng = PhasedEngine(run_s=0.01)
    q, b, reg = make_phased_stack(eng, max_batch=32, max_wait_ms=5.0)

    def client(_):
        for _ in range(4):
            [r] = _submit(q, 1)
            r.wait(timeout=10)
        return True

    with ThreadPoolExecutor(32) as pool:
        assert all(pool.map(client, range(32)))
    assert b.served == 128 and sum(n for _, n in eng.calls) >= 128
    return q, b


def _pipeline_places_channel_planar_views():
    """What the engine is handed reads as the [n, H, W, 3] batch (the rows
    in order, the last one again up to the batch step) and lies in memory
    as [n, 3, H, W]: the chip's layout, which the runtime only has to
    tile.  A buffer is rewritten once its place has returned, so the fake
    copies what it is shown."""
    placed = []

    class Keeps(PhasedEngine):
        def place(self, bucket, im1, im2, sizes=None):
            placed.append([(a.copy(), a.strides) for a in (im1, im2)])
            return super().place(bucket, im1, im2, sizes)

    q, b, reg = make_phased_stack(Keeps(), max_batch=4, max_wait_ms=5.0)
    h, w = BUCKET
    rng = np.random.default_rng(0)
    for n in (3, 1):
        rows = [rng.random((2, 1, h, w, 3), dtype=np.float32) * 0.5
                for _ in range(n)]
        reqs = [Request(r[0], r[1], BUCKET, (0, 0, 0, 0),
                        deadline=time.monotonic() + 30.0) for r in rows]
        for r in reqs:
            q.submit(r)
        for r in reqs:
            r.wait(timeout=10)
        padded = rows + [rows[-1]] * (b.pad_batch_to(n) - n)
        for k, (got, strides) in enumerate(placed.pop()):
            assert np.array_equal(got, np.concatenate([r[k] for r in padded]))
            assert strides == (3 * h * w * 4, w * 4, 4, h * w * 4)
        assert not placed
    return q, b


@pytest.mark.parametrize("case", [
    _pipeline_stages_ahead, _pipeline_part_batch_waits_for_its_mates,
    _pipeline_ready_run_releases_the_part_batch,
    _pipeline_idle_request_pays_nothing,
    _pipeline_every_client_in_one_batch,
    _pipeline_places_channel_planar_views], ids=lambda f: f.__name__[10:])
def test_pipeline(case):
    q, b = case()
    q.close()
    b.join(5)
    assert not b.alive and b._running is None


# ----------------------------------------------- streaming: session store --

def test_session_store_lru_demotes_features():
    from raft_tpu.serving import SessionStore

    store = SessionStore(max_sessions=2, ttl_s=60.0)
    a, b, c = (store.open((32, 48)) for _ in range(3))
    slots = [store.promote(s) for s in (a, b, c)]
    assert None not in slots
    # capacity 2: promoting c demoted the LRU holder (a) — record kept,
    # a's slot freed back to the pool (c reuses it)
    assert store.active_count() == 2
    assert store.pool.in_use((32, 48)) == 2
    assert store.resident_count() == 3
    assert not a.has_features and a.bucket == (32, 48)
    assert b.has_features and c.has_features
    # re-promoting a demotes the now-LRU b
    store.promote(a)
    assert a.has_features and not b.has_features and c.has_features
    # a session that already holds a slot keeps it (in-place commit path)
    assert store.promote(c) == c.slot
    assert store.pool.in_use((32, 48)) == 2


def test_session_store_skips_inflight_on_demote_and_sweep():
    from raft_tpu.serving import SessionStore

    store = SessionStore(max_sessions=1, ttl_s=60.0)
    a = store.open((32, 48))
    store.promote(a)
    with a.lock:                         # a is mid-advance
        b = store.open((32, 48))
        # a is locked (not demotable) and holds the only slot: b stays
        # cold rather than stealing an in-flight session's slot
        assert store.promote(b) is None
        assert a.has_features and not b.has_features
        assert store.sweep(now=time.monotonic() + 999) >= 1   # b reaped
        assert store.get(a.id) is a      # locked: not reaped either
    store.sweep(now=time.monotonic() + 999)
    assert store.get(a.id) is None       # unlocked: TTL reaps it
    assert store.pool.in_use((32, 48)) == 0   # ...and frees its slot


def test_session_store_ttl_and_record_cap():
    from raft_tpu.serving import SessionStore
    from raft_tpu.serving.session import RECORD_CAP_FACTOR

    store = SessionStore(max_sessions=1, ttl_s=0.001)
    ids = [store.open((32, 48)).id for _ in range(RECORD_CAP_FACTOR + 2)]
    # records bounded: the oldest were evicted outright at the cap
    assert store.resident_count() <= RECORD_CAP_FACTOR
    assert store.get(ids[0]) is None
    time.sleep(0.005)
    store.sweep()
    assert store.resident_count() == 0   # TTL reaped the rest
    assert store.close(ids[-1]) is None  # already gone


def test_sweep_frees_device_slot_back_to_pool():
    """TTL reaping must return the reaped session's device slot to the
    pool (not just drop the Python record), or a long-lived server
    strands slot capacity behind dead sessions."""
    from raft_tpu.serving import SessionStore

    store = SessionStore(max_sessions=2, ttl_s=0.001)
    a, b = store.open((32, 48)), store.open((32, 48))
    store.promote(a)
    store.promote(b)
    assert store.pool.in_use((32, 48)) == 2
    time.sleep(0.005)
    assert store.sweep() == 2
    assert store.pool.in_use((32, 48)) == 0
    # the freed slots are allocatable again
    c, d = store.open((32, 48)), store.open((32, 48))
    assert store.promote(c) is not None and store.promote(d) is not None


def test_slot_pool_concurrent_open_close_evict_no_leaks():
    """Slot alloc/free under concurrent open/promote/close/sweep from
    many threads: accounting must balance exactly — every allocated slot
    is either held by a live promoted session or back on the free list,
    and in_use never exceeds capacity."""
    from raft_tpu.serving import SessionStore

    store = SessionStore(max_sessions=4, ttl_s=60.0)
    bucket = (32, 48)
    errors = []

    def churn(seed):
        rng = np.random.RandomState(seed)
        try:
            for _ in range(60):
                s = store.open(bucket)
                with s.lock:
                    store.promote(s)
                assert store.pool.in_use(bucket) <= store.pool.capacity
                if rng.rand() < 0.5:
                    store.close(s.id)
                if rng.rand() < 0.2:
                    store.sweep(now=time.monotonic() - 1)  # reaps nothing
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=churn, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    # drain everything: no slot may stay stranded
    for sid in list(store._sessions):
        store.close(sid)
    assert store.resident_count() == 0
    assert store.pool.in_use(bucket) == 0


def test_demote_bucket_overrides_inflight_skip():
    """The failed-commit recovery hook: after a bucket's buffers are
    rebuilt zeroed, EVERY session of that bucket must lose its slot —
    in-flight ones included (a kept slot would gather the zeros) —
    while other buckets' sessions are untouched."""
    from raft_tpu.serving import SessionStore

    store = SessionStore(max_sessions=4, ttl_s=60.0)
    a, b = store.open((32, 48)), store.open((32, 48))
    c = store.open((64, 96))
    for s in (a, b, c):
        store.promote(s)
    with a.lock:                         # a is mid-advance: still demoted
        assert store.demote_bucket((32, 48)) == 2
    assert not a.has_features and not b.has_features
    assert c.has_features                # other bucket untouched
    assert store.pool.in_use((32, 48)) == 0
    assert store.pool.in_use((64, 96)) == 1
    # idempotent per session: demote after the bucket sweep is a no-op
    store.demote(a, "degraded")
    assert store.pool.in_use((32, 48)) == 0


def test_close_during_inflight_advance_defers_slot_free():
    """close() racing an in-flight advance must NOT free the slot while
    the batcher may still scatter into it — the handler's
    reclaim_if_closed epilogue frees it after the session lock drops."""
    from raft_tpu.serving import SessionStore

    store = SessionStore(max_sessions=2, ttl_s=60.0)
    s = store.open((32, 48))
    store.promote(s)
    with s.lock:                         # a frame is in flight
        store.close(s.id)
        assert s.slot is not None        # deferred: batcher-safe
        assert store.pool.in_use((32, 48)) == 1
    store.reclaim_if_closed(s)           # the handler epilogue
    assert s.slot is None
    assert store.pool.in_use((32, 48)) == 0


# --------------------------------------------- streaming: live server -----

@pytest.fixture(scope="module")
def stream_server():
    """A streaming-enabled live server: one bucket, batch 1, 2 GRU
    iterations, max_sessions=1 so eviction is exercised with only two
    sessions."""
    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models import init_raft

    config = RAFTConfig.small_model(iters=2)
    params = init_raft(init_rng(), config)
    sconfig = ServeConfig(buckets=((32, 48),), max_batch=1,
                          batch_steps=(1,), max_wait_ms=5.0,
                          queue_depth=16, default_deadline_ms=30_000.0,
                          port=0, max_sessions=1, session_ttl_s=600.0)
    server = FlowServer(config, params, sconfig)
    server.start()
    yield server, config, params
    server.stop()


def _post_stream(server, payload):
    req = urllib.request.Request(
        server.url + "/v1/stream", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def _stream_error(server, payload):
    try:
        _post_stream(server, payload)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())
    raise AssertionError("expected an HTTP error")


def _frames(seed, n, hw=(32, 48)):
    rng = np.random.RandomState(seed)
    return [rng.rand(hw[0], hw[1], 3).astype(np.float32) for _ in range(n)]


def test_stream_warmup_shares_cache_namespace(stream_server):
    """Pair, encode, and stream executables are all warmed into ONE engine
    cache, keyed by kind + policy; nothing compiles at serve time."""
    server, _, _ = stream_server
    assert server.engine.keys() == [
        ("encode", 32, 48, 1, "fixed"),
        ("pair", 32, 48, 1, "fixed"),
        ("sbatch", 32, 48, 1, "fixed"),     # continuous-batched advance
        ("scommit", 32, 48, 1, "fixed"),    # slot-pool commit scatter
        ("stream", 32, 48, 1, "fixed"),     # cold-restart solo step
        ("szero", 32, 48, 1, "fixed")]      # pool buffer builder
    assert server.engine.compile_misses == 0


def test_stream_session_lifecycle_and_equivalence(stream_server):
    """open -> advance x3 -> close over HTTP.  The FIRST advance (zero
    warm-start seed) must match the pairwise /v1/flow answer on the same
    two frames; later advances warm-start (a different, better-seeded
    trajectory) and only their shape/meta is pinned.  Exactly ONE fnet
    pass per streamed frame (engine counters — the acceptance criterion)."""
    server, _, _ = stream_server
    eng = server.engine
    frames = _frames(30, 4)
    enc0, str0 = eng.encode_calls, eng.stream_calls

    r = _post_stream(server, {"image": frames[0].tolist()})
    sid = r["session"]
    assert r["frame"] == 0 and r["meta"]["bucket"] == [32, 48]
    assert eng.encode_calls == enc0 + 1          # open: one encoder pass

    r1 = _post_stream(server, {"session": sid, "image": frames[1].tolist()})
    assert r1["frame"] == 1 and r1["meta"]["warm"] is True
    flow1 = np.asarray(r1["flow"], np.float32)
    assert flow1.shape == (32, 48, 2)
    pw = _post_json(server, frames[0], frames[1])
    np.testing.assert_allclose(flow1, np.asarray(pw["flow"], np.float32),
                               rtol=1e-4, atol=1e-2)

    for t in (2, 3):
        rt = _post_stream(server, {"session": sid,
                                   "image": frames[t].tolist()})
        assert rt["frame"] == t and rt["meta"]["warm"] is True
        assert np.isfinite(np.asarray(rt["flow"])).all()
    # 3 advances = 3 stream calls, ZERO extra encode calls: one fnet pass
    # per streamed frame after the first
    assert eng.stream_calls == str0 + 3
    assert eng.encode_calls == enc0 + 1
    assert eng.compile_misses == 0

    rc = _post_stream(server, {"op": "close", "session": sid})
    assert rc["closed"] is True and rc["frames"] == 3


def test_stream_eviction_falls_back_cold_with_correct_flow(stream_server):
    """max_sessions=1: opening session B evicts A's features.  A's next
    advance must still answer — cold two-encoder restart, flow equal to
    the pairwise answer on the same frames — and the eviction/cold
    counters must say so."""
    server, _, _ = stream_server
    eng = server.engine
    fa, fb = _frames(31, 3), _frames(32, 2)

    sa = _post_stream(server, {"image": fa[0].tolist()})["session"]
    r1 = _post_stream(server, {"session": sa, "image": fa[1].tolist()})
    assert r1["meta"]["warm"] is True
    sb = _post_stream(server, {"image": fb[0].tolist()})["session"]
    _post_stream(server, {"session": sb, "image": fb[1].tolist()})

    enc0 = eng.encode_calls
    r2 = _post_stream(server, {"session": sa, "image": fa[2].tolist()})
    assert r2["meta"]["warm"] is False           # demoted -> cold restart
    assert eng.encode_calls == enc0 + 1          # re-encoded the prev frame
    pw = _post_json(server, fa[1], fa[2])
    np.testing.assert_allclose(np.asarray(r2["flow"], np.float32),
                               np.asarray(pw["flow"], np.float32),
                               rtol=1e-4, atol=1e-2)
    with urllib.request.urlopen(server.url + "/metrics") as r:
        text = r.read().decode()
    assert 'raft_stream_evictions_total{reason="lru"}' in text
    assert "raft_stream_fnet_cache_misses_total" in text
    assert server.engine.compile_misses == 0
    for s in (sa, sb):
        _post_stream(server, {"op": "close", "session": s})


def test_stream_metrics_and_healthz(stream_server):
    server, _, _ = stream_server
    frames = _frames(33, 2)
    sid = _post_stream(server, {"image": frames[0].tolist()})["session"]
    _post_stream(server, {"session": sid, "image": frames[1].tolist()})
    with urllib.request.urlopen(server.url + "/healthz") as r:
        h = json.loads(r.read())
    assert h["stream"]["max_sessions"] == 1
    assert h["stream"]["sessions_active"] >= 1
    with urllib.request.urlopen(server.url + "/metrics") as r:
        text = r.read().decode()
    for name in ("raft_stream_sessions_active",
                 "raft_stream_sessions_resident",
                 "raft_stream_opens_total",
                 "raft_stream_frames_total",
                 "raft_stream_fnet_cache_hits_total"):
        assert name in text, name
    _post_stream(server, {"op": "close", "session": sid})


def test_stream_error_statuses(stream_server):
    server, _, _ = stream_server
    im = np.zeros((32, 48, 3)).tolist()
    # unknown session -> 404
    st, body = _stream_error(server, {"session": "deadbeef", "image": im})
    assert st == 404 and "unknown session" in body["error"]
    st, _ = _stream_error(server, {"op": "close", "session": "deadbeef"})
    assert st == 404
    # image missing -> 400
    st, body = _stream_error(server, {"op": "open"})
    assert st == 400 and "image" in body["error"]
    # bad op -> 400
    st, body = _stream_error(server, {"op": "advnce", "session": "x",
                                      "image": im})
    assert st == 400 and "op" in body["error"]
    # unroutable first frame -> 400
    big = np.zeros((72, 104, 3)).tolist()
    st, body = _stream_error(server, {"image": big})
    assert st == 400 and "bucket" in body["error"]
    # busy session (a frame already in flight) -> 409
    sid = _post_stream(server, {"image": im})["session"]
    sess = server.streams.store.get(sid)
    with sess.lock:                      # simulate an in-flight frame
        st, body = _stream_error(server, {"session": sid, "image": im})
    assert st == 409 and "in flight" in body["error"]
    _post_stream(server, {"op": "close", "session": sid})


def test_stream_disabled_server_rejects(live_server):
    """The pairwise fixture runs with --max-sessions 0: /v1/stream must
    answer 400 with a pointer, not 404-the-path or a crash."""
    server, _, _ = live_server
    st, body = _stream_error(server, {"image": np.zeros((32, 48, 3)).tolist()})
    assert st == 400 and "disabled" in body["error"]


def test_stream_npz_round_trip(stream_server):
    server, _, _ = stream_server
    frames = _frames(34, 2)

    def post_npz(**arrays):
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        req = urllib.request.Request(
            server.url + "/v1/stream", data=buf.getvalue(),
            headers={"Content-Type": "application/octet-stream",
                     "Accept": "application/octet-stream"})
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
            return np.load(io.BytesIO(r.read()))

    with post_npz(image=frames[0]) as z:
        sid = str(z["session"])
        assert int(z["frame"]) == 0
    with post_npz(op=np.asarray("advance"), session=np.asarray(sid),
                  image=frames[1]) as z:
        assert z["flow"].shape == (32, 48, 2)
        assert np.isfinite(z["flow"]).all()
        assert bool(z["warm"]) is True
    _post_stream(server, {"op": "close", "session": sid})


def test_stream_continuous_batching_coalesces_sessions():
    """The ISSUE 15 tentpole, end to end over HTTP: concurrent advances
    from DIFFERENT sessions coalesce into ONE batched stream device call
    (slot-pool gather -> batched step -> masked commit), padded to a
    declared batch step, with a demoted session's row degrading to the
    cold path INSIDE the same group, per-row iters accounted (padding
    excluded), and zero compile misses at the batched widths."""
    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models import init_raft

    config = RAFTConfig.small_model(iters=3)
    params = init_raft(init_rng(), config)
    # max_wait 250ms: wide enough that the three barrier-released
    # advances always coalesce; max_sessions=2 of 3 sessions forces one
    # demoted (cold) row into the coalesced group
    sconfig = ServeConfig(buckets=((32, 48),), max_batch=4,
                          batch_steps=(1, 2, 4), max_wait_ms=250.0,
                          queue_depth=16, default_deadline_ms=30_000.0,
                          port=0, max_sessions=2, session_ttl_s=600.0,
                          iters_policy="converge:1e9:2")
    server = FlowServer(config, params, sconfig)
    server.start()
    try:
        eng = server.engine
        seqs = [_frames(40 + i, 2) for i in range(3)]
        sids = [_post_stream(server, {"image": fr[0].tolist()})["session"]
                for fr in seqs]
        # 3 opens > max_sessions=2: the first session's slot was demoted
        assert server.streams.store.pool.in_use((32, 48)) == 2
        iters0 = server.metrics["iters_used"].count
        str0, enc0 = eng.stream_calls, eng.encode_calls
        barrier = threading.Barrier(3)
        out, errs = [None] * 3, []

        def advance(i):
            try:
                barrier.wait(timeout=10)
                out[i] = _post_stream(server, {"session": sids[i],
                                               "image": seqs[i][1].tolist()})
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        threads = [threading.Thread(target=advance, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        # one coalesced group of 3, padded to the declared step 4
        assert [r["meta"]["batch_real"] for r in out] == [3, 3, 3]
        assert [r["meta"]["batch_padded"] for r in out] == [4, 4, 4]
        # the demoted session (LRU: the first opened) healed cold inside
        # the group; its slot-holding batch-mates stayed warm
        assert [r["meta"]["warm"] for r in out] == [False, True, True]
        # every row's flow equals the pairwise answer on its own frames
        # (first advances seed zero flow, exactly like /v1/flow)
        for i, r in enumerate(out):
            pw = _post_json(server, seqs[i][0], seqs[i][1])
            np.testing.assert_allclose(
                np.asarray(r["flow"], np.float32),
                np.asarray(pw["flow"], np.float32), rtol=1e-4, atol=1e-2)
        # per-row iters recorded for the 3 REAL rows only (the padding
        # row is excluded), each exiting at min_iters
        assert [r["meta"]["iters_used"] for r in out] == [2, 2, 2]
        assert server.metrics["iters_used"].count - iters0 >= 3
        # fnet accounting: 1 stream row per warm advance + 1 for the cold
        # heal's re-run; the cold heal also re-encoded the prev frame
        assert eng.stream_calls == str0 + 3
        assert eng.encode_calls == enc0 + 1
        # the stream step families saw the real width
        with urllib.request.urlopen(server.url + "/metrics") as r:
            text = r.read().decode()
        prom = dict(
            ln.rsplit(" ", 1) for ln in text.splitlines()
            if ln and not ln.startswith("#"))
        assert float(prom["raft_stream_step_batch_sum"]) >= 3.0
        assert 'raft_stream_slots_in_use{bucket="32x48"} 2' in text
        assert 'raft_stream_slot_capacity{bucket="32x48"} 2' in text
        assert eng.compile_misses == 0       # batched widths all warmed
        for sid in sids:
            _post_stream(server, {"op": "close", "session": sid})
        assert server.streams.store.pool.in_use((32, 48)) == 0
    finally:
        server.stop()


def test_stream_converge_policy_end_to_end():
    """Streaming under --iters-policy: policy-keyed pair/encode/stream
    executables, per-advance iters_used in meta and the raft_iters_used
    histogram, zero compile misses."""
    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models import init_raft

    config = RAFTConfig.small_model(iters=3)
    params = init_raft(init_rng(), config)
    sconfig = ServeConfig(buckets=((32, 48),), max_batch=1,
                          batch_steps=(1,), max_wait_ms=5.0, queue_depth=8,
                          port=0, iters_policy="converge:1e9:2",
                          max_sessions=2)
    server = FlowServer(config, params, sconfig)
    server.start()
    try:
        assert server.engine.keys() == [
            ("encode", 32, 48, 1, "converge:1e9:2"),
            ("pair", 32, 48, 1, "converge:1e9:2"),
            ("sbatch", 32, 48, 1, "converge:1e9:2"),
            ("scommit", 32, 48, 1, "converge:1e9:2"),
            ("stream", 32, 48, 1, "converge:1e9:2"),
            ("szero", 32, 48, 1, "converge:1e9:2")]
        frames = _frames(35, 3)
        sid = _post_stream(server, {"image": frames[0].tolist()})["session"]
        for t in (1, 2):
            r = _post_stream(server, {"session": sid,
                                      "image": frames[t].tolist()})
            assert r["meta"]["iters_used"] == 2   # exited at min_iters
        with urllib.request.urlopen(server.url + "/metrics") as r:
            text = r.read().decode()
        assert "raft_iters_used_count 2" in text
        assert server.engine.compile_misses == 0
    finally:
        server.stop()


# ------------------------------------------------- request tracing (live) --

def _poll_debug_traces(server, trace_id, timeout=5.0):
    """A trace is finished by the handler AFTER the response bytes go out,
    so a client can race /debug/traces against its own request's closing
    statements — poll briefly (eventual visibility is the contract)."""
    deadline = time.monotonic() + timeout
    while True:
        with urllib.request.urlopen(
                server.url + f"/debug/traces?trace_id={trace_id}") as r:
            dbg = json.loads(r.read())
        if dbg["traces"] or time.monotonic() > deadline:
            return dbg
        time.sleep(0.02)


def test_live_trace_meta_timings_and_debug_endpoint(live_server):
    """The tracing contract over real HTTP: a client-supplied
    X-Raft-Trace-Id is adopted and echoed (meta + header), meta.timings
    carries the server-side breakdown, /debug/traces serves the trace by
    id, the top-level spans account for the server-side e2e, and nothing
    leaks open."""
    server, _, _ = live_server
    rng = np.random.RandomState(40)
    im = rng.rand(32, 48, 3).astype(np.float32)
    payload = {"image1": im.tolist(), "image2": im.tolist()}
    req = urllib.request.Request(
        server.url + "/v1/flow", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json",
                 "X-Raft-Trace-Id": "CAFED00D-7e57"})
    with urllib.request.urlopen(req) as r:
        hdr_tid = r.headers["X-Raft-Trace-Id"]
        hdr_timings = json.loads(r.headers["X-Raft-Timings"])
        resp = json.loads(r.read())
    assert resp["meta"]["trace_id"] == "cafed00d-7e57" == hdr_tid
    timings = resp["meta"]["timings"]
    assert timings == hdr_timings
    for name in ("admit", "queue_wait", "batch_form", "pad", "execute",
                 "execute_dispatch", "execute_block"):
        assert name in timings, name
    # dispatch + block partition the device call (within rounding)
    assert timings["execute"] >= timings["execute_dispatch"]

    dbg = _poll_debug_traces(server, "cafed00d")
    assert dbg["open_traces"] == 0
    [trace] = dbg["traces"]
    assert trace["status"] == "ok" and trace["kind"] == "pair"
    spans = trace["spans"]
    root = next(s for s in spans if s["name"] == "request")
    assert any(s["name"] == "respond" for s in spans)
    top = sum(s["dur_ms"] for s in spans if s.get("parent") == root["span"])
    # the acceptance bar: spans account for the request's e2e latency
    assert top >= 0.9 * root["dur_ms"]


def test_stream_advance_carries_trace_and_step_metrics(stream_server):
    """Stream advances report meta.trace_id + meta.timings, and the
    stream-step families (the occupancy-gap fix) observe every device
    step at batch 1 / occupancy 1.0."""
    server, _, _ = stream_server
    before = server.registry.get("raft_stream_steps_total").value
    frames = _frames(60, 3)
    r0 = _post_stream(server, {"image": frames[0].tolist()})
    sid = r0["session"]
    assert "trace_id" in r0["meta"]
    r1 = _post_stream(server, {"session": sid, "image": frames[1].tolist()})
    assert "trace_id" in r1["meta"]
    tm = r1["meta"]["timings"]
    assert "queue_wait" in tm and "execute" in tm
    # the stream device call is split dispatch/block too
    assert "execute_dispatch" in tm and "execute_block" in tm
    reg = server.registry
    assert reg.get("raft_stream_steps_total").value >= before + 2
    assert reg.get("raft_stream_step_seconds").count >= 2
    # batch 1 / occupancy 1.0: the baseline continuous batching must beat
    assert reg.get("raft_stream_step_batch").sum == \
        reg.get("raft_stream_step_batch").count
    assert reg.get("raft_stream_step_occupancy").sum == \
        reg.get("raft_stream_step_occupancy").count
    dbg = _poll_debug_traces(server, r1["meta"]["trace_id"])
    assert dbg["traces"] and dbg["traces"][0]["kind"] == "stream"
    _post_stream(server, {"op": "close", "session": sid})


def test_new_metric_families_prometheus_round_trip(stream_server):
    """Exposition round-trip for the families this PR adds: render ->
    parse -> the histograms are internally consistent (cumulative
    buckets nondecreasing, +Inf == count) and the SLO gauges parse as
    floats."""
    server, _, _ = stream_server
    with urllib.request.urlopen(server.url + "/metrics") as r:
        text = r.read().decode()
    # minimal Prometheus text parser (serve_bench carries the same shape)
    import re
    parsed = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        m = re.match(r"^(\S+?)(\{[^}]*\})?\s+(\S+)$", ln)
        assert m, f"unparseable exposition line: {ln!r}"
        parsed[m.group(1) + (m.group(2) or "")] = float(m.group(3))
    for fam in ("raft_stream_step_seconds", "raft_stream_step_batch",
                "raft_stream_step_occupancy"):
        count = parsed[f"{fam}_count"]
        buckets = sorted(
            ((float("inf") if k.split('le="')[1].rstrip('"}') == "+Inf"
              else float(k.split('le="')[1].rstrip('"}'))), v)
            for k, v in parsed.items() if k.startswith(f"{fam}_bucket"))
        assert buckets, fam
        cums = [v for _, v in buckets]
        assert cums == sorted(cums), f"{fam}: buckets not cumulative"
        assert cums[-1] == count, f"{fam}: +Inf bucket != count"
        assert f"{fam}_sum" in parsed
    assert parsed['raft_slo_burn_rate{class="pair"}'] >= 0.0
    assert parsed['raft_slo_burn_rate{class="stream"}'] >= 0.0
    assert 'raft_slo_violations_total{class="pair"}' in parsed
    assert parsed["raft_stream_steps_total"] >= 1


# ------------------------------------------------------------- CLI wiring --

def test_serve_cli_rejects_bad_buckets(capsys):
    from raft_tpu import cli
    rc = cli.main(["-m", "serve", "--small", "--buckets", "33x48"])
    assert rc == 2
    assert "multiples of 8" in capsys.readouterr().out


def test_serve_bench_importable_and_parses_prom():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "serve_bench", os.path.join(os.path.dirname(__file__), "..",
                                    "tools", "serve_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    prom = mod.parse_prom(
        '# HELP x y\nfoo 3\nbar{a="b"} 2.5\nbaz_bucket{le="+Inf"} 7\n')
    assert prom == {"foo": 3.0, 'bar{a="b"}': 2.5,
                    'baz_bucket{le="+Inf"}': 7.0}


# ------------------------- metric history + profiler capture endpoints ----

def test_debug_history_endpoint_serves_derived_series():
    """GET /debug/history returns the derived columnar series (all the
    DEFAULT_PANELS keys, N-1 points for N retained samples), honors
    ?window= clipping, 400s malformed windows, and healthz carries the
    sentinel verdict map."""
    from raft_tpu.telemetry.timeseries import DEFAULT_PANELS

    eng = StubEngine()
    sconfig = ServeConfig(buckets=((32, 48),), max_batch=2, max_wait_ms=5.0,
                          port=0, history_interval_s=0.05,
                          history_window=100, anomaly_window_s=0.5,
                          anomaly_baseline_s=2.0)
    server = FlowServer(None, None, sconfig, engine=eng)
    server.start()
    try:
        im = np.zeros((32, 48, 3)).tolist()
        req = urllib.request.Request(
            server.url + "/v1/flow",
            data=json.dumps({"image1": im, "image2": im}).encode(),
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req).read()
        body = None
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with urllib.request.urlopen(server.url + "/debug/history") as r:
                assert r.status == 200
                body = json.loads(r.read())
            if body["retained"] >= 3:
                break
            time.sleep(0.05)
        assert body["retained"] >= 3, body
        assert body["interval_s"] == 0.05
        series = body["series"]
        assert set(series) == {"t"} | {n for n, *_ in DEFAULT_PANELS}
        assert len(series["t"]) == body["retained"] - 1
        assert len(series["p95_ms"]) == len(series["t"])
        # a clean stub server fires nothing (the acceptance criterion's
        # zero-anomalies-when-clean half, at unit scale)
        assert body["anomalies_active"] == {}
        with urllib.request.urlopen(
                server.url + "/debug/history?window=0.01") as r:
            clipped = json.loads(r.read())
        assert clipped["retained"] <= 2        # 10ms window, 50ms interval
        for bad in ("?window=nope", "?window=-3", "?window=0"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(server.url + "/debug/history" + bad)
            assert ei.value.code == 400, bad
        with urllib.request.urlopen(server.url + "/healthz") as r:
            h = json.loads(r.read())
        assert h["anomalies"] == {}
        # the sentinel gauges are pre-created: exposition shows every rule
        with urllib.request.urlopen(server.url + "/metrics") as r:
            text = r.read().decode()
        assert 'raft_anomaly_active{rule="p95_drift"} 0' in text
        assert 'raft_anomaly_fires_total{rule="queue_growth"} 0' in text
    finally:
        server.stop()


def test_debug_history_404_when_disabled():
    eng = StubEngine()
    sconfig = ServeConfig(buckets=((32, 48),), port=0,
                          history_interval_s=0.0)
    server = FlowServer(None, None, sconfig, engine=eng)
    server.start()
    try:
        assert server.history is None and server.anomaly is None
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(server.url + "/debug/history")
        assert ei.value.code == 404
    finally:
        server.stop()


def test_debug_profile_validation_busy_and_capture(tmp_path):
    """POST /debug/profile: 400 on malformed/over-limit ms, 409 (with
    Retry-After) while another capture holds the process-wide profiler,
    200 + an on-disk XPlane tree for a real capture."""
    from pathlib import Path

    from raft_tpu.telemetry import trace as tlm_trace

    eng = StubEngine()
    sconfig = ServeConfig(buckets=((32, 48),), port=0,
                          history_interval_s=0.0)
    server = FlowServer(None, None, sconfig, engine=eng)
    server.profile_dir = str(tmp_path / "profiles")
    server.start()
    try:
        def post(qs):
            return urllib.request.Request(
                server.url + "/debug/profile" + qs, data=b"", method="POST")

        for bad in ("?ms=0", "?ms=-5", "?ms=abc", "?ms=999999999"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(post(bad))
            assert ei.value.code == 400, bad
        assert tlm_trace._capture_lock.acquire(timeout=5)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(post("?ms=50"))
            assert ei.value.code == 409
            assert int(ei.value.headers["Retry-After"]) >= 1
        finally:
            tlm_trace._capture_lock.release()
        with urllib.request.urlopen(post("?ms=50")) as r:
            info = json.loads(r.read())
        assert info["status"] == "captured"
        assert info["duration_ms"] == 50.0
        dest = Path(info["trace_dir"])
        assert dest.is_dir()
        assert str(dest).startswith(str(tmp_path))
        assert list(dest.rglob("*.xplane.pb")), \
            "capture produced no XPlane file"
    finally:
        server.stop()


# ------------------------------------------ the HTTP edge's frames (PR 36)

@pytest.mark.parametrize("size,bucket", [((1080, 1920), (1080, 1920)),
                                         ((436, 1024), (440, 1024)),
                                         ((30, 44), (32, 48))])
def test_uint8_frames_decode_to_the_same_values_with_fewer_copies(size,
                                                                  bucket):
    """A ``uint8`` frame's range is read before it is widened and it is
    scaled in place: the values of ``float32(frame) / 255.0`` bit for bit;
    a frame of the bucket's size is not copied again on its way to the queue
    (``astype`` to the dtype it has, ``np.pad`` by nothing)."""
    from raft_tpu.data.pipeline import pad_to_shape
    from raft_tpu.serving.http import _decode_image

    rng = np.random.default_rng(size[0])
    frame = rng.integers(0, 256, (*size, 3), dtype=np.uint8)
    got = _decode_image(frame, "image1")
    want = np.asarray(frame, np.float32) / 255.0
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    padded, pads = pad_to_shape(got[None].astype(np.float32, copy=False),
                                bucket)
    ref, ref_pads = pad_to_shape(want[None].astype(np.float32), bucket)
    assert pads == ref_pads and padded.shape == (1, *bucket, 3)
    np.testing.assert_array_equal(padded, ref)
    assert np.shares_memory(padded, got) == (size == bucket)


def test_decode_image_keeps_its_checks():
    """Payloads in 0..1 are not rescaled (a uint8 frame of zeros and ones
    among them), float payloads are still checked for non-finite values,
    shapes for three channels."""
    from raft_tpu.serving.http import BadRequest, _decode_image

    ones = np.zeros((4, 6, 3), np.uint8)
    ones[1, 2, 0] = 1
    assert _decode_image(ones, "image1").max() == 1.0
    unit = np.full((4, 6, 3), 0.5, np.float32)
    assert _decode_image(unit, "image1") is unit
    assert _decode_image((unit * 255).tolist(), "image1").max() == 0.5
    bad = unit.copy()
    bad[0, 0, 0] = np.nan
    with pytest.raises(BadRequest, match="non-finite"):
        _decode_image(bad, "image1")
    with pytest.raises(BadRequest, match="shape"):
        _decode_image(np.zeros((4, 6), np.uint8), "image1")


@pytest.mark.parametrize("crop", [False, True], ids=["whole", "cropped"])
def test_npz_parts_is_an_npz_whose_arrays_are_not_copied(crop):
    """The answer's body: what ``np.load`` reads back is the flow and the
    bucket, the buffers add up to the stated size, and a contiguous flow's
    data is a view of the array itself (a cropped one is made contiguous
    once, as ``np.savez`` would)."""
    from raft_tpu.serving.http import npz_parts

    flow = np.random.default_rng(3).standard_normal(
        (40, 64, 2)).astype(np.float32)
    sent = flow[2:-2, 4:-4] if crop else flow
    parts, size = npz_parts(flow=sent, bucket=np.asarray((40, 64), np.int32))
    body = b"".join(bytes(p) for p in parts)
    assert len(body) == size
    with np.load(io.BytesIO(body)) as z:
        assert sorted(z.files) == ["bucket", "flow"]
        np.testing.assert_array_equal(z["flow"], sent)
        assert tuple(z["bucket"]) == (40, 64)
    views = [p for p in parts if isinstance(p, memoryview)]
    assert len(views) == 2
    assert np.shares_memory(np.frombuffer(views[0], np.float32), flow) \
        == (not crop)
