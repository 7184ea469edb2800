"""Chaos-armed robustness tests (tier-1, CPU): the fault-injection layer
itself (determinism, spec parsing), and the self-healing ladder it exists
to drill — supervisor restart on batcher death, poisoned-batch bisection,
the non-finite output sentinel, circuit-breaker transitions, and the
stream degrade-to-cold-restart path.

Stub-engine tests are fully deterministic (forced injector outcomes, no
timing races, no compiles); the two live-model tests share one tiny
streaming server.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from raft_tpu.serving import (BatcherCrashed, BreakerOpen, ChaosSpec,
                              CircuitBreaker, FaultInjected, FaultInjector,
                              FlowServer, NonFiniteOutput, PoisonedRequest,
                              Registry, RequestQueue, ServeConfig,
                              SessionStore, make_injector, parse_chaos_spec)
from raft_tpu.serving.batcher import MicroBatcher
from raft_tpu.serving.metrics import make_serving_metrics

from test_serving import (BUCKET, PhasedEngine, StubEngine,
                          make_phased_stack, make_request)


# ------------------------------------------------------------ faults.py --

def test_parse_chaos_spec():
    s = parse_chaos_spec("seed=7,engine_error=0.05,latency=0.1,"
                         "latency_ms=150,nan=0.2,session=0.3,kill=1.0")
    assert s == ChaosSpec(seed=7, engine_error=0.05, latency=0.1,
                          latency_ms=150.0, nan=0.2, session=0.3, kill=1.0)
    assert s.armed
    assert parse_chaos_spec("") == ChaosSpec() and not ChaosSpec().armed
    with pytest.raises(ValueError, match="unknown chaos arm"):
        parse_chaos_spec("engine_eror=0.1")        # typo
    with pytest.raises(ValueError, match="in \\[0, 1\\]"):
        parse_chaos_spec("nan=1.5")
    with pytest.raises(ValueError, match="key=value"):
        parse_chaos_spec("nonsense")
    # ServeConfig validates the spec up front, like every other knob
    with pytest.raises(ValueError, match="unknown chaos arm"):
        ServeConfig(chaos="bad_arm=0.5")


def test_injector_deterministic_and_disarmable():
    spec = parse_chaos_spec("seed=3,engine_error=0.5")
    a, b = FaultInjector(spec), FaultInjector(spec)
    rolls = [a.roll("engine_error") for _ in range(32)]
    assert rolls == [b.roll("engine_error") for _ in range(32)]  # replays
    assert any(rolls) and not all(rolls)
    assert a.injected["engine_error"] == sum(rolls)
    a.disarm()
    assert not any(a.roll("engine_error") for _ in range(32))
    a.rearm()
    assert any(a.roll("engine_error") for _ in range(32))
    # forced outcomes (tests' determinism hook) win over the rng
    a.disarm()
    a.force("kill", [1, 0, 1])
    assert [a.roll("kill") for _ in range(4)] == [True, False, True, False]


def test_injector_corrupt_rows_poisons_exactly_one_row():
    inj = make_injector("seed=1")         # all-zero rates; forced only
    flow = np.zeros((4, 8, 8, 2), np.float32)
    assert inj.corrupt_rows(flow) is flow           # no fire: untouched
    inj.force("nan", [1])
    out = inj.corrupt_rows(flow)
    assert np.isfinite(flow).all()                  # input copy-protected
    bad = ~np.isfinite(out.reshape(4, -1)).all(axis=1)
    assert bad.sum() == 1
    assert inj.injected["nan"] == 1


@pytest.mark.parametrize("case", ["clean", "nan-row", "inf-row",
                                  "padded-rows", "flow-lr", "live"])
def test_sentinel_helper_gives_the_old_expressions_verdict(case):
    """``batcher.finite_rows``, the one output sentinel of ``_deliver`` and
    the stream path (PR 43), against the expressions it replaced: the
    batcher's whole-batch ``np.isfinite(flows[:n].reshape(n, -1)).all(axis=
    1)`` and the stream path's per-row test of ``flow`` and ``flow_lr``
    under ``live``.  The same verdict row for row: a NaN row, an Inf row,
    poison in the padding rows past ``n`` (not looked at), poison in
    ``flow_lr`` alone, a row its handler gave up on."""
    from raft_tpu.serving.batcher import finite_rows
    rng = np.random.default_rng(43)
    padded, n = 8, 5
    flow = rng.standard_normal((padded, 16, 24, 2)).astype(np.float32)
    flow_lr = rng.standard_normal((padded, 2, 3, 2)).astype(np.float32)
    live = None
    if case == "nan-row":
        flow[2, 7, 5, 1] = np.nan
    elif case == "inf-row":
        flow[0, 0, 0, 0] = np.inf
        flow[4, 15, 23, 1] = -np.inf
    elif case == "padded-rows":
        flow[n:] = np.nan
        flow_lr[n + 1] = np.inf
    elif case == "flow-lr":
        flow_lr[3, 1, 2, 0] = np.nan
    elif case == "live":
        live = [True, False, True, True, False]
        flow[2, 3, 3, 0] = np.nan
    old_deliver = np.isfinite(flow[:n].reshape(n, -1)).all(axis=1)
    got = finite_rows(n, flow)
    assert got.dtype == bool and got.shape == (n,)
    np.testing.assert_array_equal(got, old_deliver)
    old_stream = np.array(
        [ok and bool(np.isfinite(flow[i]).all()
                     and np.isfinite(flow_lr[i]).all())
         for i, ok in enumerate(live or [True] * n)], bool)
    np.testing.assert_array_equal(
        finite_rows(n, flow, flow_lr, live=live), old_stream)
    want = {"clean": [1, 1, 1, 1, 1], "nan-row": [1, 1, 0, 1, 1],
            "inf-row": [0, 1, 1, 1, 0], "padded-rows": [1, 1, 1, 1, 1],
            "flow-lr": [1, 1, 1, 0, 1], "live": [1, 0, 0, 1, 0]}[case]
    assert finite_rows(n, flow, flow_lr, live=live).tolist() == [
        bool(v) for v in want]
    # the solo step's one row
    assert bool(finite_rows(1, flow[1:2], flow_lr[1:2])[0])
    assert not finite_rows(1, flow[1:2], np.full((1, 2, 2, 2), np.nan))[0]


def test_injector_engine_error_and_latency_arms():
    inj = make_injector("seed=1,latency_ms=30")
    inj.force("latency", [1])
    inj.force("engine_error", [0, 1])
    t0 = time.monotonic()
    inj.pre_engine_call()                           # latency fires: sleeps
    assert time.monotonic() - t0 >= 0.025
    with pytest.raises(FaultInjected):
        inj.pre_engine_call()                       # error fires second


# ----------------------------------------------------------- breaker.py --

def test_breaker_state_machine():
    clock = [0.0]
    b = CircuitBreaker(window=8, threshold=0.5, min_volume=4,
                       cooldown_s=10.0, clock=lambda: clock[0])
    assert b.state == "closed" and b.allow() is None
    for _ in range(3):
        b.record(False)
    assert b.state == "closed"          # below min_volume: no verdict yet
    b.record(False)
    assert b.state == "open" and b.opens == 1
    retry = b.allow()
    assert retry is not None and 0 < retry <= 10.0   # shed + Retry-After
    b.record(True)                      # straggler while open: ignored
    assert b.state == "open"
    clock[0] = 10.5                     # cooldown elapsed -> half-open
    assert b.allow() is None            # the probe slot
    assert b.state == "half_open"
    assert b.allow() is not None        # only one probe at a time
    b.record(False)                     # probe failed -> re-open
    assert b.state == "open" and b.opens == 2
    clock[0] = 21.5
    assert b.allow() is None
    b.record(True)                      # probe succeeded -> closed
    assert b.state == "closed" and b.allow() is None
    # a healed window doesn't instantly re-open on one stray failure
    b.record(False)
    assert b.state == "closed"


def test_breaker_lost_probe_replenishes():
    """A granted half-open probe that dies before reaching the engine
    (400/queue-full/deadline purge: no record() ever) must not wedge the
    breaker — the slot replenishes after a cooldown."""
    clock = [0.0]
    b = CircuitBreaker(window=8, threshold=1.0, min_volume=2,
                       cooldown_s=5.0, clock=lambda: clock[0])
    b.record(False)
    b.record(False)
    clock[0] = 5.5
    assert b.allow() is None            # the probe... which is then lost
    assert b.allow() is not None        # slot taken: shed
    clock[0] = 11.0                     # a cooldown after the lost probe
    assert b.allow() is None            # replenished probe
    b.record(True)
    assert b.state == "closed"


def test_breaker_window_zero_disables():
    from test_serving import StubEngine as _SE
    sconfig = ServeConfig(buckets=((32, 48),), max_batch=2,
                          max_wait_ms=5.0, port=0, breaker_window=0)
    server = FlowServer(None, None, sconfig, engine=_SE())
    assert server.breaker is None       # --breaker-window 0: breaker off


def test_breaker_open_demotes_stream_sessions():
    store = SessionStore(max_sessions=4, ttl_s=60.0)
    opened = []
    b = CircuitBreaker(window=4, threshold=1.0, min_volume=2,
                       cooldown_s=1.0,
                       on_open=lambda: opened.append(store.demote_all()))
    s1, s2 = store.open(BUCKET), store.open(BUCKET)
    store.promote(s1)
    store.promote(s2)
    with s2.lock:                       # s2 mid-advance: not demotable
        b.record(False)
        b.record(False)
    assert b.state == "open" and opened == [1]
    assert not s1.has_features and s2.has_features


# ------------------------------------- supervisor: batcher death drill ---

def _stub_server(engine, chaos="seed=1", **cfg):
    defaults = dict(buckets=((32, 48),), max_batch=4, batch_steps=(1, 2, 4),
                    max_wait_ms=5.0, queue_depth=16, port=0, max_sessions=0,
                    chaos=chaos, degraded_window_s=0.4,
                    retry_backoff_ms=1.0, default_deadline_ms=10_000.0)
    defaults.update(cfg)
    sconfig = ServeConfig(**defaults)
    server = FlowServer(None, None, sconfig, engine=engine)
    server.start()
    return server


def _get_json(server, path):
    with urllib.request.urlopen(server.url + path) as r:
        return json.loads(r.read())


def test_batcher_death_supervisor_restart_and_degraded_healthz():
    """The drill the ISSUE names: kill the batcher thread mid-batch; the
    in-flight request fails fast (no hang into its 504 margin), the
    supervisor restarts the loop, /healthz reports degraded while the
    crash is recent and returns to ok after the window, and the restart
    is visible in raft_batcher_restarts_total."""
    server = _stub_server(StubEngine())
    try:
        server.faults.force("kill", [1])
        im = np.zeros((32, 48, 3), np.float32)
        t0 = time.monotonic()
        with pytest.raises(BatcherCrashed):
            server.infer(im, im)
        assert time.monotonic() - t0 < 5.0          # failed FAST, no hang
        deadline = time.monotonic() + 5.0
        while not server.batcher.alive and time.monotonic() < deadline:
            time.sleep(0.01)
        assert server.batcher.alive                 # supervisor restarted it
        assert server.supervisor.restarts == 1
        h = _get_json(server, "/healthz")
        assert h["status"] == "degraded"            # crash is recent
        assert h["batcher"]["restarts"] == 1
        # the restarted loop serves normally
        assert server.infer(im, im).result.shape == (32, 48, 2)
        time.sleep(0.5)                             # degraded_window_s=0.4
        assert _get_json(server, "/healthz")["status"] == "ok"
        with urllib.request.urlopen(server.url + "/metrics") as r:
            assert "raft_batcher_restarts_total 1" in r.read().decode()
    finally:
        server.stop()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_batcher_shutdown_signal_not_swallowed():
    """The BaseException satellite: KeyboardInterrupt escaping the engine
    fails the batch (no hung handler) but is NOT converted into a
    restart — shutdown wins."""
    class InterruptEngine(StubEngine):
        def run(self, bucket, im1, im2):
            raise KeyboardInterrupt

    q = RequestQueue(8)
    b = MicroBatcher(q, InterruptEngine().run, lambda n: n, 2, 5.0,
                     on_crash=lambda e: pytest.fail("restarted on KI"))
    b.start()
    r = make_request(bucket=(32, 48))
    q.submit(r)
    with pytest.raises(KeyboardInterrupt):
        r.wait(timeout=10)                          # failed, not hung
    b.join(5)
    assert not b.alive                              # thread really exited
    q.close()


# -------------------------------------- bisection + non-finite sentinel --

class PoisonEngine(StubEngine):
    """Fails (or emits NaN) whenever the marked request is in the batch:
    the marker is a constant-1.0 image1, innocents are zeros."""

    def __init__(self, mode="raise"):
        super().__init__()
        self.mode = mode

    def run(self, bucket, im1, im2):
        self.calls.append((bucket, im1.shape[0]))
        poisoned = np.asarray([float(im1[i].max()) >= 1.0
                               for i in range(im1.shape[0])])
        flows = np.zeros(im1.shape[:3] + (2,), np.float32)
        if poisoned.any():
            if self.mode == "raise":
                raise RuntimeError("device rejected the poisoned row")
            flows[np.argmax(poisoned)] = np.inf
        return flows


def _poison_request():
    h, w = BUCKET
    im = np.ones((1, h, w, 3), np.float32)
    from raft_tpu.serving import Request
    return Request(im, im, BUCKET, (0, 0, 0, 0),
                   deadline=time.monotonic() + 30.0)


def _metrics_stack(eng, max_batch=4, retries=1):
    q = RequestQueue(16)
    reg = Registry()
    sc = ServeConfig(buckets=(BUCKET,), max_batch=max_batch,
                     batch_steps=(1, 2, 4), max_wait_ms=30.0)
    metrics = make_serving_metrics(reg, sc)
    from raft_tpu.serving.metrics import make_robustness_metrics
    metrics["nonfinite"] = make_robustness_metrics(reg)["nonfinite"]
    b = MicroBatcher(q, eng.run, sc.pad_batch_to, max_batch, 30.0,
                     metrics=metrics, retries=retries,
                     retry_backoff_s=0.001)
    b.start()
    return q, b, reg


def test_bisection_isolates_exactly_the_poisoned_request():
    """4 coalesced requests, one poisons every batch containing it: the
    3 innocents resolve, the guilty one alone fails as PoisonedRequest,
    and every bisection probe ran at a declared batch step (no new
    shapes = no recompiles on a live engine)."""
    eng = PoisonEngine(mode="raise")
    q, b, reg = _metrics_stack(eng)
    innocents = [make_request() for _ in range(3)]
    guilty = _poison_request()
    for r in (innocents[0], guilty, innocents[1], innocents[2]):
        q.submit(r)
    for r in innocents:
        assert r.wait(timeout=10).shape == (32, 48, 2)   # unharmed
    with pytest.raises(PoisonedRequest, match="poisons its batch"):
        guilty.wait(timeout=10)
    # every probe used a declared step (1, 2 or 4) — warm-grid shapes only
    assert all(n in (1, 2, 4) for _, n in eng.calls)
    assert reg.get("raft_serving_requests_total").labels("ok").value == 3
    assert reg.get("raft_serving_requests_total").labels(
        "poisoned").value == 1
    q.close()
    b.join(5)


def test_transient_engine_error_healed_by_retry():
    """One flaky failure then success: the retry path absorbs it — no
    bisection, no failed requests."""
    class FlakyEngine(StubEngine):
        def __init__(self):
            super().__init__()
            self.failed_once = False

        def run(self, bucket, im1, im2):
            self.calls.append((bucket, im1.shape[0]))
            if not self.failed_once:
                self.failed_once = True
                raise RuntimeError("transient device hiccup")
            return np.zeros(im1.shape[:3] + (2,), np.float32)

    eng = FlakyEngine()
    q, b, _ = _metrics_stack(eng)
    reqs = [make_request() for _ in range(4)]
    for r in reqs:
        q.submit(r)
    for r in reqs:
        assert r.wait(timeout=10).shape == (32, 48, 2)
    assert [n for _, n in eng.calls] == [4, 4]      # same batch, retried
    q.close()
    b.join(5)


def test_sick_engine_exhausts_budget_without_trapping_the_thread():
    """Every call fails: the budget caps the retry storm, every request
    fails (status=error — the engine is sick, nobody is 'poisoned'),
    and the batcher survives to serve the next healthy batch."""
    eng = StubEngine(fail=True)
    q, b, reg = _metrics_stack(eng)
    reqs = [make_request() for _ in range(4)]
    for r in reqs:
        q.submit(r)
    for r in reqs:
        with pytest.raises(RuntimeError):
            r.wait(timeout=20)
    assert len(eng.calls) <= (1 + 1) * 2 * 4        # the bisect budget
    eng.fail = False
    r2 = make_request()
    q.submit(r2)
    assert r2.wait(timeout=10).shape == (32, 48, 2)
    q.close()
    b.join(5)


def test_nan_output_row_fails_alone_neighbors_succeed():
    """The non-finite output sentinel: the engine succeeds but one row is
    Inf — that request alone gets the poisoned 500 class, innocents
    resolve, raft_nonfinite_outputs_total counts the row."""
    eng = PoisonEngine(mode="nan")
    q, b, reg = _metrics_stack(eng)
    innocents = [make_request() for _ in range(3)]
    guilty = _poison_request()
    for r in (innocents[0], innocents[1], guilty, innocents[2]):
        q.submit(r)
    for r in innocents:
        flow = r.wait(timeout=10)
        assert np.isfinite(flow).all()
    with pytest.raises(NonFiniteOutput, match="non-finite flow output"):
        guilty.wait(timeout=10)
    assert len(eng.calls) == 1                      # no bisection needed
    assert reg.get("raft_nonfinite_outputs_total").value == 1
    assert reg.get("raft_serving_requests_total").labels(
        "poisoned").value == 1
    q.close()
    b.join(5)


# ------------------------------------ the pipeline's two batches in chaos --

def _two_batches(eng, first=None, **kw):
    """One batch dispatched and held on the fake device, the next placed
    behind it: (queue, batcher, registry, running requests, staged)."""
    q, b, reg = make_phased_stack(eng, max_batch=2, max_wait_ms=10_000.0,
                                  **kw)
    running = first or [make_request(), make_request()]
    for r in running:
        q.submit(r)
    eng.saw("dispatch", 0)
    staged = [make_request(), make_request()]
    for r in staged:
        q.submit(r)
    eng.saw("h2d", 1)
    return q, b, reg, running, staged


def _chaos_crash_fails_running_and_staged():
    """A crash of the thread with one batch running and one staged fails
    both sets of requests; no handler hangs on either."""
    class Dying(PhasedEngine):
        def ready(self, call):              # outside every phase's guard
            if self.has("h2d", 1):
                raise RuntimeError("the loop itself dies")
            return super().ready(call)

    crashes = []
    eng = Dying(hold=(0,))
    q, b, reg, running, staged = _two_batches(eng, on_crash=crashes.append)
    for r in running + staged:
        with pytest.raises(BatcherCrashed):
            r.wait(timeout=5)
    b.join(5)
    assert not b.alive and len(crashes) == 1
    assert b._running is None and b._inflight_batch is None
    assert reg.get("raft_serving_requests_total").labels("error").value == 4
    q.close()


def _chaos_poisoned_row_bisected_while_the_staged_batch_waits():
    """A poisoned row in n is retried and bisected with the device to
    itself while n+1 stays placed; n+1 is then dispatched and served whole."""
    eng = PhasedEngine(hold=(0,))
    innocent, guilty = make_request(), _poison_request()
    q, b, reg, running, staged = _two_batches(
        eng, first=[innocent, guilty], retries=1, retry_backoff_s=0.001)
    eng.finish(0)
    assert innocent.wait(timeout=10).shape == (32, 48, 2)
    with pytest.raises(PoisonedRequest, match="poisons its batch"):
        guilty.wait(timeout=10)
    for r in staged:
        assert r.wait(timeout=10).shape == (32, 48, 2)
        assert (r.batch_real, r.batch_padded) == (2, 2)
    # call 1 is the staged batch: placed second, dispatched after the
    # retry of call 0 (call 2: re-padded, so it still holds the poison),
    # the innocent half (3) and the guilty one's two attempts (4, 5)
    assert [n for _, n in eng.calls] == [2, 2, 2, 1, 1, 1]
    assert [c.poisoned for c in eng.issued] == [True, False, True, False,
                                                True, True]
    t_dispatch1 = eng.saw("dispatch", 1)
    assert all(eng.saw("wait", i) < t_dispatch1 for i in (0, 2, 3, 4, 5))
    assert reg.get("raft_serving_requests_total").labels("ok").value == 3
    q.close()
    b.join(5)


def _chaos_stream_step_behind_a_running_batch():
    """A streaming batch popped behind a running pairwise batch sees it
    delivered first."""
    import types

    from raft_tpu.serving.stream import StreamRequest
    eng = PhasedEngine(run_s=0.15)
    seen = []

    def stream_group(group):
        seen.append([r.done for r in pairs])
        flow = np.zeros((1, 32, 48, 2), np.float32)
        return [(flow, None, None) for _ in group]

    q, b, reg = make_phased_stack(eng, max_batch=2, max_wait_ms=10_000.0,
                                  stream_group_fn=stream_group)
    pairs = [make_request(), make_request()]
    for r in pairs:
        q.submit(r)
    eng.saw("dispatch", 0)
    im = np.zeros((1, 32, 48, 3), np.float32)
    steps = [StreamRequest(types.SimpleNamespace(id=i, bucket=BUCKET),
                           "advance", im, (0, 0, 0, 0),
                           time.monotonic() + 30.0) for i in range(2)]
    for r in steps:
        q.submit(r)                         # a full bucket: popped at once
    for r in steps + pairs:
        r.wait(timeout=10)
    assert seen == [[True, True]]
    q.close()
    b.join(5)


@pytest.mark.parametrize("case", [
    _chaos_crash_fails_running_and_staged,
    _chaos_poisoned_row_bisected_while_the_staged_batch_waits,
    _chaos_stream_step_behind_a_running_batch],
    ids=lambda f: f.__name__[7:])
def test_pipeline_contains_failures(case):
    case()


# ------------------------------------------------- breaker integration ---

def test_breaker_opens_sheds_503_and_recovers():
    """Persistent engine failure trips the breaker: later submissions are
    shed with BreakerOpen/503 + Retry-After before touching the queue;
    healthz reports degraded; after the cooldown a half-open probe on the
    healed engine closes it again."""
    eng = StubEngine(fail=True)
    server = _stub_server(eng, breaker_window=8, breaker_threshold=0.5,
                          breaker_min_volume=2, breaker_cooldown_s=0.3,
                          engine_retries=0)
    try:
        im = np.zeros((32, 48, 3), np.float32)
        for _ in range(2):                          # reach min_volume=2
            with pytest.raises(RuntimeError):
                server.infer(im, im)                # records the failures
        assert server.breaker.state == "open"
        with pytest.raises(BreakerOpen) as ei:
            server.infer(im, im)
        assert ei.value.http_status == 503
        assert ei.value.retry_after is not None
        assert _get_json(server, "/healthz")["breaker"]["state"] == "open"
        # the wire contract: 503 + Retry-After header
        req = urllib.request.Request(
            server.url + "/v1/flow",
            data=json.dumps({"image1": im.tolist(),
                             "image2": im.tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as he:
            urllib.request.urlopen(req)
        assert he.value.code == 503
        assert int(he.value.headers["Retry-After"]) >= 1
        # storm over: heal the engine, wait out the cooldown, probe
        eng.fail = False
        time.sleep(0.35)
        assert server.infer(im, im).result.shape == (32, 48, 2)
        assert server.breaker.state == "closed"
        with urllib.request.urlopen(server.url + "/metrics") as r:
            text = r.read().decode()
        assert "raft_breaker_state 0" in text
        assert 'raft_breaker_transitions_total{to="open"} 1' in text
        assert 'raft_breaker_transitions_total{to="closed"} 1' in text
    finally:
        server.stop()


def test_queue_full_429_advertises_retry_after():
    gate = threading.Event()
    eng = StubEngine(gate=gate)
    server = _stub_server(eng, chaos=None, max_batch=1, batch_steps=(1,),
                          queue_depth=1)
    try:
        im = np.zeros((32, 48, 3), np.float32)
        results = []

        def bg():
            try:
                results.append(server.infer(im, im))
            except Exception as e:     # noqa: BLE001 — surfaced below
                results.append(e)

        t1 = threading.Thread(target=bg)            # occupies the engine
        t1.start()
        assert eng.entered.wait(10)
        t2 = threading.Thread(target=bg)            # fills the queue
        t2.start()
        time.sleep(0.1)
        body = json.dumps({"image1": im.tolist(),
                           "image2": im.tolist()}).encode()
        req = urllib.request.Request(
            server.url + "/v1/flow", data=body,
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as he:
            urllib.request.urlopen(req)             # 3rd: shed
        assert he.value.code == 429
        assert int(he.value.headers["Retry-After"]) >= 1
        gate.set()
        t1.join(10)
        t2.join(10)
    finally:
        gate.set()
        server.stop()


# ------------------------------------------- stream degrade (live model) --

@pytest.fixture(scope="module")
def chaos_stream_server():
    """A tiny live streaming server with the injector built but every
    rate at zero: tests force the exact faults they need."""
    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models import init_raft

    config = RAFTConfig.small_model(iters=2)
    params = init_raft(init_rng(), config)
    sconfig = ServeConfig(buckets=((32, 48),), max_batch=1,
                          batch_steps=(1,), max_wait_ms=5.0,
                          queue_depth=16, default_deadline_ms=30_000.0,
                          port=0, max_sessions=2, session_ttl_s=600.0,
                          chaos="seed=1", engine_retries=0)
    server = FlowServer(config, params, sconfig)
    server.start()
    yield server
    server.stop()


def _post_stream(server, payload):
    req = urllib.request.Request(
        server.url + "/v1/stream", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def _post_flow(server, im1, im2):
    req = urllib.request.Request(
        server.url + "/v1/flow",
        data=json.dumps({"image1": im1.tolist(),
                         "image2": im2.tolist()}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_stream_engine_fault_degrades_to_cold_restart(chaos_stream_server):
    """A warm advance whose stream step faults degrades transparently:
    features dropped, the SAME advance re-runs cold, and the flow equals
    the pairwise answer on the same frames — the client sees 200, not a
    500 and a poisoned session."""
    server = chaos_stream_server
    rng = np.random.RandomState(50)
    frames = [rng.rand(32, 48, 3).astype(np.float32) for _ in range(3)]
    sid = _post_stream(server, {"image": frames[0].tolist()})["session"]
    r1 = _post_stream(server, {"session": sid, "image": frames[1].tolist()})
    assert r1["meta"]["warm"] is True
    # the NEXT stream-step device call faults (injected engine error on
    # the warm attempt); run_encode is untouched (empty forced queue ->
    # zero rates), so the cold retry inside the same advance succeeds
    server.faults.force("engine_error", [1])
    r2 = _post_stream(server, {"session": sid, "image": frames[2].tolist()})
    assert r2["meta"]["warm"] is False              # degraded to cold
    pw = _post_flow(server, frames[1], frames[2])
    np.testing.assert_allclose(np.asarray(r2["flow"], np.float32),
                               np.asarray(pw["flow"], np.float32),
                               rtol=1e-4, atol=1e-2)
    with urllib.request.urlopen(server.url + "/metrics") as r:
        text = r.read().decode()
    assert "raft_stream_degraded_total 1" in text
    assert 'raft_stream_evictions_total{reason="degraded"} 1' in text
    assert 'raft_fault_injected_total{arm="engine_error"} 1' in text
    assert server.engine.compile_misses == 0        # bisect/retry: warm grid
    _post_stream(server, {"op": "close", "session": sid})


def test_stream_session_corruption_caught_by_sentinel(chaos_stream_server):
    """The session arm poisons the cached fmap with NaN device-side; the
    NaNs propagate into the warm step's flow, the non-finite sentinel
    rejects it, and the advance still answers correct (cold) flow."""
    server = chaos_stream_server
    rng = np.random.RandomState(51)
    frames = [rng.rand(32, 48, 3).astype(np.float32) for _ in range(3)]
    sid = _post_stream(server, {"image": frames[0].tolist()})["session"]
    _post_stream(server, {"session": sid, "image": frames[1].tolist()})
    nonfinite0 = server._robustness["nonfinite"].value
    server.faults.force("session", [1])
    r2 = _post_stream(server, {"session": sid, "image": frames[2].tolist()})
    assert r2["meta"]["warm"] is False              # degraded to cold
    assert np.isfinite(np.asarray(r2["flow"])).all()
    pw = _post_flow(server, frames[1], frames[2])
    np.testing.assert_allclose(np.asarray(r2["flow"], np.float32),
                               np.asarray(pw["flow"], np.float32),
                               rtol=1e-4, atol=1e-2)
    assert server._robustness["nonfinite"].value == nonfinite0 + 1
    _post_stream(server, {"op": "close", "session": sid})


def test_degraded_advance_trace_retained_and_fault_joinable(
        chaos_stream_server, tmp_path):
    """Span lifecycle under the degrade ladder: a warm advance whose
    stream step faults answers 200 but its trace closes ``degraded`` —
    always retained by the flight recorder — and the drill's
    fault_injected run-log event carries the trace id it poisoned (the
    chaos <-> trace join the ISSUE asks for).  No spans leak open."""
    from raft_tpu.telemetry import events as tlm_events

    server = chaos_stream_server
    log = tlm_events.RunLog(tmp_path / "events.jsonl")
    tlm_events.set_current(log)
    try:
        server.faults.run_log = log
        rng = np.random.RandomState(52)
        frames = [rng.rand(32, 48, 3).astype(np.float32) for _ in range(3)]
        sid = _post_stream(server, {"image": frames[0].tolist()})["session"]
        _post_stream(server, {"session": sid, "image": frames[1].tolist()})
        server.faults.force("engine_error", [1])
        r2 = _post_stream(server, {"session": sid,
                                   "image": frames[2].tolist()})
        assert r2["meta"]["warm"] is False           # degraded to cold
        tid = r2["meta"]["trace_id"]
        # the handler finishes the trace AFTER writing the response —
        # poll briefly (eventual visibility, same as /debug/traces)
        deadline = time.monotonic() + 5.0
        degraded = []
        while time.monotonic() < deadline:
            degraded = [t for t in server.flightrec.snapshot()
                        if t["status"] == "degraded"
                        and t["trace_id"] == tid]
            if degraded:
                break
            time.sleep(0.02)
        assert degraded
        # the faulted warm device call is visible inside the trace: an
        # execute span with at least one extra device call (the cold
        # re-encode + re-run) behind it
        [trace] = degraded
        assert sum(s["name"] == "execute_dispatch"
                   for s in trace["spans"]) >= 2
        assert server.tracer.open_traces == 0
        # the fault event joins to the trace it hit
        recs = tlm_events.read_events(tmp_path / "events.jsonl")
        fault = [r for r in recs if r.get("event") == "fault_injected"]
        assert fault and tid in (fault[-1].get("trace_ids") or [])
        _post_stream(server, {"op": "close", "session": sid})
    finally:
        server.faults.run_log = None
        tlm_events.set_current(None)
        log.close()


@pytest.fixture(scope="module")
def chaos_group_server():
    """A streaming server whose advances COALESCE (max_batch 2, wide
    max_wait) with the injector built at zero rates — the group-path
    chaos drills force exactly the faults they need."""
    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models import init_raft

    config = RAFTConfig.small_model(iters=2)
    params = init_raft(init_rng(), config)
    sconfig = ServeConfig(buckets=((32, 48),), max_batch=2,
                          batch_steps=(1, 2), max_wait_ms=250.0,
                          queue_depth=16, default_deadline_ms=30_000.0,
                          port=0, max_sessions=4, session_ttl_s=600.0,
                          chaos="seed=1", engine_retries=0)
    server = FlowServer(config, params, sconfig)
    server.start()
    yield server
    server.stop()


def _coalesced_advance(server, sids, frames):
    """Advance every session concurrently (barrier-released) so the
    batcher pops them as ONE group; returns responses aligned with
    sids."""
    barrier = threading.Barrier(len(sids))
    out, errs = [None] * len(sids), []

    def adv(i):
        try:
            barrier.wait(timeout=10)
            out[i] = _post_stream(server, {"session": sids[i],
                                           "image": frames[i].tolist()})
        except Exception as e:  # noqa: BLE001 — surfaced by the caller
            errs.append(e)

    threads = [threading.Thread(target=adv, args=(i,))
               for i in range(len(sids))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    return out


def test_group_nan_row_heals_alone(chaos_group_server):
    """Chaos ``nan`` arm under the BATCHED stream path: one row of the
    coalesced output goes NaN — the sentinel rejects exactly that row,
    it heals through the cold path inside the same advance, and its
    co-batched neighbor keeps its warm result.  Both clients see 200
    with correct flow."""
    server = chaos_group_server
    rng = np.random.RandomState(60)
    seqs = [[rng.rand(32, 48, 3).astype(np.float32) for _ in range(2)]
            for _ in range(2)]
    sids = [_post_stream(server, {"image": fr[0].tolist()})["session"]
            for fr in seqs]
    nonfinite0 = server._robustness["nonfinite"].value
    degraded0 = server.streams.metrics["degraded"].value
    server.faults.force("nan", [1])
    out = _coalesced_advance(server, sids, [fr[1] for fr in seqs])
    assert [r["meta"]["batch_real"] for r in out] == [2, 2]  # coalesced
    # exactly one row was poisoned -> healed cold; the other stayed warm
    assert sorted(r["meta"]["warm"] for r in out) == [False, True]
    for i, r in enumerate(out):
        assert np.isfinite(np.asarray(r["flow"])).all()
        pw = _post_flow(server, seqs[i][0], seqs[i][1])
        np.testing.assert_allclose(np.asarray(r["flow"], np.float32),
                                   np.asarray(pw["flow"], np.float32),
                                   rtol=1e-4, atol=1e-2)
    assert server._robustness["nonfinite"].value == nonfinite0 + 1
    assert server.streams.metrics["degraded"].value == degraded0 + 1
    assert server.engine.compile_misses == 0
    for sid in sids:
        _post_stream(server, {"op": "close", "session": sid})


def test_group_engine_fault_degrades_every_row_cold(chaos_group_server):
    """Chaos ``engine_error`` on the BATCHED call: the whole group
    degrades to per-row cold restarts in the same advance — every
    client sees 200 + warm:false and the pairwise-correct flow (the
    stream path's form of poisoned-batch isolation)."""
    server = chaos_group_server
    rng = np.random.RandomState(61)
    seqs = [[rng.rand(32, 48, 3).astype(np.float32) for _ in range(2)]
            for _ in range(2)]
    sids = [_post_stream(server, {"image": fr[0].tolist()})["session"]
            for fr in seqs]
    degraded0 = server.streams.metrics["degraded"].value
    server.faults.force("engine_error", [1])
    out = _coalesced_advance(server, sids, [fr[1] for fr in seqs])
    assert [r["meta"]["warm"] for r in out] == [False, False]
    for i, r in enumerate(out):
        pw = _post_flow(server, seqs[i][0], seqs[i][1])
        np.testing.assert_allclose(np.asarray(r["flow"], np.float32),
                                   np.asarray(pw["flow"], np.float32),
                                   rtol=1e-4, atol=1e-2)
    assert server.streams.metrics["degraded"].value == degraded0 + 2
    assert server.engine.compile_misses == 0
    for sid in sids:
        _post_stream(server, {"op": "close", "session": sid})


def test_group_session_poison_isolated_by_sentinel(chaos_group_server):
    """Chaos ``session`` arm under the group path: ONE session's slot
    row is NaN-poisoned device-side; the batched gather carries the
    poison into exactly that row's output, the sentinel catches it, and
    only that session degrades — its batch-mate stays warm."""
    server = chaos_group_server
    rng = np.random.RandomState(62)
    seqs = [[rng.rand(32, 48, 3).astype(np.float32) for _ in range(2)]
            for _ in range(2)]
    sids = [_post_stream(server, {"image": fr[0].tolist()})["session"]
            for fr in seqs]
    nonfinite0 = server._robustness["nonfinite"].value
    # corrupt_session rolls once per group row: fire on the FIRST row
    # only (forced outcomes drain in call order)
    server.faults.force("session", [1, 0])
    out = _coalesced_advance(server, sids, [fr[1] for fr in seqs])
    assert sorted(r["meta"]["warm"] for r in out) == [False, True]
    for i, r in enumerate(out):
        assert np.isfinite(np.asarray(r["flow"])).all()
        pw = _post_flow(server, seqs[i][0], seqs[i][1])
        np.testing.assert_allclose(np.asarray(r["flow"], np.float32),
                                   np.asarray(pw["flow"], np.float32),
                                   rtol=1e-4, atol=1e-2)
    assert server._robustness["nonfinite"].value == nonfinite0 + 1
    assert server.engine.compile_misses == 0
    for sid in sids:
        _post_stream(server, {"op": "close", "session": sid})


def _parked_pair(server, seed):
    """Two sessions opened and advanced once, the first then demoted as to
    LRU while parked: (sids, clips of three frames, its Session)."""
    rng = np.random.RandomState(seed)
    seqs = [[rng.rand(32, 48, 3).astype(np.float32) for _ in range(3)]
            for _ in range(2)]
    sids = [_post_stream(server, {"image": fr[0].tolist()})["session"]
            for fr in seqs]
    out = _coalesced_advance(server, sids, [fr[1] for fr in seqs])
    assert [r["meta"]["warm"] for r in out] == [True, True]
    parked = server.streams.store.get(sids[0])
    server.streams.store.demote(parked, "lru")
    return sids, seqs, parked


def _pairwise(server, seqs, out, t=2):
    for i, r in enumerate(out):
        assert np.isfinite(np.asarray(r["flow"])).all()
        pw = _post_flow(server, seqs[i][t - 1], seqs[i][t])
        np.testing.assert_allclose(np.asarray(r["flow"], np.float32),
                                   np.asarray(pw["flow"], np.float32),
                                   rtol=1e-4, atol=1e-2)


def test_group_restart_whose_encode_faults_heals_solo(chaos_group_server):
    """Chaos ``engine_error`` on the encoder pass of a restart at its
    group's place: the slot is given back, the row heals through the solo
    restart in the same advance (cause ``demoted``, 200, the pairwise
    flow), its batch-mate rides the batched call alone and stays warm, and
    the healed session is warm on its next frame."""
    server = chaos_group_server
    m = server.streams.metrics
    sids, seqs, parked = _parked_pair(server, 63)
    before = (m["cold_restarts"].labels("demoted").value,
              m["restarts_batched"].value, m["degraded"].value)
    server.faults.force("engine_error", [1])    # the place's first call
    out = _coalesced_advance(server, sids, [fr[2] for fr in seqs])
    assert [r["meta"]["warm"] for r in out] == [False, True]
    _pairwise(server, seqs, out)
    assert (m["cold_restarts"].labels("demoted").value,
            m["restarts_batched"].value, m["degraded"].value) == (
        before[0] + 1, before[1], before[2])
    assert parked.has_features
    assert server.engine.compile_misses == 0
    for sid in sids:
        _post_stream(server, {"op": "close", "session": sid})


def test_group_poisoned_slot_beside_a_restarted_row(chaos_group_server):
    """Chaos ``session`` arm with a restart in the group: the roll skips the
    session that holds no slot and poisons its batch-mate's; the sentinel
    degrades that row alone, the restarted row is served by the batched
    call, and no answer was gathered from the poison or from zeros."""
    server = chaos_group_server
    m = server.streams.metrics
    sids, seqs, parked = _parked_pair(server, 64)
    before = (m["cold_restarts"].labels("demoted").value,
              m["cold_restarts"].labels("degraded").value,
              m["restarts_batched"].value, m["degraded"].value)
    nonfinite0 = server._robustness["nonfinite"].value
    server.faults.force("session", [1])
    out = _coalesced_advance(server, sids, [fr[2] for fr in seqs])
    assert [r["meta"]["warm"] for r in out] == [False, False]
    _pairwise(server, seqs, out)
    assert (m["cold_restarts"].labels("demoted").value,
            m["cold_restarts"].labels("degraded").value,
            m["restarts_batched"].value, m["degraded"].value) == tuple(
        v + 1 for v in before)
    assert server._robustness["nonfinite"].value == nonfinite0 + 1
    assert parked.has_features
    assert server.engine.compile_misses == 0
    for sid in sids:
        _post_stream(server, {"op": "close", "session": sid})


def test_session_store_demote_all_skips_inflight():
    store = SessionStore(max_sessions=4, ttl_s=60.0)
    a, b = store.open(BUCKET), store.open(BUCKET)
    store.promote(a)
    store.promote(b)
    with b.lock:
        assert store.demote_all() == 1
    # the skipped in-flight session keeps its slot; a's went back
    assert not a.has_features and b.has_features
    assert store.pool.in_use(BUCKET) == 1
    assert store.resident_count() == 2              # records kept
