"""Fleet tests: session-affinity routing, migration-on-death, rolling
weight hot-swap, signal-driven autoscaling, health aggregation.

Two tiers, like the serving suite: pure-logic tests drive the manager /
router / controllers with FAKE replicas (an injectable ``spawn_fn``
returning stub processes — no HTTP, no compiles), and one module-scoped
live fixture runs TWO real in-process FlowServers behind a real router
so the wire-level behaviors (affinity headers, migration flow equality,
hot-swap with zero recompiles) are tested end to end.  The live kill
test runs LAST in this file: it leaves replica 0 permanently dead
(``restart_dead=False`` keeps the fixture deterministic).
"""

import io
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from raft_tpu.fleet import (Autoscaler, FleetConfig, FleetRouter,
                            FleetSessionMap, ReplicaManager, RollingUpdater,
                            fleet_signals)
from raft_tpu.fleet.manager import parse_prom_text
from raft_tpu.fleet.router import NoReplica, status_class
from raft_tpu.serving import FlowServer, ServeConfig

# ---------------------------------------------------------------------------
# fakes
# ---------------------------------------------------------------------------

class FakeProc:
    """Popen-shaped stub; ``die()`` is what a SIGKILL'd child looks like
    to the manager (poll() flips non-None)."""

    def __init__(self, on_stop=None):
        self.returncode = None
        self._on_stop = on_stop

    def poll(self):
        return self.returncode

    def terminate(self):
        self._exit(0)

    def kill(self):
        self._exit(-9)

    def wait(self, timeout=None):
        return self.returncode

    def _exit(self, code):
        if self.returncode is None:
            self.returncode = code
            if self._on_stop is not None:
                self._on_stop()


def fake_fleet(n=2, **overrides):
    """A manager with ``n`` fake 'ready' replicas — no processes, no
    HTTP; the router on top can exercise pick/affinity logic (anything
    that would forward will raise, which the tests want)."""
    kw = dict(replicas=n, health_poll_s=60.0, restart_dead=False,
              spawn_timeout_s=5.0)
    kw.update(overrides)
    config = FleetConfig(**kw)
    spawned = []

    def spawn(rep):
        spawned.append(rep)
        return FakeProc(), f"http://127.0.0.1:{10000 + rep.idx}"

    manager = ReplicaManager(config, out_dir="/tmp", spawn_fn=spawn)
    for _ in range(n):
        manager._spawn_one()
    return config, manager, spawned


# ---------------------------------------------------------------------------
# config + parsing
# ---------------------------------------------------------------------------

def test_fleet_config_validates():
    with pytest.raises(ValueError):
        FleetConfig(replicas=0)
    with pytest.raises(ValueError):
        FleetConfig(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError):
        FleetConfig(replicas=5, max_replicas=2)
    with pytest.raises(ValueError):
        FleetConfig(health_poll_s=0)


def test_parse_prom_text_labels_and_comments():
    text = ("# HELP raft_serving_queue_depth d\n"
            "raft_serving_queue_depth 3\n"
            "raft_serving_queue_limit 16\n"
            'raft_serving_requests_total{status="shed"} 2\n'
            "garbage line without value\n")
    out = parse_prom_text(text)
    assert out["raft_serving_queue_depth"] == 3.0
    assert out['raft_serving_requests_total{status="shed"}'] == 2.0
    assert "# HELP raft_serving_queue_depth d" not in out


def test_status_class_classification():
    assert status_class(200) == "ok"
    assert status_class(429) == "shed"
    assert status_class(503) == "shed"
    assert status_class(504) == "timeout"
    assert status_class(404) == "bad_request"
    assert status_class(500) == "error"


# ---------------------------------------------------------------------------
# least-loaded routing (fake replicas — pure pick logic)
# ---------------------------------------------------------------------------

def test_pick_least_loaded_and_exclude():
    config, manager, _ = fake_fleet(3)
    router = FleetRouter(config, manager)
    r0 = router._pick()
    assert r0.idx == 0                     # tie -> lowest index
    r1 = router._pick()
    assert r1.idx == 1                     # 0 now has an in-flight forward
    r2 = router._pick(exclude={2})
    assert r2.idx in (0, 1)
    router._unpick(r0.idx)
    router._unpick(r1.idx)
    router._unpick(r2.idx)
    assert router.total_inflight() == 0


def test_pick_skips_updating_replica_but_never_sheds():
    config, manager, _ = fake_fleet(2)
    router = FleetRouter(config, manager)
    manager.get(0).updating = True
    for _ in range(3):                     # all picks avoid the updating one
        assert router._pick().idx == 1
    # every replica updating: still route (soft drain must not shed)
    manager.get(1).updating = True
    assert router._pick().idx in (0, 1)


def test_pick_raises_no_replica_when_all_dead():
    config, manager, _ = fake_fleet(2)
    router = FleetRouter(config, manager)
    for rep in manager.replicas():
        rep.state = "dead"
    with pytest.raises(NoReplica):
        router._pick()


def test_scale_to_clamps_and_retires_highest_index():
    config, manager, spawned = fake_fleet(3, max_replicas=4)
    manager.scale_to(1)
    states = {r.idx: r.state for r in manager.replicas()}
    assert states[0] in ("ready", "starting")
    assert states[1] == "stopped" and states[2] == "stopped"
    assert manager.desired == 1
    manager.scale_to(99)                   # clamped to max_replicas
    assert manager.desired == 4
    assert manager.ready_count() == 4
    assert manager.scale_to(0) == 1        # clamped to min_replicas


def test_dead_replica_respawns_to_desired():
    config, manager, spawned = fake_fleet(2, restart_dead=True)
    manager.get(0).proc.kill()
    manager.poll_once()
    assert manager.get(0).state == "dead"
    # the respawn runs on a thread; wait for the replacement record
    for _ in range(100):
        if manager.ready_count() == 2:
            break
        import time
        time.sleep(0.05)
    assert manager.ready_count() == 2
    assert manager.restarts == 1
    assert {r.idx for r in manager.routable()} == {1, 2}


# ---------------------------------------------------------------------------
# session map
# ---------------------------------------------------------------------------

def test_session_map_create_get_remove_reap():
    m = FleetSessionMap()
    frame = np.zeros((1, 4, 4, 3), np.float32)
    s = m.create(0, "backend-1", frame)
    assert m.get(s.rsid) is s
    assert m.count() == 1
    assert [x.rsid for x in m.on_replica(0)] == [s.rsid]
    assert m.on_replica(1) == []
    s.last_used -= 7200.0
    assert m.reap(ttl_s=3600.0) == 1
    assert m.get(s.rsid) is None
    assert m.remove("nope") is None


# ---------------------------------------------------------------------------
# autoscaler hysteresis (synthetic signal traces, fake clock)
# ---------------------------------------------------------------------------

def _mk_autoscaler(signals, **cfg_overrides):
    kw = dict(replicas=2, min_replicas=1, max_replicas=3, up_after=2,
              down_after=3, cooldown_s=100.0, health_poll_s=60.0,
              restart_dead=False)
    kw.update(cfg_overrides)
    config, manager, _ = fake_fleet(2, **{k: v for k, v in kw.items()
                                          if k != "replicas"})
    clock = {"t": 0.0}
    it = iter(signals)
    scaler = Autoscaler(config, manager,
                        signals_fn=lambda: next(it),
                        now_fn=lambda: clock["t"])
    return scaler, manager, clock


CALM = {"burn": 0.0, "queue_frac": 0.0, "breaker_open": False,
        "shed_rate": 0.0}
HOT = {"burn": 2.0, "queue_frac": 0.9, "breaker_open": False,
       "shed_rate": 0.0}


def test_autoscaler_up_needs_consecutive_pressure():
    # hot, calm, hot: the calm poll resets the streak -> no scale event
    scaler, manager, _ = _mk_autoscaler([HOT, CALM, HOT])
    assert scaler.step() is None
    assert scaler.step() is None
    assert scaler.step() is None
    assert manager.desired == 2


def test_autoscaler_scales_up_then_respects_cooldown():
    scaler, manager, clock = _mk_autoscaler([HOT] * 6)
    assert scaler.step() is None
    assert scaler.step() == "up"
    assert manager.desired == 3
    # still hot, but inside the cooldown window: no second event
    assert scaler.step() is None
    assert scaler.step() is None
    clock["t"] = 200.0                      # past cooldown
    assert scaler.step() is None            # streak restarted after _fire
    assert scaler.step() is None            # ... and desired==max: no up
    assert manager.desired == 3


def test_autoscaler_scales_down_slowly_and_floors():
    sig = [CALM] * 10
    scaler, manager, clock = _mk_autoscaler(sig)
    assert scaler.step() is None
    assert scaler.step() is None
    assert scaler.step() == "down"          # down_after=3 calm polls
    assert manager.desired == 1
    clock["t"] = 1000.0
    for _ in range(5):
        assert scaler.step() is None        # min_replicas floor holds
    assert manager.desired == 1


def test_autoscaler_shed_and_breaker_count_as_pressure():
    shed = dict(CALM, shed_rate=3.0)
    breaker = dict(CALM, breaker_open=True)
    scaler, manager, _ = _mk_autoscaler([shed, breaker])
    assert scaler.step() is None
    assert scaler.step() == "up"


def test_fleet_signals_aggregate_and_shed_rate_is_a_delta():
    config, manager, _ = fake_fleet(2)
    manager.get(0).prom = {
        "raft_slo_burn_rate{objective=\"pair\"}": 0.4,
        "raft_serving_queue_depth": 8.0, "raft_serving_queue_limit": 16.0,
        'raft_serving_requests_total{status="shed"}': 5.0}
    manager.get(1).prom = {
        "raft_slo_burn_rate{objective=\"pair\"}": 1.5,
        "raft_serving_queue_depth": 0.0, "raft_serving_queue_limit": 16.0,
        "raft_breaker_state": 2.0}
    prev = {}
    sig = fleet_signals(manager, prev)
    assert sig["burn"] == 1.5               # max over replicas
    assert sig["queue_frac"] == pytest.approx(0.25)  # mean of 0.5, 0.0
    assert sig["breaker_open"] is True
    assert sig["shed_rate"] == 0.0          # first poll: no baseline yet
    manager.get(0).prom['raft_serving_requests_total{status="shed"}'] = 9.0
    assert fleet_signals(manager, prev)["shed_rate"] == 4.0
    assert fleet_signals(manager, prev)["shed_rate"] == 0.0


# ---------------------------------------------------------------------------
# rolling updater (fake push)
# ---------------------------------------------------------------------------

def test_rolling_update_aborts_on_failure_and_clears_drain_flags():
    config, manager, _ = fake_fleet(3)
    updater = RollingUpdater(manager)
    seen_updating = []

    def push(rep, body, tag):
        seen_updating.append((rep.idx, rep.updating))
        if rep.idx == 1:
            return 409, {"error": "param tree structure differs"}
        return 200, {"weights": {"version": 2, "tag": tag}}

    updater._push = push
    results = updater.roll(b"npz-bytes", tag="v2")
    assert [r["status"] for r in results] == ["reloaded", "failed",
                                              "skipped"]
    assert results[1]["http_status"] == 409
    # each replica was soft-drained exactly while its push ran...
    assert seen_updating == [(0, True), (1, True)]
    # ... and released afterwards, even on the failure path
    assert all(not r.updating for r in manager.replicas())


# ---------------------------------------------------------------------------
# scrape history, replica skew, fleet metrics rollup (fake replicas)
# ---------------------------------------------------------------------------

def _lat_scrape(pairs, b01, b1):
    """A /metrics-shaped flat dict with ``b01`` observations <= 0.1s and
    ``b1 - b01`` in (0.1, 1]."""
    return {"raft_serving_pairs_total": float(pairs),
            'raft_serving_request_latency_seconds_bucket{le="0.1"}':
                float(b01),
            'raft_serving_request_latency_seconds_bucket{le="1"}': float(b1),
            'raft_serving_request_latency_seconds_bucket{le="+Inf"}':
                float(b1),
            "raft_serving_request_latency_seconds_sum": float(b1) * 0.05,
            "raft_serving_request_latency_seconds_count": float(b1)}


def _poll_scrapes(router, manager, scrapes):
    """Install per-replica scrapes and fire the manager's poll callback
    the way the poll thread does."""
    for idx, flat in scrapes.items():
        rep = manager.get(idx)
        rep.prom = flat
        router._replica_polled(rep)


def test_router_skew_detection_steering_and_clear():
    """One replica serving 10x-slower p95s than its siblings is judged
    skewed (cross-ring replica_skew over the scrape history), _pick
    steers new work away SOFTLY (still picked when nothing else is
    routable), and the verdict clears when its latency rejoins the
    fleet."""
    config, manager, _ = fake_fleet(3)
    router = FleetRouter(config, manager)
    # scrape 1: all counters at zero (the baseline sample)
    _poll_scrapes(router, manager, {i: _lat_scrape(0, 0, 0)
                                    for i in range(3)})
    assert router.skewed() == [] and router.skew_count() == 0
    # scrape 2: replicas 0/1 fast (all obs <= 0.1s), replica 2 slow
    _poll_scrapes(router, manager, {0: _lat_scrape(100, 100, 100),
                                    1: _lat_scrape(100, 100, 100),
                                    2: _lat_scrape(100, 0, 100)})
    assert router.skewed() == [2]
    assert router.skew_count() == 1
    assert sorted(router.fleet_history.sources()) == ["0", "1", "2"]
    # soft steering: new picks avoid the skewed replica...
    picked = set()
    for _ in range(4):
        r = router._pick()
        picked.add(r.idx)
        router._unpick(r.idx)
    assert 2 not in picked
    # ...but a fully-skewed fleet still serves (preference, not outage)
    for rep in manager.replicas():
        if rep.idx != 2:
            rep.state = "dead"
    assert router._pick().idx == 2
    router._unpick(2)
    for rep in manager.replicas():
        rep.state = "ready"
    # recovery: replica 2's recent window turns fast -> verdict clears
    _poll_scrapes(router, manager, {0: _lat_scrape(200, 200, 200),
                                    1: _lat_scrape(200, 200, 200),
                                    2: _lat_scrape(200, 200, 200)})
    assert router.skewed() == []
    # death: the ring and any verdict are dropped with the replica
    router._replica_died(manager.get(2))
    assert "2" not in router.fleet_history.sources()


def test_router_skew_needs_three_replicas():
    config, manager, _ = fake_fleet(2)
    router = FleetRouter(config, manager)
    _poll_scrapes(router, manager, {0: _lat_scrape(0, 0, 0),
                                    1: _lat_scrape(0, 0, 0)})
    _poll_scrapes(router, manager, {0: _lat_scrape(100, 100, 100),
                                    1: _lat_scrape(100, 0, 100)})
    # with two replicas either could be the outlier: never judge
    assert router.skewed() == []


def test_render_fleet_metrics_relabels_and_rolls_up():
    config, manager, _ = fake_fleet(3)
    router = FleetRouter(config, manager)
    manager.get(0).prom = {"raft_serving_pairs_total": 300.0,
                           'raft_serving_requests_total{status="ok"}': 30.0,
                           "raft_serving_queue_depth": 2.0}
    manager.get(1).prom = {"raft_serving_pairs_total": 100.0,
                           'raft_serving_requests_total{status="ok"}': 10.0,
                           "raft_serving_queue_depth": 1.0}
    manager.get(2).prom = {"raft_serving_pairs_total": 999.0}
    manager.get(2).state = "dead"           # non-routable: excluded
    text = router.render_fleet_metrics()
    assert 'raft_serving_pairs_total{replica="0"} 300' in text
    assert 'raft_serving_pairs_total{replica="1"} 100' in text
    assert 'raft_serving_pairs_total{replica="all"} 400' in text
    # existing labels merge after the replica label
    assert ('raft_serving_requests_total{replica="0",status="ok"} 30'
            in text)
    assert ('raft_serving_requests_total{replica="all",status="ok"} 40'
            in text)
    assert 'replica="2"' not in text
    assert text.endswith("\n")
    # the round-trip through the fleet parser keeps the values
    parsed = parse_prom_text(text)
    assert parsed['raft_serving_queue_depth{replica="all"}'] == 3.0


def test_fleet_signals_count_anomaly_sentinels():
    config, manager, _ = fake_fleet(2)
    manager.get(0).prom = {'raft_anomaly_active{rule="p95_drift"}': 1.0,
                           'raft_anomaly_active{rule="queue_growth"}': 0.0,
                           "raft_serving_queue_limit": 16.0}
    manager.get(1).prom = {'raft_anomaly_active{rule="p95_drift"}': 0.0,
                           "raft_serving_queue_limit": 16.0}
    sig = fleet_signals(manager, {})
    assert sig["anomaly"] == 1.0
    manager.get(0).prom['raft_anomaly_active{rule="p95_drift"}'] = 0.0
    assert fleet_signals(manager, {})["anomaly"] == 0.0


def test_autoscaler_anomaly_is_pressure_and_blocks_scale_down():
    # a firing sentinel anywhere in the fleet scales up...
    anomalous = dict(CALM, anomaly=1.0)
    scaler, manager, _ = _mk_autoscaler([anomalous, anomalous])
    assert scaler.step() is None
    assert scaler.step() == "up"
    # ...and an otherwise-calm fleet with a sentinel firing never
    # scales down (calm requires anomaly == 0)
    scaler2, manager2, _ = _mk_autoscaler([anomalous] * 5, replicas=2)
    manager2.scale_to(2)
    for _ in range(5):
        scaler2.step()
    assert manager2.desired > 1


# ---------------------------------------------------------------------------
# live fleet: two real FlowServers behind a real router
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def live_fleet(tmp_path_factory):
    """Two real in-process replicas (own engines, shared params) behind
    a real FleetRouter.  ``restart_dead=False`` so the kill test (last
    in this file) is deterministic."""
    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models import init_raft

    out = tmp_path_factory.mktemp("fleet")
    config = RAFTConfig.small_model(iters=1)
    params = init_raft(init_rng(), config)
    sconfig = ServeConfig(buckets=((32, 48),), max_batch=1,
                          batch_steps=(1,), max_wait_ms=5.0,
                          queue_depth=16, default_deadline_ms=30_000.0,
                          port=0, max_sessions=2, session_ttl_s=600.0)
    servers = {}

    def spawn(rep):
        server = FlowServer(config, params, sconfig)
        server.start()
        servers[rep.idx] = server
        return FakeProc(on_stop=lambda: server.stop(drain=False)), server.url

    fconfig = FleetConfig(replicas=2, health_poll_s=60.0,
                          restart_dead=False, forward_retries=2,
                          trace_sample=1.0)
    manager = ReplicaManager(fconfig, out_dir=str(out), spawn_fn=spawn)
    for _ in range(2):
        manager._spawn_one()
    manager.poll_once()                     # first healthz/metrics scrape
    router = FleetRouter(fconfig, manager, out_dir=str(out))
    router.updater = RollingUpdater(manager, metrics=router.metrics)
    router.start()
    yield router, manager, servers, params
    router.stop()
    for server in servers.values():
        try:
            server.stop(drain=False)
        except Exception:
            pass


def _post(router, path, payload, headers=None, raw=None):
    data = raw if raw is not None else json.dumps(payload).encode()
    h = {"Content-Type": ("application/octet-stream" if raw is not None
                          else "application/json")}
    h.update(headers or {})
    req = urllib.request.Request(router.url + path, data=data, headers=h)
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, dict(r.getheaders()), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def _frames(seed, n):
    rng = np.random.RandomState(seed)
    return [rng.rand(32, 48, 3).astype(np.float32) for _ in range(n)]


def test_fleet_healthz_ok_and_replica_states(live_fleet):
    router, manager, servers, _ = live_fleet
    status, payload = router.health()
    assert status == 200
    assert payload["status"] == "ok"
    assert payload["ready"] == 2 and payload["desired"] == 2
    assert [r["state"] for r in payload["replicas"]] == ["ready", "ready"]
    # per-replica weight provenance surfaces through the aggregation
    assert all(r["weights"]["version"] >= 1 for r in payload["replicas"])


def test_fleet_flow_routes_and_tags_replica(live_fleet):
    router, manager, servers, _ = live_fleet
    f1, f2 = _frames(60, 2)
    st, headers, body = _post(router, "/v1/flow",
                              {"image1": f1.tolist(), "image2": f2.tolist()})
    assert st == 200
    assert headers["X-Raft-Replica"] in ("0", "1")
    assert np.asarray(json.loads(body)["flow"]).shape == (32, 48, 2)
    assert router.metrics["requests"].labels("ok").value >= 1


def test_fleet_stream_affinity_pins_one_replica(live_fleet):
    router, manager, servers, _ = live_fleet
    frames = _frames(61, 4)
    st, h, body = _post(router, "/v1/stream",
                        {"op": "open", "image": frames[0].tolist()})
    assert st == 200
    sid = json.loads(body)["session"]
    pinned = h["X-Raft-Replica"]
    hit = set()
    for fr in frames[1:]:
        st, h, body = _post(router, "/v1/stream",
                            {"session": sid, "image": fr.tolist()})
        assert st == 200
        assert json.loads(body)["meta"]["migrated"] is False
        hit.add(h["X-Raft-Replica"])
    assert hit == {pinned}                  # every advance, same replica
    st, _, _ = _post(router, "/v1/stream", {"op": "close", "session": sid})
    assert st == 200
    assert router.sessions.count() == 0


def test_fleet_stream_unknown_session_is_404(live_fleet):
    router, _, _, _ = live_fleet
    frame = _frames(62, 1)[0]
    st, _, body = _post(router, "/v1/stream",
                        {"session": "deadbeef", "image": frame.tolist()})
    assert st == 404
    assert "unknown session" in json.loads(body)["error"]


def test_fleet_hot_swap_rolls_without_drops_or_recompiles(live_fleet):
    """The rolling-update acceptance, in-process: a weight push through
    the router reloads every replica one at a time while a stream keeps
    advancing — zero non-200s, zero compile misses, weight version
    bumped everywhere, and the warm executables still serve."""
    from raft_tpu.convert.weights import save_params_npz

    router, manager, servers, params = live_fleet
    frames = _frames(63, 6)
    st, h, body = _post(router, "/v1/stream",
                        {"op": "open", "image": frames[0].tolist()})
    assert st == 200
    sid = json.loads(body)["session"]
    misses0 = {i: s.engine.compile_misses for i, s in servers.items()}
    versions0 = {i: s.engine.weight_info()["version"]
                 for i, s in servers.items()}
    buf = io.BytesIO()
    save_params_npz(params, buf)
    statuses = []
    done = threading.Event()

    def advance_loop():
        for fr in frames[1:]:
            st, _, _ = _post(router, "/v1/stream",
                             {"session": sid, "image": fr.tolist()})
            statuses.append(st)
        done.set()

    t = threading.Thread(target=advance_loop)
    t.start()
    st, _, body = _post(router, "/admin/reload", None, raw=buf.getvalue(),
                        headers={"X-Raft-Weight-Tag": "test-roll"})
    assert done.wait(60.0)
    t.join(5.0)
    assert st == 200
    result = json.loads(body)
    assert result["status"] == "reloaded"
    assert [r["status"] for r in result["replicas"]] == ["reloaded"] * 2
    assert statuses == [200] * (len(frames) - 1)        # zero drops
    for i, server in servers.items():
        assert server.engine.compile_misses == misses0[i]  # zero recompiles
        info = server.engine.weight_info()
        assert info["version"] == versions0[i] + 1
        assert info["tag"] == "test-roll"
    assert router.metrics["hot_swaps"].value == 2.0
    # swapped weights still serve a correct pairwise request
    f1, f2 = _frames(64, 2)
    st, _, body = _post(router, "/v1/flow",
                        {"image1": f1.tolist(), "image2": f2.tolist()})
    assert st == 200
    assert np.isfinite(np.asarray(json.loads(body)["flow"])).all()
    _post(router, "/v1/stream", {"op": "close", "session": sid})


def test_fleet_hot_swap_rejects_mismatched_tree(live_fleet):
    """A wrong-layout npz must 409 on the FIRST replica and abort the
    roll — no replica past the failure touches its weights."""
    router, manager, servers, _ = live_fleet
    versions0 = {i: s.engine.weight_info()["version"]
                 for i, s in servers.items()}
    buf = io.BytesIO()
    np.savez(buf, **{"cnet/conv1/w": np.zeros((3, 3), np.float32)})
    st, _, body = _post(router, "/admin/reload", None, raw=buf.getvalue())
    assert st == 409
    result = json.loads(body)
    assert result["status"] == "partial"
    assert result["replicas"][0]["status"] == "failed"
    assert [r["status"] for r in result["replicas"][1:]] == ["skipped"]
    for i, server in servers.items():
        assert server.engine.weight_info()["version"] == versions0[i]


def test_fleet_metrics_and_history_endpoints(live_fleet):
    """GET /metrics/fleet re-labels every replica's cached scrape with
    replica=<idx> plus replica="all" rollups; GET /debug/history serves
    the per-source derived series + the skew verdict list.  Both are
    built from the manager's cached polls — no replica round-trips at
    request time."""
    router, manager, servers, _ = live_fleet
    manager.poll_once()                     # fresh scrape -> on_poll ingest
    manager.poll_once()                     # second sample: rates derivable
    with urllib.request.urlopen(router.url + "/metrics/fleet") as r:
        assert r.status == 200
        assert "text/plain" in r.headers["Content-Type"]
        text = r.read().decode()
    for rep in ("0", "1", "all"):
        assert f'raft_serving_queue_limit{{replica="{rep}"}}' in text, rep
    parsed = parse_prom_text(text)
    assert parsed['raft_serving_queue_limit{replica="all"}'] == \
        parsed['raft_serving_queue_limit{replica="0"}'] \
        + parsed['raft_serving_queue_limit{replica="1"}']
    with urllib.request.urlopen(router.url + "/debug/history") as r:
        body = json.loads(r.read())
    assert set(body["sources"]) == {"0", "1"}
    assert body["skewed"] == []             # two healthy replicas
    series = body["sources"]["0"]
    assert "pairs_per_s" in series and "p95_ms" in series
    assert len(series["t"]) >= 1            # two ingests -> >= 1 point
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(router.url + "/debug/history?window=junk")
    assert ei.value.code == 400


def test_fleet_kill_migrates_sessions_with_pairwise_flow(live_fleet):
    """The chaos-drill acceptance, in-process: SIGKILL the replica a
    session is pinned to; the next advance migrates (open(prev) on the
    survivor + re-pin + forward) and its flow equals the pairwise answer
    on the same frames — the repo's cold==pairwise bar (test_chaos.py).
    Runs LAST: replica 0 or 1 stays dead afterwards."""
    router, manager, servers, _ = live_fleet
    frames = _frames(65, 3)
    st, h, body = _post(router, "/v1/stream",
                        {"op": "open", "image": frames[0].tolist()})
    assert st == 200
    sid = json.loads(body)["session"]
    pinned = int(h["X-Raft-Replica"])
    st, _, body = _post(router, "/v1/stream",
                        {"session": sid, "image": frames[1].tolist()})
    assert st == 200

    manager.kill(pinned)                    # SIGKILL, no drain, no warning
    manager.poll_once()                     # failure detection
    assert manager.get(pinned).state == "dead"

    st, h, body = _post(router, "/v1/stream",
                        {"session": sid, "image": frames[2].tolist()})
    assert st == 200                        # the client never saw the death
    resp = json.loads(body)
    assert resp["meta"]["migrated"] is True
    survivor = resp["meta"]["replica"]
    assert survivor != pinned
    # flow equality: the migrated advance replayed frames[1] as the new
    # open, so its flow on frames[2] is the cold path == pairwise answer
    st, _, body = _post(router, "/v1/flow",
                        {"image1": frames[1].tolist(),
                         "image2": frames[2].tolist()})
    assert st == 200
    np.testing.assert_allclose(np.asarray(resp["flow"], np.float32),
                               np.asarray(json.loads(body)["flow"],
                                          np.float32),
                               rtol=1e-4, atol=1e-2)
    assert router.metrics["migrations"].value == 1.0
    # aggregation reflects the dead replica
    status, payload = router.health()
    assert status == 200 and payload["status"] == "degraded"
    assert payload["ready"] == 1
    # the session stays healthy on the survivor (now warm there)
    st, h, body = _post(router, "/v1/stream",
                        {"session": sid, "image": frames[1].tolist()})
    assert st == 200
    assert json.loads(body)["meta"]["migrated"] is False
    assert int(h["X-Raft-Replica"]) == survivor
    _post(router, "/v1/stream", {"op": "close", "session": sid})
