"""The cell ``things-stream-sessions`` and video sessions as a supported
deployment: the cell's files say what ISSUE 39 asked for; a live server's
session through ``POST /v1/stream`` agrees with ``benchmark/references/
warm.py`` walked over the same frames, in float32 to round-off and in
bfloat16 under the configuration's limit, which the e4m3 control is over; the
reference's forward projection against the program's; ``benchmark/drivers/
sessions.py`` run by ``run.py`` on the CPU to ``correct: true``, and to
``correct: false`` with one frame of a session altered; every stage and
counter the stream path gained grows on a stream batch; and the new readers
on a window made by hand."""

import http.client
import importlib.util
import io
import json
import os
import shutil
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL = "things-stream-sessions"
CONFIG = "raft-things-1080p-stream"
MIX = "davis1080p-sessions"
# PR 41's cell runs the same service with more live sessions than slots and
# is appended after this one wherever a metric's reader reads right there
CHURN = "things-stream-churn"
INT8 = "things-stream-int8-pool"         # PR 45's, after the churn cell
CHURN_LEFT_OUT = ("gru_roofline", "corr_window_roofline",
                  "stream_staged_ahead_share")
NEW_METRICS = {
    "stream_warm_share": "server", "stream_fnet_passes_per_pair": "engine",
    "stream_sentinel_ms": "server", "stream_seed_ms": "server",
    "stream_commit_ms": "server", "slot_io_ms": "kernels",
    "slot_io_roofline": "kernels",
    # PR 40: the batched advances ride the batcher's pipeline
    "stream_staged_ahead_share": "server"}
# the accepted metrics whose readers, unedited, read the stream path
SHARED_METRICS = (
    "batch_fill", "host_path_ms", "compile_misses", "device_idle_share",
    "peak_hbm_gb", "decode_ms", "encode_ms", "deliver_ms", "batch_prep_ms",
    "h2d_ms", "fetch_ms", "host_stall_s", "gru_roofline",
    "corr_window_roofline", "corr_keyblock_share", "corr_bands_per_tile",
    "corr_steps_per_tile", "stage_unmapped_share")
# and those that would read wrong there (PERF.md §3 says why each)
NOT_LISTED = (
    "update_ms", "outside_loop_ms", "idle_host_prep_ms", "idle_h2d_ms",
    "idle_fetch_ms", "idle_unnamed_share", "batch_staged_ahead_share",
    "batcher_serial_ms", "batcher_cpu_ms", "batcher_offcpu_ms",
    "deliver_sentinel_ms", "deliver_offcpu_ms", "corr_ms", "corr_l0_ms",
    "corr_pooled_ms", "encoders_ms", "upsample_ms", "corr_lookup_roofline")


@pytest.fixture(scope="module")
def bench_modules():
    """The benchmark's own modules, importable by their bare names as
    ``run.py`` imports them (the path stays: the driver imports
    ``references.warm`` when it is loaded)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import check
    import costs
    import inputs
    import readers
    import stage_cpu  # noqa: F401
    import stages  # noqa: F401
    import system
    import tracered
    import weights
    return {"check": check, "costs": costs, "inputs": inputs,
            "readers": readers, "system": system, "tracered": tracered,
            "weights": weights}


@pytest.fixture(scope="module")
def run(bench_modules):
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


@pytest.fixture(scope="module")
def driver(run):
    return run.load_named(BENCH, "drivers", "sessions", "the test's driver")


@pytest.fixture(scope="module")
def warm(run):
    return run.load_named(BENCH, "references", "warm", "the test's reference")


def _serve_args(config, **replace):
    """The configuration's ``serve_args`` with the values of the flags in
    ``replace`` (``max_batch="2"``) exchanged."""
    argv = [str(a) for a in config["serve_args"]]
    for flag, value in replace.items():
        argv[argv.index("--" + flag.replace("_", "-")) + 1] = value
    return argv


# ------------------------------------------------------------ the cell's data

@pytest.mark.parametrize("what,want", [
    ("config", CONFIG), ("traffic", MIX), ("chips", 1)])
def test_the_cell_is_the_one_the_issue_names(cell, what, want):
    assert cell["entry"][what] == want
    assert len(cell["entry"]["why"]) <= 200


def test_the_traffic_is_24_lockstep_sessions_of_full_hd_frames(cell):
    t, f = cell["traffic"], cell["file"]
    assert (t["loop"], t["endpoint"], t["driver"]) == (
        "closed", "/v1/stream", "sessions")
    assert (t["height"], t["width"], t["clips"], t["max_shift"]) == (
        1080, 1920, 4, 12)
    assert t["session_frames"] == [24, 48]
    assert t["first_session_frames"] == [8, 48]
    assert t["kept_frames"] == [2, 3, 4]
    serve = cell["config"]["serve_args"]
    max_batch = int(serve[serve.index("--max-batch") + 1])
    assert f["clients"] == 3 * max_batch == 24
    assert f["warm_total_seconds"] == 20 and f["trace_seconds"] == 12
    assert f["trace_seconds"] <= 0.4 * cell["bench"]["run_seconds"]


def test_the_configuration_is_raft_things_1080p_with_sessions(cell, run):
    """Every width and the precision of ``raft-things-1080p``; what differs
    is the sessions, the reference, what was assumed, and the flow head's
    damping: a tenth, so that a warm start's carried flow stays inside a
    tile's band of key rows over a window (PERF.md §6)."""
    other = run.load_json(os.path.join(BENCH, "configs",
                                       "raft-things-1080p.json"))
    cfg = cell["config"]
    for key in ("small", "fnet_dim", "hidden_dim", "context_dim",
                "corr_levels", "corr_radius", "iters", "parameters",
                "program", "precision"):
        assert cfg[key] == other[key], key
    assert cfg["weights"] == {"flow_head_scale": 0.0005}
    assert other["weights"] == {"flow_head_scale": 0.005}
    assert cfg["parameters"] == 5257536 and cfg["iters"] == 12
    want = list(other["serve_args"])
    i = want.index("--max-sessions")
    want[i + 1:i + 2] = ["32", "--session-ttl-s", "3600"]
    assert cfg["serve_args"] == want
    assert cfg["check"]["reference"] == "warm"
    assert cfg["check"]["own_precision"] == "bfloat16"
    assert cfg["check"]["sample"] == 3
    assert cfg["check"]["ratio_limit"] == 2.5     # PERF.md §4 has both ranges
    assert cell["cfg_entry"]["reduced"] == cfg["reduced"] == []
    assert len(cell["cfg_entry"]["source"]) <= 200
    assert "warm_start" in cell["cfg_entry"]["source"]
    assert any("session lengths" in a for a in cfg["assumed"])


def test_serve_warms_fifteen_executables_and_the_pool_fits(cell):
    from raft_tpu import cli
    from raft_tpu.lint import budget
    from raft_tpu.serving.config import (ServeConfig, enumerate_warmup_grid,
                                         parse_buckets)
    args = cli.parse_args(["-m", "serve"] + _serve_args(cell["config"]))
    rconfig = cli._make_config(args)
    for key, value in cell["config"]["program"].items():
        assert getattr(rconfig, key) == value, key
    sconfig = ServeConfig(buckets=parse_buckets(args.buckets),
                          max_batch=args.max_batch,
                          max_sessions=args.max_sessions,
                          session_ttl_s=args.session_ttl_s)
    kinds = [k[0] for k in enumerate_warmup_grid(rconfig, sconfig,
                                                 stream=True)]
    assert sorted(kinds) == sorted(
        ["pair", "sbatch", "scommit"] * 4 + ["encode", "stream", "szero"])
    # a slot: one frame's fnet and cnet maps in bfloat16, the seed in float32
    report = budget.analyze(rconfig, sconfig, device_kind="tpu-v5e")
    assert not report["violations"]
    slot = 135 * 240 * (256 + 256) * 2 + 135 * 240 * 2 * 4
    assert report["totals"]["per_session_bytes"] == slot
    assert report["buckets"][0]["pool_bytes"] == 33 * slot


@pytest.mark.parametrize("metric", sorted(NEW_METRICS) + list(SHARED_METRICS)
                         + ["pairs_per_s", "setup_s"])
def test_listed_gives_the_cell_its_metrics(cell, run, metric):
    bench = cell["bench"]
    reporting = {m["name"] for m in bench["end_to_end"]
                 if run.listed(m, CELL, set())}
    assert reporting == {"pairs_per_s", "setup_s"}
    entry = run.find(bench["end_to_end"] + bench["per_layer"], metric,
                     "metric")
    assert run.listed(entry, CELL, reporting)
    later = [] if metric in CHURN_LEFT_OUT else (
        [CHURN] if metric == "slot_io_roofline" else [CHURN, INT8])
    if metric in NEW_METRICS:
        assert entry["workloads"] == [CELL] + later
        assert entry["layer"] == NEW_METRICS[metric]
        assert entry["moves"] == "pairs_per_s"
        base = os.path.join(BENCH, "layer_metrics", metric)
        assert os.path.exists(base + ".json") and os.path.exists(base + ".py")
    elif "workloads" in entry:
        # appended, after the cells that were there (and before PR 41's)
        i = entry["workloads"].index(CELL)
        assert entry["workloads"][i + 1:] == later
        assert "things-1080p-closed" in entry["workloads"][:i]


@pytest.mark.parametrize("metric", NOT_LISTED)
def test_a_metric_that_would_read_wrong_is_not_given_the_cell(cell, run,
                                                              metric):
    entry = run.find(cell["bench"]["per_layer"], metric, "metric")
    assert not run.listed(entry, CELL, {"pairs_per_s", "setup_s"})


# ------------------------------------------- the forward projection, both ways

def _program_projection(flow_lr):
    from raft_tpu.utils.frame_utils import forward_interpolate
    return forward_interpolate(np.asarray(flow_lr, np.float32))


def _flows(kind, h=24, w=40):
    rng = np.random.default_rng(20260939)
    if kind == "uniform":           # the clip's motion: a constant velocity
        f = np.zeros((h, w, 2), np.float32)
        f[..., 0], f[..., 1] = 1.4, -0.7
    elif kind == "rough":           # seeded weights' flow: collisions, holes
        f = rng.normal(0.0, 2.5, (h, w, 2)).astype(np.float32)
    elif kind == "out-of-frame":    # a third of the pixels leave the frame
        f = rng.normal(0.0, 0.5, (h, w, 2)).astype(np.float32)
        f[:, : w // 3, 0] -= w
    else:                           # nothing lands anywhere
        f = np.full((h, w, 2), 3.0 * w, np.float32)
    return f


@pytest.mark.parametrize("kind,most_differ", [
    ("uniform", 0.0), ("rough", 0.05), ("out-of-frame", 0.05),
    ("all-out", 0.0)])
def test_reference_projection_against_the_programs(warm, kind, most_differ):
    """Every pixel that some pixel lands on holds the same average in both;
    the pixels nothing lands on are filled from the exact nearest hit by the
    reference and by OpenCV's 3x3-mask distance transform by the program
    (``frame_utils.py``): the pixels where the two fills took another hit
    are COUNTED, and are few."""
    f = _flows(kind)
    ours, theirs = warm.forward_interpolate(f), _program_projection(f)
    assert ours.shape == theirs.shape == f.shape
    assert ours.dtype == np.float32 and np.isfinite(ours).all()
    h, w = f.shape[:2]
    ys, xs = np.mgrid[0:h, 0:w]
    tx, ty = xs + f[..., 0], ys + f[..., 1]
    keep = (tx > 0) & (tx < w) & (ty > 0) & (ty < h)
    hit = np.zeros((h, w), bool)
    hit[np.clip(np.rint(ty[keep]), 0, h - 1).astype(int),
        np.clip(np.rint(tx[keep]), 0, w - 1).astype(int)] = True
    np.testing.assert_allclose(ours[hit], theirs[hit], rtol=0, atol=1e-6)
    differ = (np.abs(ours - theirs).max(-1) > 1e-6) & ~hit
    assert differ.sum() <= most_differ * h * w, int(differ.sum())
    if kind == "uniform":
        # the whole field moved: the strip it left is filled with its flow
        np.testing.assert_allclose(ours, f, atol=1e-6)
    if kind == "all-out":
        assert not hit.any() and not ours.any() and not theirs.any()
    if kind in ("rough", "out-of-frame"):
        assert 0 < (~hit).sum() < h * w      # there was something to fill
        # a filled pixel holds the flow of a hit at the least distance
        hit_rc = np.argwhere(hit)
        for r, c in np.argwhere(~hit)[:50]:
            d2 = ((hit_rc - (r, c)) ** 2).sum(-1)
            nearest = hit_rc[d2 == d2.min()]
            assert any(np.allclose(ours[r, c], ours[a, b], atol=1e-6)
                       for a, b in nearest)


@pytest.mark.parametrize("lacks", [
    ("raft.stream.sentinel", "raft.stream.seed", "raft.stream.commit"),
    ("raft.stream.seed",), ()])
def test_the_driver_ends_the_run_on_a_program_off_the_measured_path(
        driver, run, monkeypatch, lacks):
    """A program whose ``/v1/stream`` has no ``raft.stream.*`` host stage
    (every commit before PR 39) cannot be read in the cell: loading the
    driver ends the run with one line and no result, before any server is
    built, as a name with no file does; this program passes."""
    from raft_tpu.telemetry import trace
    stages = {k: v for k, v in trace.HOST_STAGES.items() if k not in lacks}
    if not lacks:
        assert driver.require_measured_stream(stages) is None
        return
    with pytest.raises(SystemExit) as err:
        driver.require_measured_stream(stages)
    assert "measured path" in str(err.value.code)
    assert all(name in str(err.value.code) for name in lacks)
    monkeypatch.setattr(trace, "HOST_STAGES", stages)
    monkeypatch.setitem(sys.modules, driver.__name__, driver)   # put back
    with pytest.raises(SystemExit):
        run.load_named(BENCH, "drivers", "sessions", "a parent's run")


def test_advance_body_carries_its_session_in_a_body_encoded_once(
        driver, bench_modules):
    """The frame is encoded once; an advance's body is that encoding with the
    session's 32 hex digits written in, a valid npz that the server's own
    parser reads."""
    from raft_tpu.serving.http import parse_stream_request
    frame = np.arange(16 * 24 * 3, dtype=np.uint8).reshape(16, 24, 3)
    enc = driver.encode_advance(frame)
    for sid in ("0123456789abcdef" * 2, "f" * 32):
        body = driver.advance_body(enc, sid)
        assert len(body) == len(enc.template)
        got = bench_modules["inputs"].npz_load(bytes(body))
        assert str(got["session"]) == sid and got["session"].shape == ()
        np.testing.assert_array_equal(got["image"], frame)
        op, session, image, _ = parse_stream_request(
            bytes(body), "application/octet-stream")
        assert (op, session) == ("advance", sid)
        assert image.shape == (16, 24, 3)
    with pytest.raises(ValueError):
        driver.advance_body(enc, "short")


def test_clips_glide_at_a_constant_velocity_and_lengths_come_from_the_seed(
        driver, cell):
    frames = driver.make_clip(7, 1, 6, 48, 64, 4)
    assert len(frames) == 6 and frames[0].shape == (48, 64, 3)
    assert frames[0].dtype == np.uint8
    # frame k is frame 0 displaced by k x one velocity (but for the noise)
    f0, f1, f2 = (f.astype(np.float32) for f in frames[:3])

    def shift_of(a, b):
        best = min(((np.abs(a[8 + dy:40 + dy, 8 + dx:56 + dx]
                            - b[8:40, 8:56]).mean(), dx, dy)
                    for dx in range(-4, 5) for dy in range(-2, 3)))
        return best[1:]
    assert shift_of(f0, f1) == shift_of(f1, f2) != (0, 0)
    same = driver.make_clip(7, 1, 6, 48, 64, 4)
    assert all((a == b).all() for a, b in zip(frames, same))
    other = driver.make_clip(7, 2, 6, 48, 64, 4)
    assert not (frames[0] == other[0]).all()
    t = cell["traffic"]
    first = [next(driver.session_lengths(5, c, t)) for c in range(24)]
    assert all(8 <= n <= 48 for n in first) and len(set(first)) > 8
    gen = driver.session_lengths(5, 0, t)
    later = [next(gen) for _ in range(9)][1:]
    assert all(24 <= n <= 48 for n in later)
    assert later == [n for _, n in zip(range(9),
                                       driver.session_lengths(5, 0, t))][1:]


# ----------------------- a served session against the reference's own walk

SEED, H, W = 3_900_000_011, 64, 96
MID = (240, 384)
FRAMES = 5                       # an open and four advances: indices 1-4


def _session(cell, bench_modules, driver, warm, h, w, shift):
    """Five frames of a seeded clip, the benchmark's seeded weights, and the
    reference walked over the frames as the driver walks it, in float32, at
    the stated precision and one step below it."""
    weights, check = bench_modules["weights"], bench_modules["check"]
    mcfg = weights.model_cfg(cell["config"])
    wts = weights.make_weights(SEED, mcfg)
    frames = driver.make_clip(SEED, 0, FRAMES, h, w, shift)
    made = driver.Made([frames], [None], [None])
    which = [(0, k) for k in range(1, FRAMES)]
    iters = int(cell["config"]["iters"])
    walk = {p: driver.reference_answers(
        check.forward(warm, wts, mcfg, iters, p), made, which)
        for p in ("float32", "bfloat16", "float8")}
    # and in float32 with the PROGRAM's projection between the calls: the
    # reference's forward pass, apart from the one departure in the fill
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(driver, "forward_interpolate", _program_projection)
        walk["float32, the program's fill"] = driver.reference_answers(
            check.forward(warm, wts, mcfg, iters), made, which)
    return {"weights": wts, "frames": frames, "which": which, "walk": walk,
            "size": (h, w)}


@pytest.fixture(scope="module")
def session(cell, bench_modules, driver, warm):
    return _session(cell, bench_modules, driver, warm, H, W, 2)


@pytest.fixture(scope="module")
def session_mid(cell, bench_modules, driver, warm):
    """The same at 240 x 384 (a 30 x 48 grid), for the comparisons of
    roundings: on an 8 x 12 grid, where a third of the pixels lie at a
    border, the program's bfloat16 activations read 3.5-4.9 times the
    reference's rounded operands from index 2 on, 1.5-2.3 at 15 x 24, 1.5-1.6
    here, 1.1-1.5 at the cell's size (PERF.md §4)."""
    return _session(cell, bench_modules, driver, warm, *MID, 4)


def _post(conn, path, **arrays):
    inputs = sys.modules["inputs"]
    conn.request("POST", path, body=inputs.npz_body(**arrays),
                 headers={"Content-Type": "application/octet-stream",
                          "Accept": "application/octet-stream"})
    resp = conn.getresponse()
    payload = resp.read()
    assert resp.status == 200, payload[:300]
    return inputs.npz_load(payload), resp


def _served_session(cell, bench_modules, session, tmp_path, dtype):
    """The clip's flows as one session through ``POST /v1/stream`` answered
    them: the server ``benchmark/system.py`` builds from the configuration's
    serve arguments at a bucket of the session's size and ``dtype``,
    compiling what the session uses as it goes; and the window's counters."""
    system = bench_modules["system"]
    config = dict(cell["config"])
    config["serve_args"] = _serve_args(
        config, buckets="%dx%d" % session["size"], max_batch="2",
        dtype=dtype, max_sessions="2") + ["--no-warmup"]
    config["program"] = dict(config["program"], compute_dtype=dtype)
    sut = system.start(config, session["weights"], str(tmp_path),
                       "stream-cpu")
    try:
        assert sut.config.corr_impl == "pallas" and not sut.config.small
        before = sut.scrape()
        conn = http.client.HTTPConnection(sut.host, sut.port, timeout=600)
        frames = session["frames"]
        opened, _ = _post(conn, "/v1/stream", image=frames[0])
        sid = str(opened["session"])
        assert "flow" not in opened and int(opened["frame"]) == 0
        flows, timings = {}, None
        for k in range(1, FRAMES):
            got, resp = _post(conn, "/v1/stream", session=np.asarray(sid),
                              image=frames[k])
            assert int(got["frame"]) == k and bool(got["warm"])
            assert got["session"].shape == () and str(got["session"]) == sid
            flows[0, k] = got["flow"]
            timings = json.loads(resp.getheader("X-Raft-Timings"))
        closed, _ = _post(conn, "/v1/stream", op=np.asarray("close"),
                          session=np.asarray(sid))
        conn.close()
        prom = system.diff_prom(before, sut.scrape())
        return flows, timings, prom
    finally:
        sut.stop()


def test_served_float32_session_is_the_references_walk(
        cell, bench_modules, session, tmp_path):
    """Twelve updates a frame from the projected seed, the slot pool and the
    batched step, in float32 through the server: float32 round-off apart
    from the reference's forward pass AT EVERY INDEX, the fourth advance
    too, whose seed has been through three projections, when both sides
    fill the projection's holes alike; and apart from the reference's own
    walk by what the fill's one departure makes (``references/warm.py``:
    among hits at one distance the exact fill and OpenCV's take another,
    and seeded weights' flow is rough): nothing at index 1, whose seed is
    zeros, a few per cent after (at this size: an 8 x 12 grid, where the
    holes are a tenth of the pixels; 1-2 % of them differ at 30 x 48)."""
    check = bench_modules["check"]
    flows, timings, prom = _served_session(cell, bench_modules, session,
                                           tmp_path, "float32")
    for which in session["which"]:
        ref = session["walk"]["float32, the program's fill"][which]
        assert flows[which].shape == ref.shape == (H, W, 2)
        assert np.linalg.norm(ref, axis=-1).mean() > 0.3     # a real field
        assert check.rel_epe(flows[which], ref) < 1e-4, which
        own = check.rel_epe(flows[which], session["walk"]["float32"][which])
        assert own < (1e-4 if which[1] == 1 else 0.15), (which, own)
    # from index 2 on the seed matters: the walk is not a walk of cold pairs
    cold = np.asarray(sys.modules["reference"].flow(
        session["weights"], session["frames"][1], session["frames"][2],
        bench_modules["weights"].model_cfg(cell["config"]),
        int(cell["config"]["iters"])))
    assert check.rel_epe(cold, session["walk"]["float32"][0, 2]) > 1e-3
    # an advance's timings carry every span a pair's do, and the stream's own
    assert set(timings) >= {
        "decode", "admit", "queue_wait", "batch_form", "pad", "execute",
        "deliver", "respond", "encode", "execute_h2d", "execute_dispatch",
        "execute_block", "execute_fetch", "execute_sentinel", "execute_seed",
        "execute_commit"}, timings
    # every stage and counter of the stream path grew on these batches
    for stage in ("http.decode", "http.admit", "http.encode", "http.respond",
                  "batch.form", "batch.pad", "batch.deliver", "engine.h2d",
                  "engine.dispatch", "engine.wait", "engine.fetch",
                  "stream.sentinel", "stream.seed", "stream.commit"):
        for family in ("raft_serving_stage_seconds_total",
                       "raft_serving_stage_cpu_seconds_total"):
            assert prom[f'{family}{{stage="{stage}"}}'] > 0.0, (family, stage)
    advances = FRAMES - 1
    assert prom["raft_serving_device_calls_total"] == advances + 1
    assert prom["raft_serving_batch_size_count"] == advances
    # a batched advance is a job of the batcher's pipeline (PR 40): it
    # counts as staged, here behind nothing (one session: never ahead)
    assert prom['raft_serving_batches_staged_total{when="late"}'] == advances
    assert prom.get('raft_serving_batches_staged_total{when="ahead"}',
                    0.0) == 0.0
    assert prom["raft_stream_fnet_cache_hits_total"] == advances
    assert prom["raft_stream_fnet_cache_misses_total"] == 0
    assert prom['raft_stream_encoder_passes_total{call="encode"}'] == 1
    assert prom['raft_stream_encoder_passes_total{call="stream"}'] == advances
    assert (0 < prom["raft_serving_corr_tiles_total"]
            <= prom["raft_serving_corr_keyblocks_visited_total"]
            <= prom["raft_serving_corr_grid_steps_total"]
            <= prom["raft_serving_corr_keyblocks_possible_total"])
    # and the key positions those steps multiplied over (PR 43), from the
    # levels' plans: no more live than stored, and a multiple of 128 stored
    positions = "raft_serving_corr_key_positions_total"
    stored = prom[positions + '{kind="stored"}']
    assert 0 < prom[positions + '{kind="live"}'] <= stored
    assert stored % 128 == 0


def test_served_bfloat16_session_is_under_the_cells_limit(
        cell, bench_modules, session_mid, tmp_path):
    """The program the cell times, at the precision the configuration
    states, at the frame indices the cell keeps: ``precision_ratio``
    (check.py) under ``check.ratio_limit``."""
    check, session = bench_modules["check"], session_mid
    limit = float(cell["config"]["check"]["ratio_limit"])
    flows, _, _ = _served_session(cell, bench_modules, session, tmp_path,
                                  "bfloat16")
    kept = [w for w in session["which"]
            if w[1] in cell["traffic"]["kept_frames"]]
    assert [k for _, k in kept] == [2, 3, 4]
    verdict = check.compare([(k, (c, k), flows[c, k]) for c, k in kept],
                            session["walk"]["float32"],
                            session["walk"]["bfloat16"], limit,
                            out=lambda _m: None)
    assert verdict["correct"] and verdict["worst"] < limit, verdict


def test_the_e4m3_control_is_over_the_cells_limit(cell, bench_modules,
                                                  session_mid):
    """The reference itself one precision step below the stated one, walked
    over the same session in the program's place: over the limit at every
    kept index."""
    check, session = bench_modules["check"], session_mid
    limit = float(cell["config"]["check"]["ratio_limit"])
    walk = session["walk"]
    for which in session["which"]:
        if which[1] not in cell["traffic"]["kept_frames"]:
            continue
        ratio = (check.rel_epe(walk["float8"][which], walk["float32"][which])
                 / check.rel_epe(walk["bfloat16"][which],
                                 walk["float32"][which]))
        assert ratio > limit, (which, ratio)


# ------------------------------------- the driver under run.py, on the CPU

@pytest.fixture()
def tiny_cell(tmp_path, cell):
    """A copy of the benchmark with one more cell: this configuration at a
    64x96 bucket in float32 with batches of 2, three sessions of 5-6 frames
    at a time over two clips, kept at frame indices 2 and 3."""
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", "tests"))
    cfg = dict(cell["config"])
    cfg["serve_args"] = _serve_args(
        cfg, buckets="64x96", iters="3", dtype="float32", max_batch="2",
        max_sessions="6", gru_impl="xla")
    cfg.update(iters=3, program={"small": False, "compute_dtype": "float32"},
               check=dict(cfg["check"], ratio_limit=0.5, sample=2))
    (bench / "configs" / "tiny-stream.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny-sessions.json").write_text(json.dumps(dict(
        cell["traffic"], height=64, width=96, clips=2, max_shift=2,
        session_frames=[5, 6], first_session_frames=[5, 6],
        kept_frames=[2, 3])))
    (bench / "workloads" / "tiny-stream-cell.json").write_text(json.dumps(
        {"clients": 3, "why": "rehearsal"}))
    manifest = json.loads(json.dumps(cell["bench"]))
    manifest["configs"].append({
        "name": "tiny-stream", "source": "rehearsal",
        "file": "benchmark/configs/tiny-stream.json", "reduced": [],
        "why": "x"})
    manifest["workloads"].append({
        "name": "tiny-stream-cell", "config": "tiny-stream",
        "traffic": "tiny-sessions", "chips": 1, "why": "x"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-stream-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return bench, tmp_path / "BENCHMARK.json"


def _drive(run, tiny, capsys, trace=0):
    bench, manifest = tiny
    rc = run.main(["--workload", "tiny-stream-cell", "--seed", "3000000019",
                   "--seconds", "4", "--trace", str(trace)],
                  bench_dir=str(bench), manifest=str(manifest),
                  require_tpu=False)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1]), lines


def test_sessions_driver_under_the_harness_is_correct(run, tiny_cell,
                                                      capsys):
    """``run.py`` finds the driver and the reference by name and runs the
    cell: the window's first requests are the opens, every session is walked
    in lockstep and closed, the kept advances at indices 2 and 3 agree with
    the reference's walk from frame 0, nothing fails and nothing compiles;
    the counters' new readers read the window."""
    rc, result, lines = _drive(run, tiny_cell, capsys, trace=1)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0, lines[-12:]
    window = json.loads(next(ln for ln in lines
                             if ln.startswith("window: "))[8:])
    assert window["opens"] >= 3 and window["closes"] >= window["opens"] - 3
    assert window["advances_attempted"] >= 8
    assert (window["attempted"] >= window["advances_attempted"]
            + window["opens"] + window["closes"])
    ratios = {n: c for n, c in result["checks"].items()
              if n.startswith("precision_ratio.r")}
    assert len(ratios) == 2 and all(c["ok"] and 0 < c["value"] < 0.5
                                    for c in ratios.values())
    assert result["checks"]["answers_compared"]["value"] == 2
    assert result["checks"]["compile_misses"]["value"] == 0
    kept = [ln for ln in lines if ln.startswith("check: request")]
    assert sorted(ln.split("(pair ")[1].split(")")[0].split(", ")[1]
                  for ln in kept) == ["2", "3"]
    m = result["metrics"]
    assert m["stream_warm_share"]["value"] == 100.0
    # an encoder pass an advance and one an open
    assert m["stream_fnet_passes_per_pair"]["value"] == pytest.approx(
        1.0 + window["opens"] / (window["ok"] + window["in_flight_at_end"]),
        rel=0.25)
    for name in ("stream_sentinel_ms", "stream_seed_ms", "stream_commit_ms",
                 "decode_ms", "encode_ms", "deliver_ms", "batch_prep_ms",
                 "h2d_ms", "fetch_ms", "host_path_ms"):
        assert m[name]["value"] > 0.0, name
    assert m["batch_fill"]["value"] > 50.0
    assert m["compile_misses"]["value"] == m["host_stall_s"]["value"] == 0
    assert 0 < m["corr_keyblock_share"]["value"] <= 100.0
    # (no device plane in a CPU trace: the trace's readers are left out)
    assert "slot_io_ms" not in m and "device_idle_share" not in m


def test_a_session_with_one_frame_altered_is_not_correct(
        run, tiny_cell, capsys, monkeypatch):
    """The frame a session posts at index 1 is not the clip's (it is the
    clip's frame 3): every later answer of that session starts from another
    seed and holds another frame's maps, and the reference, which walks the
    clip, says so."""
    load = run.load_named

    def load_altered(bench_dir, folder, name, key):
        # run.py loads the driver anew from the copy: alter what it loads
        mod = load(bench_dir, folder, name, key)
        if folder == "drivers":
            make = mod.make_inputs

            def altered(seed, traffic):
                made = make(seed, traffic)
                for clip in made.advances:
                    clip[0] = clip[2]
                return made

            mod.make_inputs = altered
        return mod

    monkeypatch.setattr(run, "load_named", load_altered)
    rc, result, lines = _drive(run, tiny_cell, capsys)
    assert rc == 0
    assert result["correct"] is False and result["failed"] == 0
    assert set(result["metrics"]) == {"pairs_per_s", "setup_s"}
    over = [c for n, c in result["checks"].items()
            if n.startswith("precision_ratio.r") and not c["ok"]]
    assert over and all(c["value"] > c["limit"] == 0.5 for c in over)


# ------------------------------------- the new readers on a hand-made window

def _stream_window(program: str) -> dict:
    """A window of /metrics made by hand: 35 batched advances of 8 sessions
    each and 28 opens.  ``program`` "PR 38": what the parent exposes, the
    stream counters it had and none of this PR's."""
    prom = {"raft_serving_device_calls_total": 63.0,
            "raft_serving_batch_size_count": 35.0,
            "raft_serving_batch_size_sum": 280.0,
            "raft_stream_fnet_cache_hits_total": 280.0,
            "raft_stream_fnet_cache_misses_total": 0.0,
            "raft_stream_frames_total": 280.0,
            "raft_stream_opens_total": 28.0}
    for stage, v in (("engine.fetch", 1.4), ("batch.deliver", 0.7)):
        prom[f'raft_serving_stage_seconds_total{{stage="{stage}"}}'] = v
    if program == "PR 39":
        prom['raft_stream_encoder_passes_total{call="encode"}'] = 28.0
        prom['raft_stream_encoder_passes_total{call="stream"}'] = 280.0
        for stage, v in (("stream.sentinel", 8.75), ("stream.seed", 7.0),
                         ("stream.commit", 0.175)):
            prom[f'raft_serving_stage_seconds_total{{stage="{stage}"}}'] = v
    return prom


def _read(bench_modules, metric, prom, **ctx):
    readers = bench_modules["readers"]
    base = dict(config={}, traffic={}, cell={}, records=[], summary={},
                prom_window=prom, max_batch=8, peak={}, memory_peak_bytes=0,
                shapes={})
    return readers.read_metric(BENCH, metric, readers.RunContext(
        **dict(base, **ctx)))


@pytest.mark.parametrize("metric,want,on_parent", [
    ("stream_warm_share", 100.0, 100.0),
    ("stream_fnet_passes_per_pair", 1.1, None),
    ("stream_sentinel_ms", 250.0, None), ("stream_seed_ms", 200.0, None),
    ("stream_commit_ms", 5.0, None)])
def test_stream_counter_readers(bench_modules, metric, want, on_parent):
    assert _read(bench_modules, metric, _stream_window("PR 39")) \
        == pytest.approx(want)
    # the parent's window: nothing of this PR's to read, and no exception
    got = _read(bench_modules, metric, _stream_window("PR 38"))
    assert got == (None if on_parent is None else pytest.approx(on_parent))
    # a window in which nothing ran, and a pairwise cell's window
    idle = dict.fromkeys(_stream_window("PR 39"), 0.0)
    assert _read(bench_modules, metric, idle) is None
    assert _read(bench_modules, metric,
                 {"raft_serving_device_calls_total": 10.0}) is None
    # a cold restart is a miss and two more passes
    if metric == "stream_warm_share":
        cold = dict(_stream_window("PR 39"), **{
            "raft_stream_fnet_cache_hits_total": 270.0,
            "raft_stream_fnet_cache_misses_total": 10.0})
        assert _read(bench_modules, metric, cold) == pytest.approx(
            100.0 * 270 / 280)


_STAGED = "raft_serving_batches_staged_total"


@pytest.mark.parametrize("staged,want", [
    # 35 batched advances of a window, three of them behind an open, a
    # renewal or an empty queue
    ({_STAGED + '{when="ahead"}': 32.0, _STAGED + '{when="late"}': 3.0},
     100.0 * 32 / 35),
    # a host that is never in time: no series of "ahead"
    ({_STAGED + '{when="late"}': 35.0}, 0.0),
    # the parent's window: stream batches are not counted there, and the
    # cell sends no pair; a window in which nothing was staged
    ({}, None),
    ({_STAGED + '{when="ahead"}': 0.0, _STAGED + '{when="late"}': 0.0},
     None)])
def test_stream_staged_ahead_share_reader(bench_modules, staged, want):
    """``batch_staged_ahead_share``'s reader under the stream cell's name:
    the share of the window's stream batches placed before the batch in
    front of them was ready, None where the window staged none."""
    got = _read(bench_modules, "stream_staged_ahead_share",
                dict(_stream_window("PR 39"), **staged))
    assert got is None if want is None else got == pytest.approx(want)
    # the pair cells' metric reads the same counter and keeps its own list
    assert _read(bench_modules, "batch_staged_ahead_share",
                 dict(_stream_window("PR 39"), **staged)) == got


def test_the_pair_cells_ahead_share_keeps_its_list(cell):
    entry = next(m for m in cell["bench"]["per_layer"]
                 if m["name"] == "batch_staged_ahead_share")
    assert CELL not in entry["workloads"] and len(entry["workloads"]) == 3


def _slot_trace(bench_modules, tmp_path, with_gather=True, with_commit=True):
    """A traced window reduced to its operations: two runs of a stream batch
    program whose gather is a ``while`` over the rows (120 us a run, its
    body's events inside it) and a fusion (30 us), and two programs
    ``jit_slot_commit``: the batch's (two whole runs of 50 us, one cut by
    the window) and an open's."""
    tracered = bench_modules["tracered"]
    insts = {
        "while.19": ("raft/stream/gather", 0,
                     "%while.19 = (s32[], bf16[8,135,240,128]) while(%t)"),
        "fusion.566": ("raft/stream/gather", 0,
                       "%fusion.566 = bf16[33,135,240,128]{3,2,1,0} "
                       "fusion(%p), kind=kLoop"),
        "dynamic-slice_fusion.8": ("raft/stream/gather", 1,
                                   "%dynamic-slice_fusion.8 = bf16[8,135,"
                                   "240,128]{3,2,1,0} fusion(%g), kind=kLoop"),
        "fusion.1": ("raft/fnet", 0,
                     "%fusion.1 = bf16[8,135,240,256]{3,2,1,0} fusion(%x), "
                     "kind=kOutput"),
    }
    if not with_gather:
        insts = {k: (("raft/cnet",) + v[1:]) for k, v in insts.items()}
    maps = tmp_path / "sbatch.stages.json"
    maps.write_text(json.dumps({"instructions": {
        name: {"stage": stage, "loop": loop, "text": text}
        for name, (stage, loop, text) in insts.items()}}))

    def op(name, total_ns, count):
        label = tracered.op_label(insts[name][2])
        return label, tracered.Op(name, label, total_ns, count, total_ns)

    ops = dict([op("while.19", 240e3, 2), op("fusion.566", 60e3, 2),
                op("dynamic-slice_fusion.8", 224e3, 16),
                op("fusion.1", 9e6, 2)])
    modules = [("jit_fn(111)", 700e6, True), ("jit_fn(111)", 700e6, True)]
    if with_commit:
        modules += [("jit_slot_commit(222)", 50e3, True),
                    ("jit_slot_commit(222)", 50e3, True),
                    ("jit_slot_commit(222)", 20e3, False),
                    ("jit_slot_commit(333)", 9e3, True)]
    dev = {"busy_ns": 1.4e9, "gaps": [], "ops": ops, "modules": modules}
    trace = tracered.Trace(window_s=2.0, devices={0: dev}, host_events=[],
                           clipped=True)
    return trace, str(maps)


def test_slot_io_reads_the_gather_and_the_commit_program(bench_modules,
                                                         tmp_path, cell):
    costs = bench_modules["costs"]
    trace, maps = _slot_trace(bench_modules, tmp_path)
    prom = dict(_stream_window("PR 39"),
                **{'raft_serving_batch_size_bucket{le="8.0"}': 35.0,
                   'raft_serving_batch_size_bucket{le="+Inf"}': 35.0})
    ctx = bench_modules["readers"].RunContext(
        config=cell["config"], traffic={}, cell={}, records=[], summary={},
        prom_window=prom, max_batch=8,
        peak={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        memory_peak_bytes=0,
        shapes=costs.grid_shapes(cell["config"], 1080, 1920), trace=trace)
    sys.modules["stages"].load_stage_maps.cache_clear()
    with open(os.path.join(BENCH, "layer_metrics", "slot_io_ms.json")) as f:
        params = dict(json.load(f)["params"], maps=maps)
    import stream_metrics
    # the loop whole (120 us) and the fusion (30 us), not the loop's body
    # again; one whole run of the batch's commit program (50 us)
    ms = stream_metrics.slot_io_ms(ctx, params)
    assert ms == pytest.approx(0.2)
    share = stream_metrics.slot_io_roofline(ctx, params)
    # 8 rows of 135 x 240 x (512 x 2 B + 8 B), four times, at 819 GB/s
    row = 135 * 240 * (512 * 2 + 8)
    assert row == 33_436_800
    least_ms = 1e3 * 8 * 4 * row / 819e9
    assert share == pytest.approx(100.0 * least_ms / 0.2)
    assert costs.COSTS["slot_io"]({"q": 32400, "fnet_dim": 256,
                                   "slot_channels": 256, "slot_itemsize": 2}
                                  ) == {"ops": 0, "bytes": 4 * row}


@pytest.mark.parametrize("lacks", ["gather", "commit", "trace"])
def test_slot_io_finds_nothing_in_a_program_that_lacks_it(
        bench_modules, tmp_path, cell, lacks):
    """The parent's stream batch program has no gather scope and its commit
    program is one more ``jit_fn``; an untraced run has no trace: None, the
    metric is left out, nothing is raised."""
    import stream_metrics
    trace, maps = _slot_trace(bench_modules, tmp_path,
                              with_gather=lacks != "gather",
                              with_commit=lacks != "commit")
    sys.modules["stages"].load_stage_maps.cache_clear()
    ctx = bench_modules["readers"].RunContext(
        config=cell["config"], traffic={}, cell={}, records=[], summary={},
        prom_window=_stream_window("PR 38"), max_batch=8,
        peak={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        memory_peak_bytes=0, shapes={},
        trace=None if lacks == "trace" else trace)
    with open(os.path.join(BENCH, "layer_metrics", "slot_io_ms.json")) as f:
        params = dict(json.load(f)["params"], maps=maps)
    for read in (stream_metrics.slot_io_ms, stream_metrics.slot_io_roofline):
        assert read(ctx, params) is None


# --------------------------------------------------- /admin/reload's probe

def test_reload_probes_a_program_that_returns_key_block_counts():
    """``engine.reload``'s probe runs a warm pair executable and reads its
    flow; with the dense Pallas lookup the program returns (flow, key-block
    counts), the tuple the stream kinds return too: the probe takes the
    flow, the swap goes through (``POST /admin/reload`` answered 500:
    ROADMAP B11)."""
    import jax

    from raft_tpu.config import RAFTConfig, init_rng
    from raft_tpu.models import init_raft
    from raft_tpu.serving.config import ServeConfig
    from raft_tpu.serving.engine import InferenceEngine, ReloadMismatch
    config = RAFTConfig.small_model(iters=2, corr_impl="pallas")
    params = init_raft(init_rng(), config)
    sconfig = ServeConfig(buckets=((32, 48),), max_batch=1, batch_steps=(1,),
                          max_sessions=0)
    engine = InferenceEngine(config, params, sconfig)
    engine.warmup(verbose=False)
    assert engine.programs.keyblocks
    out = engine.run((32, 48), *(np.zeros((1, 32, 48, 3), np.float32),) * 2)
    assert out.shape == (1, 32, 48, 2) and engine.corr_keyblocks[2] > 0
    # a 4x6 grid: every level is one block of eight map rows to a 128-lane
    # row (columns 6, 3, 1 and 0 of 16 lanes: level 3 is pooled away), one
    # tile a level, two updates
    assert engine.corr_keyblocks[4:] == [2 * 3 * 128, 2 * 8 * (6 + 3 + 1)]
    info = engine.reload(jax.tree.map(lambda a: a * 0.5, params), tag="half")
    assert info == {"version": 2, "tag": "half", "probed": True}
    assert engine.compile_misses == 0
    # and a tree that makes the probe's flow non-finite is still refused
    with pytest.raises(ReloadMismatch, match="non-finite"):
        engine.reload(jax.tree.map(lambda a: a * np.nan, params))
    assert engine.weight_info() == {"version": 2, "tag": "half"}
