"""The cell ``things-stream-churn``: more live video sessions than device
slots (ISSUE 41), a cell of the benchmark (``BENCHMARK.json``'s last
configuration, last cell and last five per-layer metrics; its files under
``benchmark/``).  The cell's files say what the issue's table says; a cold
restart through a live server equals ``/v1/flow``'s answer for (previous
frame, frame) and the advance after it the reference's seeded one
(``benchmark/references/warm_restart.py``); ``benchmark/drivers/
sessions_churn.py`` run by ``run.py`` on the CPU against a server with fewer
slots than live sessions comes out ``correct: true``, and ``correct: false``
against a server that reports ``warm`` wrongly or restarts without zeroing
the seed; with one player the window's cold advances are the resumes that
took the longest parked; ``promote`` under 48 sessions x 200 seeded steps; a
group without a cold row records no ``stream.cold.*`` stage and every cause
of a restart is counted under its name; the five new readers on windows made
by hand."""

import http.client
import importlib.util
import json
import os
import random
import shutil
import statistics
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL, STREAM_CELL = "things-stream-churn", "things-stream-sessions"
# the cell a later PR appended after this one (PR 45), to every list this
# one is on but ``slot_io_roofline``
INT8_CELL = "things-stream-int8-pool"
CONFIG = "raft-things-1080p-stream-churn"
MIX = "davis1080p-sessions-churn"
NEW_METRICS = {
    "stream_cold_ms": "server", "stream_cold_wait_ms": "server",
    "stream_cold_device_share": "device",
    "stream_lru_demotions_per_advance": "server",
    "stream_restart_cause_share": "server",
    # (PR 42: restarts that rode their group's batched call)
    "stream_restart_batched_share": "server"}
# the accepted metrics that list the stream cell and read right with cold
# rows in the window
SHARED_METRICS = (
    "batch_fill", "host_path_ms", "compile_misses", "device_idle_share",
    "peak_hbm_gb", "decode_ms", "encode_ms", "deliver_ms", "batch_prep_ms",
    "h2d_ms", "fetch_ms", "host_stall_s", "corr_keyblock_share",
    "corr_bands_per_tile", "corr_steps_per_tile", "stage_unmapped_share",
    "stream_warm_share", "stream_fnet_passes_per_pair", "stream_sentinel_ms",
    "stream_seed_ms", "stream_commit_ms", "slot_io_ms", "slot_io_roofline")
# and the three that are not listed: a kernel's launches of the batch-8
# program and of the solo batch-1 step are one set of events, credited with
# the mean padded batch's rows each; and the staged-ahead share reads right
# here (44.7-50.0 %, my chip runs, PR 41) but a test of the benchmark pins
# its list to the stream cell alone, which only a `benchmark` PR may change
# (PERF.md §3)
NOT_LISTED = ("gru_roofline", "corr_window_roofline",
              "stream_staged_ahead_share")
COLD_STAGES = ("stream.cold.wait", "stream.cold.encode", "stream.cold.step",
               "stream.cold.attach")
STAGE_SECONDS = "raft_serving_stage_seconds_total"
RESTARTS = "raft_stream_cold_restarts_total"
PROMOTIONS = "raft_stream_promotions_total"


@pytest.fixture(scope="module")
def bench_modules():
    """The benchmark's own modules, importable by their bare names as
    ``run.py`` imports them (the paths stay: the drivers import
    ``references.*`` and ``drivers.sessions`` when they are loaded)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import check
    import costs
    import inputs
    import readers
    import stage_cpu  # noqa: F401
    import stages
    import system
    import tracered
    import weights
    return {"check": check, "costs": costs, "inputs": inputs,
            "readers": readers, "system": system, "tracered": tracered,
            "weights": weights, "stages": stages}


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
    return run.load_named(BENCH, "drivers", "sessions_churn",
                          "the test's driver")


@pytest.fixture(scope="module")
def restart_ref(run):
    return run.load_named(BENCH, "references", "warm_restart",
                          "the test's reference")


def _serve_args(config, **replace):
    """The configuration's ``serve_args`` with the values of the flags in
    ``replace`` (``max_batch="2"``) exchanged."""
    argv = [str(a) for a in config["serve_args"]]
    for flag, value in replace.items():
        argv[argv.index("--" + flag.replace("_", "-")) + 1] = value
    return argv


# ------------------------------------------------------------ the cell's data

def test_the_cell_is_in_the_benchmark_and_only_appended_to_it():
    """The manifest gained one configuration, one cell and five metrics, each
    last in its list, and the cell's name at the end of the lists that take
    it (PR 45's configuration and cell come after them); every file the
    entries name is the benchmark's own."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [c["name"] for c in bench["configs"]].count(CONFIG) == 1
    assert bench["configs"][4]["name"] == CONFIG
    assert [w["name"] for w in bench["workloads"][4:]] == [CELL, INT8_CELL]
    assert bench["run_seconds"] == 40 and len(bench["workloads"]) == 6
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][m["workloads"].index(CELL) + 1:] in (
                [], [INT8_CELL])
    for rel in ("configs/" + CONFIG + ".json", "traffic/" + MIX + ".json",
                "workloads/" + CELL + ".json", "drivers/sessions_churn.py",
                "references/warm_restart.py"):
        assert os.path.isfile(os.path.join(BENCH, rel)), rel
    assert not os.path.exists(os.path.join(REPO, "tests", "data",
                                           "churn_cell"))


@pytest.mark.parametrize("what,want", [
    ("config", CONFIG), ("traffic", MIX), ("chips", 1)])
def test_the_cell_is_the_one_the_issue_names(cell, what, want):
    assert cell["entry"][what] == want
    assert len(cell["entry"]["why"]) <= 200
    assert cell["bench"]["workloads"][4] is cell["entry"]
    assert cell["bench"]["configs"][4] is cell["cfg_entry"]


@pytest.mark.parametrize("key,want", [
    ("loop", "closed"), ("endpoint", "/v1/stream"),
    ("driver", "sessions_churn"), ("height", 1080), ("width", 1920),
    ("clips", 4), ("max_shift", 12), ("session_frames", [12, 48]),
    ("session_shape", 1.5), ("burst_frames", [2, 48]), ("burst_shape", 1.2),
    ("resume_recent_probability", 0.25), ("kept_frames", [2, 3, 4]),
    ("check_first_burst", 2), ("check_park_place", 12)])
def test_the_mix_carries_the_issues_table(cell, key, want):
    assert cell["traffic"][key] == want


def test_48_live_sessions_on_32_slots_24_playing(cell, run):
    f, cfg = cell["file"], cell["config"]
    serve = cfg["serve_args"]
    max_batch = int(serve[serve.index("--max-batch") + 1])
    slots = int(serve[serve.index("--max-sessions") + 1])
    assert f["clients"] == 3 * max_batch == 24
    assert f["live_sessions"] == 48 == 1.5 * slots
    assert f["warm_total_seconds"] == 20 and f["trace_seconds"] == 12
    assert f["trace_seconds"] <= 0.4 * cell["bench"]["run_seconds"]
    assert cfg["deployment"]["live_sessions"] == f["live_sessions"]
    assert cfg["deployment"]["slots"] == slots == 32
    assert cfg["deployment"]["playing"] == f["clients"]
    # the mix shares its clips and bodies with the stream cell's
    other = run.load_json(os.path.join(BENCH, "traffic",
                                       "davis1080p-sessions.json"))
    for key in ("height", "width", "clips", "max_shift", "kept_frames",
                "endpoint", "loop"):
        assert cell["traffic"][key] == other[key], key
    assert cell["traffic"]["session_frames"][1] == other["session_frames"][1]


def test_the_configuration_is_the_stream_configuration_but_for_its_traffic(
        cell, run):
    """``raft-things-1080p-stream``'s service letter for letter, so that the
    two cells differ by traffic alone: what differs is the name, the source,
    the reference (which restarts), the deployment and what was assumed."""
    other = run.load_json(os.path.join(BENCH, "configs",
                                       "raft-things-1080p-stream.json"))
    cfg = cell["config"]
    for key in ("small", "fnet_dim", "hidden_dim", "context_dim",
                "corr_levels", "corr_radius", "iters", "parameters",
                "serve_args", "program", "precision", "weights", "reduced"):
        assert cfg[key] == other[key], key
    assert cfg["weights"] == {"flow_head_scale": 0.0005}
    assert cfg["check"] == dict(other["check"], reference="warm_restart")
    assert cfg["check"]["ratio_limit"] == 2.5 and cfg["check"]["sample"] == 3
    assert cfg["name"] == cell["cfg_entry"]["name"] == CONFIG
    assert cell["cfg_entry"]["reduced"] == cfg["reduced"] == []
    assert len(cell["cfg_entry"]["source"]) <= 200
    assert "warm_start" in cell["cfg_entry"]["source"]
    assert "pause" in cell["cfg_entry"]["source"]
    assert "heavy tails" in cfg["source"]
    assert "warm: false" in cfg["guarantees"]
    assert any("bounded Pareto" in a for a in cfg["assumed"])


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
    later = [] if metric == "slot_io_roofline" else [INT8_CELL]
    if metric in NEW_METRICS:
        assert entry["workloads"] == [CELL] + later
        assert entry["layer"] == NEW_METRICS[metric]
        assert entry["moves"] == "pairs_per_s"
        base = os.path.join(BENCH, "layer_metrics", metric)
        assert os.path.exists(base + ".json") and os.path.exists(base + ".py")
    elif "workloads" in entry:
        # appended, right after the cell it shares the stream path with
        assert entry["workloads"][-2 - len(later):] == [STREAM_CELL,
                                                        CELL] + later


@pytest.mark.parametrize("metric", NOT_LISTED)
def test_a_metric_whose_reader_or_pin_does_not_hold_here_is_not_given(
        cell, run, metric):
    entry = run.find(cell["bench"]["per_layer"], metric, "metric")
    assert STREAM_CELL in entry["workloads"]
    assert not run.listed(entry, CELL, {"pairs_per_s", "setup_s"})


def test_the_new_metrics_are_the_manifests_last(cell):
    """PR 41's five, then PR 42's one (then PR 43's ``corr_lane_fill``, for
    every cell, and PR 45's five of the int8 pool): each appended, none
    moved."""
    assert [m["name"] for m in cell["bench"]["per_layer"][-12:-5]] == [
        "stream_cold_ms", "stream_cold_wait_ms", "stream_cold_device_share",
        "stream_lru_demotions_per_advance", "stream_restart_cause_share",
        "stream_restart_batched_share", "corr_lane_fill"]
    by_name = {m["name"]: m for m in cell["bench"]["per_layer"]}
    assert by_name["stream_restart_batched_share"]["better"] == "higher"
    assert by_name["stream_restart_batched_share"]["source"] \
        == "program_counter"
    assert by_name["stream_cold_device_share"]["source"] == "device_trace"
    assert by_name["stream_cold_ms"]["source"] == "program_span"
    assert by_name["stream_restart_cause_share"]["better"] == "higher"


# ------------------------------------------------------------ the draws

@pytest.mark.parametrize("shape,lo,hi,median,mean,top_tenth", [
    (1.2, 2, 48, 4, 5.8, 0.3),       # a burst: median about 4, mean about 6
    (1.5, 12, 48, 18, 20.6, 0.18)])  # a session: mean about 21
def test_a_draw_is_the_tables_bounded_pareto(
        driver, shape, lo, hi, median, mean, top_tenth):
    """The inverse distribution function: its bounds (both reached), its
    median and mean, its heavy tail, over 20,000 draws one by one."""
    rng = random.Random(41)
    draws = [driver.bounded_pareto(rng.random(), shape, lo, hi)
             for _ in range(20000)]
    assert driver.bounded_pareto(0.0, shape, lo, hi) == lo == min(draws)
    assert driver.bounded_pareto(1.0 - 1e-12, shape, lo, hi) == hi \
        == max(draws)
    assert statistics.median(draws) == pytest.approx(median, abs=1)
    assert statistics.fmean(draws) == pytest.approx(mean, rel=0.04)
    # heavy-tailed: a tenth of the draws carry well over a tenth of the work
    top = sorted(draws)[-len(draws) // 10:]
    assert sum(top) > top_tenth * sum(draws)


@pytest.mark.parametrize("what", ["lengths", "bursts", "first", "picks"])
def test_every_draw_comes_from_the_seed(driver, cell, what):
    """The same seed draws the same, another seed draws otherwise.  A
    session's length goes by its ordinal and a player's first burst by the
    player's number; a burst or a choice after that is the next of the
    seed's run, whichever player asks."""
    t = cell["traffic"]

    def draws(seed, order=range(24)):
        order = list(order)
        pop = driver.Population(seed, t, 24, 4)
        pop.parked.extend(driver.Live(j, 0, f"s{j}", 20) for j in range(24))
        out = {"lengths": [pop.length(j) for j in range(96)],
               "first": {c: pop.burst(c, whole=False) for c in order},
               "bursts": [], "picks": []}
        for c in order:
            out["bursts"] += [pop.burst(c) for _ in range(4)]
            own = driver.Live(100 + c, 0, f"p{c}", 20)
            for _ in range(8):
                own, pick = pop.swap(c, own)
                out["picks"].append(pick)
        return out[what]

    a, b = draws(4_100_000_007), draws(4_100_000_007, reversed(range(24)))
    assert a == b and a != draws(7)
    if what == "lengths":
        assert all(12 <= n <= 48 for n in a) and len(set(a)) > 10
    elif what == "bursts":
        assert all(2 <= n <= 48 for n in a) and len(set(a)) > 5
    elif what == "first":
        assert all(1 <= n <= 48 for n in a.values()) and 1 in a.values()
    else:
        assert 0.2 < a.count("recent") / len(a) < 0.3


@pytest.mark.parametrize("what", ["session", "burst", "share", "pick"])
def test_a_number_of_a_spread_is_uniform_as_an_independent_draw_is(
        driver, what):
    """Over the seeds the n-th number of a quantity is uniform on [0, 1):
    every tenth of it is met a tenth of the time, the last (where a burst
    of 48 lies) as well as the first."""
    for n in (0, 5, 59):
        us = [driver.Spread(seed, what).nth(n) for seed in range(4000)]
        assert all(0.0 <= u < 1.0 for u in us)
        tenths = [sum(1 for u in us if k / 10 <= u < (k + 1) / 10)
                  for k in range(10)]
        assert min(tenths) > 330 and max(tenths) < 470, (n, tenths)


@pytest.mark.parametrize("what,shape,lo,hi,mean", [
    ("burst", 1.2, 2, 48, 5.8), ("session", 1.5, 12, 48, 20.6)])
def test_a_drawn_length_has_the_tables_law_whatever_its_place_in_the_run(
        driver, what, shape, lo, hi, mean):
    """The 7th burst (or length) of 4,000 seeds: the bounded Pareto's mean,
    its lower bound and its tail (a burst of 48 is three draws in ten
    thousand; ``bounded_pareto`` itself reaches it, above)."""
    draws = [driver.bounded_pareto(driver.Spread(seed, what).nth(7),
                                   shape, lo, hi) for seed in range(4000)]
    assert min(draws) == lo and 40 <= max(draws) <= hi
    assert statistics.fmean(draws) == pytest.approx(mean, rel=0.06)


@pytest.mark.parametrize("what", ["session", "burst", "share", "pick"])
def test_a_run_of_a_spread_covers_the_unit_interval_evenly(driver, what):
    """Any sixty numbers in a row: each quarter of [0, 1) holds 15 of them
    give or take 3 (independent draws: give or take 8 that often), so of
    sixty resumes 45 take the longest parked, give or take 3."""
    for seed in (1, 4_100_000_007, 2**31 + 5):
        run = driver.Spread(seed, what, first=11)
        us = [run.next() for _ in range(600)]
        assert us[0] == run.nth(11) and len(set(us)) == 600
        for i in range(0, 540, 7):
            for k in range(4):
                n = sum(1 for u in us[i:i + 60] if k / 4 <= u < (k + 1) / 4)
                assert 12 <= n <= 18, (seed, i, k, n)


def test_a_windows_bursts_move_less_from_seed_to_seed_than_independent_ones(
        driver, cell):
    """The advances that sixty bursts in a row hold, over 300 seeds: the
    seed's run moves them under half as far as independent draws do (a
    window's resumes follow: PERF.md §4)."""
    t = cell["traffic"]

    def burst(u):
        return driver.bounded_pareto(u, t["burst_shape"], *t["burst_frames"])

    spread, plain = [], []
    for seed in range(300):
        run, rng = driver.Spread(seed, "burst"), random.Random(seed)
        spread.append(sum(burst(run.next()) for _ in range(60)))
        plain.append(sum(burst(rng.random()) for _ in range(60)))
    assert statistics.fmean(spread) == pytest.approx(60 * 5.8, rel=0.05)
    assert statistics.pstdev(spread) < 0.5 * statistics.pstdev(plain)


@pytest.mark.parametrize("probability,want", [(0.0, 0), (1.0, 400)])
def test_the_resume_probability_is_the_mixs(driver, cell, probability, want):
    """At 0.0 (the issue's fallback form) every resume takes the longest
    parked."""
    pop = driver.Population(
        5, dict(cell["traffic"], resume_recent_probability=probability), 1, 4)
    pop.parked.extend(driver.Live(j, 0, f"s{j}", 20) for j in range(8))
    own, picks = driver.Live(8, 0, "s8", 20), []
    for _ in range(400):
        own, pick = pop.swap(0, own)
        picks.append(pick)
    assert picks.count("recent") == want


def test_a_player_takes_the_longest_parked_three_times_in_four(driver, cell):
    """It chooses among the sessions parked before its own joins the queue;
    a check session is taken by the longest-parked rule alone and joins the
    queue ``place`` places from its head."""
    pop = driver.Population(12345, cell["traffic"], 2, 4)
    live = [driver.Live(j, 0, f"s{j}", 20) for j in range(40)]
    pop.parked.extend(live[:8])
    own, picks = live[8], []
    for _ in range(400):
        before = list(pop.parked)
        taken, pick = pop.swap(0, own)
        picks.append(pick)
        assert taken is (before[-1] if pick == "recent" else before[0])
        assert pop.parked[-1] is own and pop.held[0] is taken
        assert len(pop.parked) == 8
        own = taken
    assert 270 <= picks.count("longest") <= 330
    check = driver.Live(99, 0, "c", 20, check=1)
    taken, _ = pop.swap(1, check, place=3)
    assert taken is not check and pop.parked[3] is check
    # never by the most-recent rule, and by the longest-parked rule only
    # once its slot is gone for sure: twelve advances sent since its park
    # answered cold, one more for the other player's session, which was
    # waiting for its first answer then, and one more for a close since
    assert check.need == cell["traffic"]["check_park_place"] + 1 == 13
    pop.saw_cold(check.parked_t - 1.0)         # (sent before the park)
    for n in range(60):
        if n == 30:
            assert pop.parked[0] is check      # the head, and unripe
            for _ in range(check.need):
                pop.saw_cold(time.monotonic())
            pop.close_began(1)
            pop.close_ended()
        if n == 40:
            assert pop.parked[0] is check      # a close freed a slot
            pop.saw_cold(time.monotonic())
        before = list(pop.parked)
        taken, pick = pop.swap(0, own)
        if taken is check:
            assert n == 40 and pick == "longest" and before[0] is check
            break
        assert taken.check < 0
        if pick == "longest":
            assert taken is next(s for s in before if s.check < 0)
        own = taken
    else:
        raise AssertionError("the check session was never taken")
    # nothing parked: the player keeps what it holds
    empty = driver.Population(1, cell["traffic"], 1, 4)
    assert empty.swap(0, own)[0] is own


def test_players_swapping_at_once_lose_no_session(driver, cell):
    """Sixteen players swap for a second with a short switch interval: every
    session is held by one player or parked once, none twice, none lost."""
    import threading
    import time
    pop = driver.Population(7, cell["traffic"], 16, 4)
    live = [driver.Live(j, 0, f"s{j}", 20) for j in range(40)]
    pop.parked.extend(live[16:])
    pop.held = live[:16]
    stop = time.monotonic() + 1.0
    swaps = [0] * 16

    def player(c):
        own = pop.held[c]
        while time.monotonic() < stop:
            own, _ = pop.swap(c, own)
            swaps[c] += 1

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=player, args=(c,))
                   for c in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert min(swaps) > 10
    everyone = [s.j for s in pop.held] + [s.j for s in pop.parked]
    assert sorted(everyone) == list(range(40))


# ------------------------------------------------ the restart's semantics

SEED, H, W = 4_100_000_011, 64, 96
FRAMES = 6


def _program_projection(flow_lr):
    from raft_tpu.utils.frame_utils import forward_interpolate
    return forward_interpolate(np.asarray(flow_lr, np.float32))


@pytest.fixture(scope="module")
def clip(cell, bench_modules, driver):
    weights = bench_modules["weights"]
    mcfg = weights.model_cfg(cell["config"])
    return {"weights": weights.make_weights(SEED, mcfg), "mcfg": mcfg,
            "frames": driver.sessions.make_clip(SEED, 0, FRAMES, H, W, 2),
            "other": driver.sessions.make_clip(SEED, 1, FRAMES, H, W, 2),
            "iters": int(cell["config"]["iters"])}


def test_the_reference_restarts_where_it_is_told(clip, restart_ref,
                                                 bench_modules):
    """``walk`` with a restart at frame 3: frames 1 and 2 are ``warm.py``'s
    walk; frame 3 is the dense zero-seeded pair (frames 2, 3), whatever came
    before; frame 4 is seeded with the projection of the restart's 1/8
    flow, which is not what the unbroken walk carries there."""
    check = bench_modules["check"]
    forward = check.forward(restart_ref, clip["weights"], clip["mcfg"],
                            clip["iters"])
    frames = clip["frames"]
    whole = restart_ref.walk(forward, frames, 4)
    broken = restart_ref.walk(forward, frames, 4, cold=(3,))
    for k in (1, 2):
        np.testing.assert_array_equal(whole[k], broken[k])
    dense = np.asarray(sys.modules["reference"].flow(
        clip["weights"], frames[2], frames[3], clip["mcfg"], clip["iters"]))
    np.testing.assert_allclose(broken[3], dense, rtol=0, atol=1e-5)
    assert check.rel_epe(whole[3], dense) > 1e-3     # the seed mattered
    pair, pair_lr = forward(frames[2], frames[3], flow_init=whole[2],
                            restart=True)            # the seed is dropped
    np.testing.assert_array_equal(np.asarray(pair), broken[3])
    after, _ = forward(frames[3], frames[4], restart=False,
                       flow_init=restart_ref.forward_interpolate(
                           np.asarray(pair_lr)))
    np.testing.assert_array_equal(np.asarray(after), broken[4])
    assert check.rel_epe(broken[4], whole[4]) > 1e-4


def _post(conn, path, trace_id=None, **arrays):
    inputs = sys.modules["inputs"]
    headers = {"Content-Type": "application/octet-stream",
               "Accept": "application/octet-stream"}
    if trace_id:
        headers["X-Raft-Trace-Id"] = trace_id
    conn.request("POST", path, body=inputs.npz_body(**arrays),
                 headers=headers)
    resp = conn.getresponse()
    payload = resp.read()
    assert resp.status == 200, payload[:300]
    return inputs.npz_load(payload), resp


def _tiny_config(cell, **serve):
    """The configuration at a 64x96 bucket in float32, three updates, XLA's
    GRU, batches of 2; ``serve``: further flags exchanged."""
    config = dict(cell["config"])
    config["serve_args"] = _serve_args(config, **dict(dict(
        buckets="64x96", iters="3", dtype="float32", max_batch="2",
        gru_impl="xla"), **serve))
    config.update(iters=3, program={"small": False,
                                    "compute_dtype": "float32"})
    return config


def test_a_cold_restart_is_the_pair_and_the_advance_after_it_is_seeded(
        cell, bench_modules, clip, restart_ref, driver, tmp_path,
        monkeypatch):
    """One slot, two sessions: the second's open takes the first's slot.
    The first's next advance comes back ``warm: false`` and equals
    ``/v1/flow``'s answer for (previous frame, frame) and the reference's
    restart; the advance after it is warm and equals the reference's seeded
    one (float32, 1e-4, both sides filling the projection's holes alike).
    Its timings hold the restart's spans beside a warm advance's, which add
    up to ``execute``; the stages and the counters say what happened and
    why."""
    check, system = bench_modules["check"], bench_modules["system"]
    config = _tiny_config(cell, max_sessions="1")
    config["serve_args"].append("--no-warmup")
    sut = system.start(config, clip["weights"], str(tmp_path), "churn-cpu")
    try:
        conn = http.client.HTTPConnection(sut.host, sut.port, timeout=600)
        frames = clip["frames"]
        a = str(_post(conn, "/v1/stream", image=frames[0])[0]["session"])
        flows, warm = {}, {}

        def advance(k):
            got, resp = _post(conn, "/v1/stream", trace_id=f"c01d41{k:02d}",
                              session=np.asarray(a), image=frames[k])
            flows[k], warm[k] = got["flow"], bool(got["warm"])
            return json.loads(resp.getheader("X-Raft-Timings"))

        advance(1)
        t_warm = advance(2)
        before = sut.scrape()
        b = str(_post(conn, "/v1/stream", image=clip["other"][0])[0]
                ["session"])                      # takes the one slot
        t_cold = advance(3)
        t_after = advance(4)
        prom = system.diff_prom(before, sut.scrape())
        pair, _ = _post(conn, "/v1/flow", image1=frames[2], image2=frames[3])
        for sid in (a, b):
            _post(conn, "/v1/stream", op=np.asarray("close"),
                  session=np.asarray(sid))
        conn.close()
        deadline = time.monotonic() + 5.0   # (finished after the body went)
        while time.monotonic() < deadline:
            traced = [r for r in sut.server.flightrec.snapshot()
                      if r["trace_id"] == "c01d4103"]
            if traced:
                break
            time.sleep(0.01)
    finally:
        sut.stop()
    assert warm == {1: True, 2: True, 3: False, 4: True}
    # the cold advance's spans add up to its server time, level by level
    [rec] = traced
    root = rec["spans"][0]
    assert root["name"] == "request" and root["parent"] is None
    top = sum(sp["dur_ms"] for sp in rec["spans"]
              if sp.get("parent") == root["span"])
    assert top == pytest.approx(root["dur_ms"], rel=0.05, abs=2.0)
    by_id = {sp["span"]: sp for sp in rec["spans"]}
    held = [sp for sp in rec["spans"] if "held_by" in sp]
    assert held and all(by_id[sp["parent"]]["name"].startswith(
        "execute_cold_") for sp in held)
    assert {sp["call"] for sp in held} == {"encode", "commit"}
    # the restart is the pair's answer, and the reference's
    assert check.rel_epe(flows[3], pair["flow"]) < 1e-4
    with monkeypatch.context() as mp:
        mp.setattr(restart_ref, "forward_interpolate", _program_projection)
        walk = restart_ref.walk(
            check.forward(restart_ref, clip["weights"], clip["mcfg"], 3),
            frames, 4, cold=(3,))
        whole = restart_ref.walk(
            check.forward(restart_ref, clip["weights"], clip["mcfg"], 3),
            frames, 4)
    for k in (1, 2, 3, 4):
        assert np.linalg.norm(walk[k], axis=-1).mean() > 0.03  # a real field
        assert check.rel_epe(flows[k], walk[k]) < 1e-4, k
    # and not the unbroken walk's: the server did restart, and frame 4 was
    # seeded from the restart
    assert check.rel_epe(flows[3], whole[3]) > 1e-3
    assert check.rel_epe(flows[4], whole[4]) > 1e-4
    # the spans: a restart at the place adds the kept frame's encode and the
    # zero-seeded commit to a warm advance's children of execute, and
    # neither a wait nor a solo step
    cold_spans = {"execute_cold_encode", "execute_cold_attach"}
    assert cold_spans <= set(t_cold) and not cold_spans & set(t_warm)
    assert not cold_spans & set(t_after)
    assert not {"execute_cold_wait", "execute_cold_step"} & set(t_cold)
    inside = {k for k in t_warm if k.startswith("execute_")}
    assert {"execute_h2d", "execute_block", "execute_fetch"} <= inside
    assert {k for k in t_cold if k.startswith("execute_")} \
        == inside | cold_spans
    # the place-time calls' engine stages are folded into the restart's
    # spans (``held_by``), so the children stay within execute (a lone
    # group's run is waited for under the next take: not all of it is theirs)
    for t in (t_warm, t_cold):
        assert sum(v for k, v in t.items() if k.startswith("execute_")) \
            <= t["execute"] * 1.001, t
    top = ("decode", "admit", "queue_wait", "batch_form", "execute",
           "deliver", "respond", "encode")
    assert set(top) <= set(t_cold)
    # stage seconds and counters
    for stage in COLD_STAGES:
        assert (prom[f'{STAGE_SECONDS}{{stage="{stage}"}}'] > 0.0) == (
            stage in ("stream.cold.encode", "stream.cold.attach")), stage
    assert prom["raft_stream_restarts_batched_total"] == 1
    assert prom[f'{RESTARTS}{{cause="demoted"}}'] == 1
    assert prom[f'{RESTARTS}{{cause="displaced"}}'] == 0
    assert prom[f'{RESTARTS}{{cause="degraded"}}'] == 0
    # b's open and a's attach each took the slot from the other
    assert prom[f'{PROMOTIONS}{{result="demoted_other"}}'] == 2
    assert prom[f'{PROMOTIONS}{{result="free"}}'] == 0
    assert prom['raft_stream_evictions_total{reason="lru"}'] == 2
    assert prom["raft_stream_fnet_cache_misses_total"] == 1
    assert prom["raft_stream_fnet_cache_hits_total"] == 1


# ------------------------------------- the driver under run.py, on the CPU

TINY = "tiny-churn-cell"
LIMIT = 0.1             # of the tiny cell's precision_ratio: see tiny_cell


@pytest.fixture()
def tiny_cell(tmp_path, cell, driver, monkeypatch):
    """A copy of the benchmark with one more cell: this configuration at a
    64x96 bucket in float32 with batches of 2 and FOUR slots, eight sessions
    live and three playing, sessions of 6-8 frames in bursts of 2-4, the
    check sessions parked two places from the queue's head.  The reference
    fills the projection's holes as the program does (on an 8 x 12 grid a
    tenth of the pixels are holes, and the one departure of ``warm.py``'s
    fill reads 0.2-1.2 % from seed to seed, more than a seed's whole effect
    at three updates): the float32 program is then the reference to
    round-off, and the limit can sit a hundred times above it."""
    monkeypatch.setattr(sys.modules["references.warm_restart"],
                        "forward_interpolate", _program_projection)
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", "tests"))
    cfg = _tiny_config(cell, max_sessions="4")
    cfg.update(name="tiny-churn",
               check=dict(cfg["check"], ratio_limit=LIMIT, sample=3))
    (bench / "configs" / "tiny-churn.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny-churn.json").write_text(json.dumps(dict(
        cell["traffic"], height=64, width=96, clips=2, max_shift=2,
        session_frames=[6, 8], burst_frames=[2, 4], check_park_place=2)))
    (bench / "workloads" / (TINY + ".json")).write_text(json.dumps(
        {"clients": 3, "live_sessions": 8, "why": "rehearsal"}))
    manifest = json.loads(json.dumps(cell["bench"]))
    manifest["configs"].append({
        "name": "tiny-churn", "source": "rehearsal",
        "file": "benchmark/configs/tiny-churn.json", "reduced": [],
        "why": "x"})
    manifest["workloads"].append({
        "name": TINY, "config": "tiny-churn", "traffic": "tiny-churn",
        "chips": 1, "why": "x"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return bench, tmp_path / "BENCHMARK.json"


def _drive(run, tiny, capsys, trace=0, seed="4100000019", seconds="12"):
    bench, manifest = tiny
    rc = run.main(["--workload", TINY, "--seed", seed, "--seconds", seconds,
                   "--trace", str(trace)],
                  bench_dir=str(bench), manifest=str(manifest),
                  require_tpu=False)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1]), lines


def _window_line(lines):
    return json.loads(next(ln for ln in lines
                           if ln.startswith("window: "))[8:])


def test_churn_driver_under_the_harness_is_correct(run, tiny_cell, capsys):
    """``run.py`` finds the driver, the mix and the reference by name and
    runs the cell against a server with half as many slots as live
    sessions: sessions are parked, resumed, demoted and restarted, ended and
    renewed; the three kept answers (A warm, B the restart, C seeded from a
    restart) agree with the reference's walk, B reported cold; nothing fails
    and nothing compiles; the new readers read the window."""
    rc, result, lines = _drive(run, tiny_cell, capsys, trace=1)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0, lines[-12:]
    window = _window_line(lines)
    assert window["resumes"] >= 6 and window["cold_advances"] >= 3
    assert window["resumes_longest"] > window["resumes"] - window[
        "resumes_longest"] >= 0
    assert window["cold_advances"] >= window["resumes"] - window[
        "resumes_warm"]
    assert window["opens"] >= 3 and window["closes"] >= 3
    assert window["advances_attempted"] >= 20
    ratios = {n: c for n, c in result["checks"].items()
              if n.startswith("precision_ratio.r")}
    assert len(ratios) == 3 and all(c["ok"] and 0 < c["value"] < 0.02
                                    for c in ratios.values()), ratios
    assert result["checks"]["answers_compared"]["value"] == 3
    assert result["checks"]["compile_misses"]["value"] == 0
    kept = sorted(ln.split("(pair ")[1].split("): precision")[0]
                  for ln in lines if ln.startswith("check: request"))
    # (clip, frame, frames answered cold): A never cold, B and C at frame 3
    frames = sorted((int(k.split(", ")[1]), k.split(", ", 2)[2]) for k in kept)
    assert [f for f, _ in frames] == [2, 3, 4]
    assert frames[0][1] == "())" and frames[1][1] == "(3,))"
    assert frames[2][1] == "(3,))"
    m = result["metrics"]
    assert 50.0 < m["stream_warm_share"]["value"] < 100.0
    assert m["stream_restart_cause_share"]["value"] == 100.0
    assert m["stream_cold_ms"]["value"] > 0.0
    # every restart rode its group's batched call: nothing waited
    assert m["stream_cold_wait_ms"]["value"] == 0.0
    assert m["stream_restart_batched_share"]["value"] == 100.0
    assert 0.0 < m["stream_lru_demotions_per_advance"]["value"] < 1.0
    assert m["stream_fnet_passes_per_pair"]["value"] > 1.1
    for name in ("stream_sentinel_ms", "stream_seed_ms", "stream_commit_ms",
                 "decode_ms", "encode_ms", "deliver_ms", "batch_prep_ms",
                 "h2d_ms", "fetch_ms", "host_path_ms", "batch_fill"):
        assert m[name]["value"] > 0.0, name
    assert m["compile_misses"]["value"] == m["host_stall_s"]["value"] == 0
    # (no device plane in a CPU trace: the trace's readers are left out)
    assert "stream_cold_device_share" not in m and "slot_io_ms" not in m
    assert "gru_roofline" not in m and "corr_window_roofline" not in m


def _altered_server(monkeypatch, how):
    from raft_tpu.serving import stream
    if how == "says cold, used the seed":
        # every advance reports warm: false; B (which did restart) passes
        # the driver's rule, A and C used their seeds and say they did not
        real = stream.StreamCoordinator.advance

        def advance(self, *args, **kw):
            res = real(self, *args, **kw)
            res["meta"]["warm"] = False
            return res

        monkeypatch.setattr(stream.StreamCoordinator, "advance", advance)
    elif how == "says warm, restarted":
        real = stream.StreamCoordinator.advance

        def advance(self, *args, **kw):
            res = real(self, *args, **kw)
            res["meta"]["warm"] = True
            return res

        monkeypatch.setattr(stream.StreamCoordinator, "advance", advance)
    else:
        # a restart that does not start from zeros: a stale field of a pixel
        # and a half in its place
        real = stream.StreamCoordinator._reseat

        def reseat(self, req, engine):
            commit_row = engine.commit_row

            def seeded(ab, slot, fmap, cnet, seed):
                return commit_row(ab, slot, fmap, cnet,
                                  np.full_like(seed, 1.5))

            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(engine, "commit_row", seeded)
                return real(self, req, engine)

        monkeypatch.setattr(stream.StreamCoordinator, "_reseat", reseat)


@pytest.mark.parametrize("how", [
    "says cold, used the seed", "says warm, restarted",
    "restarts without zeroing the seed"])
def test_a_server_that_misreports_or_keeps_the_seed_is_not_correct(
        run, tiny_cell, capsys, monkeypatch, how):
    _altered_server(monkeypatch, how)
    rc, result, lines = _drive(run, tiny_cell, capsys)
    assert rc == 0 and result["failed"] == 0
    assert result["correct"] is False, lines[-12:]
    assert set(result["metrics"]) == {"pairs_per_s", "setup_s"}
    ratios = [c for n, c in result["checks"].items()
              if n.startswith("precision_ratio.r")]
    over = [c for c in ratios if c["value"] is not None and not c["ok"]]
    if how == "says warm, restarted":
        # no answer ever says cold, so no check session ripens for its
        # resume and no restart is kept: answers are missing
        assert any("no answer kept" in ln for ln in lines)
        assert not result["checks"]["answers_compared"]["ok"]
    else:
        assert over and all(c["value"] > c["limit"] == LIMIT for c in over)
        print(how, [c["value"] for c in ratios])


def test_with_one_player_the_cold_advances_are_the_longest_parked_resumes(
        cell, bench_modules, driver, clip, tmp_path):
    """One player, two slots, five live sessions, no check session: a
    resume that takes the most recently parked finds its slot, one that
    takes the longest parked does not, and the program counts each restart
    under ``cause="demoted"``."""
    system = bench_modules["system"]
    config = _tiny_config(cell, max_sessions="2", max_batch="1")
    sut = system.start(config, clip["weights"], str(tmp_path), "churn-one")
    traffic = dict(cell["traffic"], height=H, width=W, clips=2, max_shift=2,
                   session_frames=[6, 8], burst_frames=[2, 3])
    file = {"clients": 1, "live_sessions": 5}
    try:
        made = driver.make_inputs(SEED, traffic)
        driver.warm_up(sut, made, SEED, traffic, file, 1.0)
        assert len(made.population.parked) == 4
        before = sut.scrape()
        win = driver.run_window(sut, made, SEED, traffic, file, 5.0, 0)
        prom = system.diff_prom(before, sut.scrape())
    finally:
        sut.stop()
    s = driver.summarize(win)
    assert s["failed"] == 0 and s["resumes"] >= 8
    assert 0 < s["resumes_longest"] < s["resumes"]
    assert s["cold_advances"] == s["resumes_longest"]
    assert s["resumes_warm"] == s["resumes"] - s["resumes_longest"]
    assert prom[f'{RESTARTS}{{cause="demoted"}}'] == s["cold_advances"]
    assert prom[f'{RESTARTS}{{cause="displaced"}}'] == 0
    assert prom[f'{RESTARTS}{{cause="degraded"}}'] == 0
    assert prom["raft_stream_fnet_cache_misses_total"] == s["cold_advances"]
    assert driver.kept_answers(win) == []
    # the same seed plays the same window
    picks = [(r.session, r.frame, r.pick) for r in win.records
             if r.op == "advance"]
    assert len({j for j, _, _ in picks}) >= 4


# ------------------------------------------------ promote, under its lock

def test_promote_under_48_sessions_and_200_seeded_steps():
    """32 slots, 48 sessions: sessions go in flight (their lock held), come
    back, are promoted, closed and renewed.  No session in flight ever loses
    its slot, no slot is held twice or freed twice, the pool's count is the
    holders', every demotion is counted once and ``result="none"`` exactly
    where every slot was pinned."""
    from raft_tpu.serving import SessionStore
    from raft_tpu.telemetry.registry import Registry
    from raft_tpu.serving.metrics import make_stream_metrics

    bucket = (32, 48)
    store = SessionStore(max_sessions=32, ttl_s=3600.0)
    m = make_stream_metrics(Registry(), store, buckets=(bucket,))
    rng = random.Random(41)
    sessions = [store.open(bucket) for _ in range(48)]
    flying = []

    def value(family, label):
        return m[family].labels(label).value

    def check():
        holders = [s for s in sessions if s.slot is not None]
        slots = [s.slot for s in holders]
        assert len(set(slots)) == len(slots) <= 32
        assert store.pool.in_use(bucket) == len(slots)
        free = store.pool._free[bucket]
        assert len(set(free)) == len(free)
        assert not set(free) & set(slots) and len(free) + len(slots) == 32

    for step in range(200):
        op = rng.random()
        if op < 0.35 and len(flying) < 34:
            s = rng.choice([s for s in sessions if s not in flying])
            assert s.lock.acquire(blocking=False)
            store.get(s.id)
            flying.append(s)
        elif op < 0.6 and flying:
            s = flying.pop(rng.randrange(len(flying)))
            s.lock.release()
        elif op < 0.95:
            s = rng.choice(sessions)
            pinned = [f.slot for f in flying if f.slot is not None]
            held = {f.id: f.slot for f in flying}
            before = {k: value("promotions", k)
                      for k in ("free", "demoted_other", "none")}
            lru0 = value("evictions", "lru")
            had = s.slot
            in_use = store.pool.in_use(bucket)
            slot = store.promote(s)
            after = {k: value("promotions", k) for k in before}
            for f in flying:                # nobody in flight lost its slot
                assert f.slot == held[f.id] or f is s
            if had is not None:
                assert slot == had and after == before
            elif slot is None:
                # only where every slot was pinned by a session in flight
                assert len(pinned) == 32
                assert after["none"] == before["none"] + 1
            elif in_use < 32:
                assert after["free"] == before["free"] + 1
                assert value("evictions", "lru") == lru0
            else:
                assert after["demoted_other"] == before["demoted_other"] + 1
                assert value("evictions", "lru") == lru0 + 1
        else:
            i = rng.randrange(len(sessions))
            if sessions[i] not in flying:
                store.close(sessions[i].id)
                assert sessions[i].slot is None
                sessions[i] = store.open(bucket)
        check()
    assert value("promotions", "demoted_other") > 0
    assert value("promotions", "free") >= 32


def test_promote_counts_none_when_every_slot_is_pinned():
    from raft_tpu.serving import SessionStore
    from raft_tpu.telemetry.registry import Registry
    from raft_tpu.serving.metrics import make_stream_metrics
    store = SessionStore(max_sessions=2, ttl_s=60.0)
    m = make_stream_metrics(Registry(), store)
    a, b, c = (store.open((32, 48)) for _ in range(3))
    assert store.promote(a) is not None and store.promote(b) is not None
    with a.lock, b.lock:
        assert store.promote(c) is None and c.slot is None
    assert a.slot is not None and b.slot is not None
    assert m["promotions"].labels("none").value == 1
    assert m["promotions"].labels("free").value == 2
    assert m["evictions"].labels("lru").value == 0
    assert store.promote(c) is not None       # a is the LRU holder now
    assert a.slot is None and m["evictions"].labels("lru").value == 1
    assert m["promotions"].labels("demoted_other").value == 1
    assert store.promote(c) == c.slot         # a holder keeps its slot:
    assert sum(m["promotions"].labels(k).value       # nothing counted
               for k in ("free", "demoted_other", "none")) == 4


# ---------------------------------- the stages and the causes, on the stub

def _stage(ss, label):
    return ss.server.registry.get(STAGE_SECONDS).labels(label).value


def _restarts(ss, cause):
    return ss.server.streams.metrics["cold_restarts"].labels(cause).value


def test_a_group_without_a_cold_row_takes_no_cold_stage():
    """Warm groups two deep record none of the four stages and no cause;
    a demoted row beside a warm one is re-seated at the group's place and
    records the encode and the attach alone (no wait, no solo step), its
    group's batch-mate stays warm; a row whose place-time restart faults
    heals solo and records all four."""
    from test_stream_pipeline import Sessions, SlotEngine
    eng = SlotEngine()
    ss = Sessions(eng)
    try:
        for _ in range(3):
            for k, f in zip(range(6), ss.advance(*range(6))):
                ss.served(k, f)
        assert all(_stage(ss, label) == 0.0 for label in COLD_STAGES)
        assert sum(_restarts(ss, c) for c in
                   ("demoted", "displaced", "degraded")) == 0
        ss.demote(0)
        f = ss.advance(0, 1)
        ss.served(0, f[0], warm=False)
        ss.served(1, f[1])
        assert _restarts(ss, "demoted") == 1
        assert ss.server.streams.metrics["restarts_batched"].value == 1
        placed = ("stream.cold.encode", "stream.cold.attach")
        for label in COLD_STAGES:
            assert (_stage(ss, label) > 0.0) == (label in placed), label
        assert ss.session(0).has_features
        ss.demote(0)
        eng.fail_encode.add(float(ss.session(0).last_image[0, 0, 0, 0]))
        f = ss.advance(0, 1)
        ss.served(0, f[0], warm=False)
        ss.served(1, f[1])
        assert _restarts(ss, "demoted") == 2
        assert ss.server.streams.metrics["restarts_batched"].value == 1
        for label in COLD_STAGES:
            assert _stage(ss, label) > 0.0, label
    finally:
        ss.close()


def test_a_row_demoted_between_place_and_dispatch_is_displaced():
    from test_stream_pipeline import Sessions, SlotEngine
    eng = SlotEngine(hold=(0, 1))
    ss = Sessions(eng)
    try:
        f0 = ss.advance(0, 1)
        eng.saw("dispatch", 0)
        f1 = ss.advance(2, 3)
        eng.saw("h2d", 1)                     # placed while call 0 "runs"
        ss.server.streams.store.demote_bucket(ss.session(2).bucket)
        eng.finish(0)
        eng.finish(1)
        for k, f in zip((0, 1), f0):
            ss.served(k, f)
        for k, f in zip((2, 3), f1):
            ss.served(k, f, warm=False)
        assert _restarts(ss, "displaced") == 2
        assert _restarts(ss, "demoted") == _restarts(ss, "degraded") == 0
    finally:
        ss.close()


def test_a_warm_row_that_faulted_is_degraded():
    from test_stream_pipeline import Sessions, SlotEngine
    eng = SlotEngine(fail_dispatch=(0,))
    ss = Sessions(eng)
    try:
        for k, f in zip((0, 1), ss.advance(0, 1)):
            ss.served(k, f, warm=False)
        assert _restarts(ss, "degraded") == 2
        assert _restarts(ss, "demoted") == _restarts(ss, "displaced") == 0
    finally:
        ss.close()


# ------------------------------------- the new readers on a hand-made window

def _churn_window(program: str) -> dict:
    """A window of /metrics made by hand: 40 batched advances of 7 warm rows
    and 40 cold restarts beside them, 16 opens.  ``program`` "PR 40": what
    the parent exposes (no cold stage, no cause)."""
    prom = {"raft_serving_device_calls_total": 136.0,
            "raft_serving_batch_size_count": 40.0,
            "raft_serving_batch_size_sum": 280.0,
            "raft_stream_fnet_cache_hits_total": 280.0,
            "raft_stream_fnet_cache_misses_total": 40.0,
            "raft_stream_frames_total": 320.0,
            "raft_stream_opens_total": 16.0,
            'raft_stream_evictions_total{reason="lru"}': 56.0,
            'raft_stream_evictions_total{reason="ttl"}': 0.0}
    for stage, v in (("engine.fetch", 1.4), ("stream.sentinel", 8.75)):
        prom[f'{STAGE_SECONDS}{{stage="{stage}"}}'] = v
    if program == "PR 41":
        for stage, v in (("stream.cold.wait", 12.0),
                         ("stream.cold.encode", 2.0),
                         ("stream.cold.step", 5.0),
                         ("stream.cold.attach", 1.0)):
            prom[f'{STAGE_SECONDS}{{stage="{stage}"}}'] = v
        prom[f'{RESTARTS}{{cause="demoted"}}'] = 38.0
        prom[f'{RESTARTS}{{cause="displaced"}}'] = 0.0
        prom[f'{RESTARTS}{{cause="degraded"}}'] = 2.0
    if program == "PR 42":
        # every demoted row re-seated at its group's place and served by the
        # batched call but one, which found every slot pinned; the degraded
        # two healed solo: no wait but theirs, no solo step but theirs
        prom.update(_churn_window("PR 41"))
        prom["raft_stream_restarts_batched_total"] = 37.0
    return prom


def _read(bench_modules, metric, prom, **ctx):
    readers = bench_modules["readers"]
    base = dict(config={}, traffic={}, cell={}, records=[], summary={},
                prom_window=prom, max_batch=8, peak={}, memory_peak_bytes=0,
                shapes={})
    return readers.read_metric(BENCH, metric, readers.RunContext(
        **dict(base, **ctx)))


@pytest.mark.parametrize("metric,want,on_parent", [
    ("stream_cold_ms", 200.0, None), ("stream_cold_wait_ms", 300.0, None),
    ("stream_restart_cause_share", 95.0, None),
    ("stream_lru_demotions_per_advance", 0.175, 0.175)])
def test_churn_counter_readers(bench_modules, metric, want, on_parent):
    assert _read(bench_modules, metric, _churn_window("PR 41")) \
        == pytest.approx(want)
    # the parent's window: nothing of this PR's to read, and no exception
    got = _read(bench_modules, metric, _churn_window("PR 40"))
    assert got == (None if on_parent is None else pytest.approx(on_parent))
    # a window in which nothing ran or nothing restarted, a pair cell's
    idle = dict.fromkeys(_churn_window("PR 41"), 0.0)
    assert _read(bench_modules, metric, idle) is None
    assert _read(bench_modules, metric,
                 {"raft_serving_device_calls_total": 10.0}) is None


def test_restart_batched_share_reader(bench_modules):
    """100 x restarts served by the batched call / every restart; nothing,
    and no exception, on a program without the counter (the parent's line
    leaves the metric out) and in a window without a restart."""
    metric = "stream_restart_batched_share"
    assert _read(bench_modules, metric, _churn_window("PR 42")) \
        == pytest.approx(92.5)
    assert _read(bench_modules, metric, _churn_window("PR 41")) is None
    assert _read(bench_modules, metric, _churn_window("PR 40")) is None
    idle = dict.fromkeys(_churn_window("PR 42"), 0.0)
    assert _read(bench_modules, metric, idle) is None
    # the solo form alone (every row healed in ``finish``): 0, not nothing
    solo = dict(_churn_window("PR 42"),
                raft_stream_restarts_batched_total=0.0)
    assert _read(bench_modules, metric, solo) == 0.0


def _solo_trace(bench_modules, tmp_path):
    """A traced window reduced to its operations: runs of the batch-8 stream
    program (its lookup, its loop, a weight's convert), of the solo stream
    program (its lookup at batch 1 and the same convert) and of the encode
    program, and the three programs' stage maps under their files' names."""
    tracered = bench_modules["tracered"]
    programs = {
        "sbatch-1080x1920-b8-aaaa": {
            "corr_lookup.39": "%corr_lookup.39 = bf16[8,32512,81]{2,1,0} "
                              "custom-call(%a)",
            "while.7": "%while.7 = (s32[], bf16[8,135,240,128]) while(%t)",
            "convert.5": "%convert.5 = bf16[3,3,128,128]{3,2,1,0} "
                         "convert(%w)"},
        "stream-1080x1920-b1-aaaa": {
            "corr_lookup.39": "%corr_lookup.39 = bf16[1,32512,81]{2,1,0} "
                              "custom-call(%a)",
            "while.7": "%while.7 = (s32[], bf16[1,135,240,128]) while(%t)",
            "convert.5": "%convert.5 = bf16[3,3,128,128]{3,2,1,0} "
                         "convert(%w)"},
        "encode-1080x1920-b1-aaaa": {
            "fusion.12": "%fusion.12 = bf16[1,135,240,256]{3,2,1,0} "
                         "fusion(%x), kind=kOutput"}}
    maps = tmp_path / "engine" / "cfg" / "identity"
    maps.mkdir(parents=True)
    for name, insts in programs.items():
        (maps / (name + ".stages.json")).write_text(json.dumps({
            "instructions": {k: {"stage": "raft", "loop": 0, "text": text}
                             for k, text in insts.items()}}))

    def op(program, name, total_ns, count):
        label = tracered.op_label(programs[program][name])
        return label, tracered.Op(name, label, total_ns, count, total_ns)

    ops = dict([
        op("sbatch-1080x1920-b8-aaaa", "corr_lookup.39", 6.0e9, 480),
        op("sbatch-1080x1920-b8-aaaa", "while.7", 6.4e9, 10),
        # (one label in both programs' maps: the main program's keeps it)
        op("sbatch-1080x1920-b8-aaaa", "convert.5", 0.2e9, 20),
        op("stream-1080x1920-b1-aaaa", "corr_lookup.39", 0.9e9, 480),
        op("stream-1080x1920-b1-aaaa", "while.7", 1.0e9, 10),
        op("encode-1080x1920-b1-aaaa", "fusion.12", 0.3e9, 14)])
    dev = {"busy_ns": 8.0e9, "gaps": [], "ops": ops, "modules": []}
    trace = tracered.Trace(window_s=12.0, devices={0: dev}, host_events=[],
                           clipped=True)
    return trace, str(maps / "*.stages.json")


def test_cold_device_share_reads_the_solo_programs_operations(
        bench_modules, tmp_path, monkeypatch):
    """The solo step's lookup (0.9 s) and the encode program's fusion (0.3 s)
    of 8 busy seconds: 15 %; the loop that contains the lookup is not taken
    again, and the label both maps hold stays the main program's."""
    trace, maps = _solo_trace(bench_modules, tmp_path)
    spec = importlib.util.spec_from_file_location(
        "layer_metric_cold_share", os.path.join(
            BENCH, "layer_metrics", "stream_cold_device_share.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(BENCH, "layer_metrics",
                           "stream_cold_device_share.json")) as f:
        params = json.load(f)["params"]
    assert params["kinds"] == ["encode", "stream"]
    ctx = bench_modules["readers"].RunContext(
        config={"name": "cfg"}, traffic={}, cell={}, records=[], summary={},
        prom_window={}, max_batch=8, peak={}, memory_peak_bytes=0, shapes={},
        trace=trace)
    assert mod.read(ctx, dict(params, maps=maps)) == pytest.approx(15.0)
    # found by the configuration's name under the benchmark's engine cache
    monkeypatch.setattr(bench_modules["stages"], "BENCH_DIR", str(tmp_path))
    os.rename(tmp_path / "engine", tmp_path / ".cache")
    os.makedirs(tmp_path / "x")
    os.rename(tmp_path / ".cache", tmp_path / "x" / "engine")
    os.rename(tmp_path / "x", tmp_path / ".cache")
    assert mod.read(ctx, params) == pytest.approx(15.0)
    # no maps (another configuration's cache), no trace, an idle device
    other = bench_modules["readers"].RunContext(**dict(
        ctx.__dict__, config={"name": "another"}))
    assert mod.read(other, params) is None
    assert mod.read(bench_modules["readers"].RunContext(**dict(
        ctx.__dict__, trace=None)), params) is None


def test_engine_stages_inside_a_cold_stage_are_its_children():
    """The span tree adds up at every level: the solo calls' engine stages
    hang under the cold stage that holds them, and the flat timings view
    (``X-Raft-Timings``) leaves them out."""
    from raft_tpu.serving.batcher import MicroBatcher
    from raft_tpu.telemetry import spans
    tr = spans.Tracer(1.0).start("stream")
    t = tr.t0
    calls = [("sbatch", "execute_dispatch", "engine.dispatch", t, t + 0.1,
              0.0, False),
             ("encode", "execute_dispatch", "engine.dispatch", t + 1.0,
              t + 1.1, 0.0, False),
             ("encode", "execute_block", "engine.wait", t + 1.1, t + 1.4,
              0.0, False),
             ("stream", "execute_cold_encode", "stream.cold.encode", t + 0.9,
              t + 1.5, 0.0, True)]
    MicroBatcher._device_spans(tr, calls, "exec")
    by = {(s["name"], s.get("call")): s for s in tr._spans}
    cold = by["execute_cold_encode", "stream"]
    assert cold["parent"] == "exec"
    assert by["execute_dispatch", "sbatch"]["parent"] == "exec"
    for key in (("execute_dispatch", "encode"), ("execute_block", "encode")):
        assert by[key]["parent"] == cold["span"]
        assert by[key]["held_by"] == "execute_cold_encode"
    assert tr.timings_ms() == {"execute_cold_encode": 600.0,
                               "execute_dispatch": 100.0}


@pytest.mark.parametrize("cold_at_resume,missing", [
    # every check session lost its slot through its pause: the first gives
    # its first burst's last advance, the second the restart, the third the
    # advance after one
    ({50: True, 51: True, 52: True}, ()),
    # the first kept its slot: its answer is the warm one anyway
    ({50: False, 51: True, 52: True}, ()),
    # the second kept its slot: no restart to hold against the pair
    ({50: True, 51: False, 52: True}, ("restart",)),
    # the third kept its slot: its advance was not seeded from a restart
    ({50: True, 51: True, 52: False}, ("after_restart",)),
    ({50: False, 51: False, 52: False}, ("restart", "after_restart"))])
def test_each_check_session_gives_the_answer_the_issue_gives_it(
        driver, bench_modules, capsys, cold_at_resume, missing):
    """A = the first check session's, B = the second's, C = the third's, and
    no other way round: an answer that is not of its kind is missing (the
    run is then not correct), whatever the other sessions did."""
    loadgen, inputs = sys.modules["loadgen"], bench_modules["inputs"]
    records, keep, checks = [], {}, {}
    for j, cold in cold_at_resume.items():
        checks[j] = driver.Live(j, j % 4, f"s{j}", 20, at=4, check=j - 50,
                                cold=[3] if cold else [])
        for k in (1, 2, 3, 4):
            r = loadgen.Record(len(records), k, 0.0, 0.0, 1.0, 200)
            r.op, r.session, r.clip, r.frame = "advance", j, j % 4, k
            r.warm = not (cold and k == 3)
            r.pick = "longest" if k == 3 else None
            if k > 1:
                keep[j, k] = j % 4
                r.payload = inputs.npz_body(
                    flow=np.full((2, 2, 2), 10 * j + k, np.float32))
            records.append(r)
    win = driver.Window(records, 0.0, 40.0, keep, checks,
                        list(driver.KINDS))
    got = driver.kept_answers(win)
    said = capsys.readouterr().out
    assert [key[1] for _, key, _ in got] == [2, 3, 4]
    assert ("did not do what the cell is for" in said) == bool(missing)
    for (kind, j), (_, (clip, k, cold), flow) in zip(
            zip(driver.KINDS, (50, 51, 52)), got):
        assert clip == j % 4
        assert cold == ((3,) if cold_at_resume[j] and k >= 3 else ())
        if kind in missing:
            assert flow is None and f"answer {kind!r}" in said
        else:
            assert float(flow[0, 0, 0]) == 10 * j + k
    # what was kept and is no session's answer is let go
    assert sum(r.payload is not None for r in records) == 3


def test_an_advance_after_a_restart_that_came_back_cold_is_missing(
        driver, bench_modules, capsys):
    """C is warm and seeded from the restart: a third check session answered
    cold twice running restarted, and seeded nothing."""
    loadgen, inputs = sys.modules["loadgen"], bench_modules["inputs"]
    records, keep = [], {}
    for j in (50, 51, 52):
        for k in (2, 3, 4):
            r = loadgen.Record(len(records), k, 0.0, 0.0, 1.0, 200)
            r.op, r.session, r.clip, r.frame = "advance", j, 0, k
            r.warm = k == 2
            r.pick = "longest" if k == 3 else None
            keep[j, k] = 0
            r.payload = inputs.npz_body(flow=np.zeros((2, 2, 2), np.float32))
            records.append(r)
    checks = {j: driver.Live(j, 0, f"s{j}", 20, at=4, check=j - 50,
                             cold=[3, 4]) for j in (50, 51, 52)}
    got = driver.kept_answers(driver.Window(
        records, 0.0, 40.0, keep, checks, list(driver.KINDS)))
    assert [flow is None for _, _, flow in got] == [False, False, True]
    assert "answer 'after_restart'" in capsys.readouterr().out
