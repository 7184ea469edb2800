"""Percentile, lateness and due-time arithmetic on synthetic schedules, and
the cost functions against hand-counted operations and bytes."""

import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import costs
import loadgen
from loadgen import Record


def test_percentile_interpolates():
    v = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert loadgen.percentile(v, 0) == 10.0
    assert loadgen.percentile(v, 50) == 30.0
    assert loadgen.percentile(v, 100) == 50.0
    assert loadgen.percentile(v, 95) == pytest.approx(48.0)
    assert loadgen.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        loadgen.percentile([], 50)


MIX = {"arrivals": "poisson", "block_s": 2.0, "pattern_seed": 7}


def test_open_schedule_same_blocks_for_every_seed_in_another_order():
    a = loadgen.open_schedule(1, 12.0, 20.0, MIX)
    b = loadgen.open_schedule(3_000_000_019, 12.0, 20.0, MIX)
    assert len(a) == len(b) == 240
    assert a == sorted(a) and a != b
    assert 0 < a[0] and a[-1] < 20.0 and b[-1] < 20.0
    assert a == loadgen.open_schedule(1, 12.0, 20.0, MIX)

    def blocks(due):                       # each 2 s block, as offsets
        out = [[] for _ in range(10)]
        for t in due:
            out[int(t // 2.0)].append(round(t % 2.0, 9))
        return sorted(tuple(x) for x in out)

    assert blocks(a) == blocks(b)          # the same work, in another order
    assert all(len(x) == 24 for x in blocks(a))
    # exponential gaps inside a block: mean 1/rate, median about ln(2)/rate
    one = blocks(a)[0]
    g = sorted(y - x for x, y in zip((0.0,) + one[:-1], one))
    assert sum(g) / len(g) == pytest.approx(1 / 12.0, rel=0.05)
    assert g[len(g) // 2] == pytest.approx(math.log(2) / 12.0, rel=0.15)
    # a window that is not a whole number of blocks still gets every request
    assert len(loadgen.open_schedule(5, 12.0, 7.0, MIX)) == 84
    u = loadgen.open_schedule(5, 10.0, 4.0, dict(MIX, arrivals="uniform"))
    assert len(u) == 40 and u[1] - u[0] == pytest.approx(u[2] - u[1])


def test_latency_counts_from_due_time_not_from_send():
    # three requests due at 0, 1, 2 s; the generator sent the third 0.5 s
    # late (a starved pool); each took 0.1 s on the wire
    recs = []
    for i, (due, sent) in enumerate([(0.0, 0.0), (1.0, 1.0), (2.0, 2.5)]):
        recs.append(Record(i, 0, 100.0 + due, 100.0 + sent,
                           100.0 + sent + 0.1, 200))
    s = loadgen.summarize(recs, 100.0, 110.0, "open")
    assert s["attempted"] == 3 and s["failed"] == 0
    assert s["latency_max_ms"] == pytest.approx(600.0)      # not 100
    assert s["latency_p50_ms"] == pytest.approx(100.0)
    assert s["gen_late_max_ms"] == pytest.approx(500.0)
    assert s["offered_per_s"] == pytest.approx(0.3)


def test_failures_count_against_attempts_and_have_no_latency():
    recs = [Record(0, 0, 1.0, 1.0, 1.2, 200), Record(1, 0, 1.0, 1.0, 1.3, 429),
            Record(2, 0, 2.0, 2.0, 2.1, -1), Record(3, 0, 3.0)]   # never sent
    s = loadgen.summarize(recs, 0.0, 10.0, "open")
    assert (s["attempted"], s["ok"], s["failed"]) == (4, 1, 3)
    assert s["latency_max_ms"] == pytest.approx(200.0)


def test_closed_loop_rate_counts_answers_inside_the_window_only():
    recs = [Record(i, 0, t, t, t + 0.5, 200) for i, t in
            enumerate([0.0, 0.5, 1.0, 1.5, 1.8])]      # the last ends at 2.3
    s = loadgen.summarize(recs, 0.0, 2.0, "closed")
    assert s["ok_in_window"] == 4 and s["in_flight_at_end"] == 1
    # the fifth was sent at 1.8 and answered at 2.3: 0.2 of its 0.5 s
    assert s["pairs_per_s"] == pytest.approx((4 + 0.4) / 2.0)
    assert s["pairs_per_nominal_s"] == pytest.approx(2.0)
    assert s["failed"] == 0 and s["attempted"] == 5


def test_closed_loop_rate_does_not_step_with_the_batches():
    # batches of 4 answered every second, each pair 2 s in the system (one
    # batch waiting while one runs), for ever: 4 pairs/s.  Whole answers in
    # a window of 4.5 s or of 4.9 s are the same 16; with the pairs in
    # flight counted by their share inside the window the rate follows it
    def window(seconds):
        recs = [Record(4 * b + k, 0, float(b - 1), float(b - 1), b + 1.0, 200)
                for b in range(0, 6) for k in range(4)]
        recs = [r for r in recs if r.sent < seconds]
        return loadgen.summarize(recs, -1.0, seconds, "closed")
    a, b = window(4.5), window(4.9)
    assert a["ok_in_window"] == b["ok_in_window"] == 16
    assert a["pairs_per_s"] == pytest.approx((16 + 4 * 0.75 + 4 * 0.25) / 5.5)
    assert b["pairs_per_s"] == pytest.approx((16 + 4 * 0.95 + 4 * 0.45) / 5.9)
    # a pair in flight at the close that then fails earns nothing
    recs = [Record(0, 0, 0.0, 0.0, 1.0, 200), Record(1, 0, 0.5, 0.5, 2.5, 504)]
    s = loadgen.summarize(recs, 0.0, 2.0, "closed")
    assert s["pairs_per_s"] == pytest.approx(0.5) and s["failed"] == 1


def test_sample_ordinals_are_seeded_and_in_range():
    a = loadgen.sample_ordinals(5, 3, 8, 40)
    assert a == loadgen.sample_ordinals(5, 3, 8, 40) and len(set(a)) == 3
    assert all(8 <= x < 40 for x in a)
    assert a != loadgen.sample_ordinals(6, 3, 8, 40)


# ------------------------------------------------------------------ costs

THINGS = {"small": False, "hidden_dim": 128, "context_dim": 128,
          "corr_levels": 4, "corr_radius": 4}


def test_grid_shapes_of_the_sintel_bucket():
    s = costs.grid_shapes(THINGS, 440, 1024)
    assert (s["h"], s["w"], s["q"]) == (55, 128, 7040)
    assert (s["fnet_dim"], s["hidden"], s["motion"]) == (256, 128, 128)


def test_corr_lookup_cost_counted_by_hand():
    s = costs.grid_shapes(THINGS, 440, 1024)
    c = costs.corr_lookup(s)
    # pooled maps: 55x128, 27x64, 13x32, 6x16 positions
    positions = [7040, 1728, 416, 96]
    ops = sum(2 * 7040 * p * 256 + 8 * 7040 * 81 for p in positions)
    byts = sum(4 * (7040 * 256 + p * 256 + 2 * 7040 + 7040 * 81)
               for p in positions)
    assert c == {"ops": ops, "bytes": byts}
    assert ops == pytest.approx(33.47e9, rel=1e-3)


def test_gru_cost_counted_by_hand():
    s = costs.grid_shapes(THINGS, 440, 1024)
    c = costs.sep_conv_gru(s)
    # 2 passes x 3 gates x 5 taps, each a [7040, 256] x [256, 128] product
    assert c["ops"] == 2 * 3 * 5 * (2 * 7040 * 256 * 128)
    acts = 2 * 7040 * (128 + 128 + 128 + 6 * 128)      # h in, h out, motion, ctx
    assert c["bytes"] == acts + 2 * (2 * 3 * 5 * 256 * 128)


def test_min_seconds_names_the_bound():
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    s = costs.grid_shapes(THINGS, 440, 1024)
    least = costs.min_seconds(costs.corr_lookup(s), peak)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(33.47e9 / 197e12, rel=1e-3)
    assert costs.min_seconds({"ops": 1.0, "bytes": 819e9}, peak) == {
        "seconds": 1.0, "bound": "memory"}
