"""The reader of ``stream_staged_ahead_share`` on windows of /metrics: the
pair cells' reader (``batch_staged_ahead_share``) under the stream cell's
name, listed for that cell alone."""

import json
import os

import pytest

import readers

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTER = "raft_serving_batches_staged_total"


def _read(prom_window, name="stream_staged_ahead_share"):
    ctx = readers.RunContext(
        config={}, traffic={}, cell={}, records=[], summary={},
        prom_window=prom_window, max_batch=8, peak={},
        memory_peak_bytes=0, shapes={})
    return readers.read_metric(BENCH, name, ctx)


@pytest.mark.parametrize("prom_window,want", [
    ({COUNTER + '{when="ahead"}': 13.0, COUNTER + '{when="late"}': 1.0,
      "raft_serving_batch_size_count": 14.0}, 100.0 * 13 / 14),
    ({COUNTER + '{when="late"}': 14.0}, 0.0),
    # a program that stages no stream batch (the parent: the cell sends no
    # pair, so the counter stands still), and an empty window
    ({"raft_serving_batch_size_count": 14.0}, None),
    ({COUNTER + '{when="ahead"}': 0.0, COUNTER + '{when="late"}': 0.0},
     None)])
def test_share_of_stream_batches_staged_ahead(prom_window, want):
    got = _read(prom_window)
    assert got is None if want is None else got == pytest.approx(want)
    assert _read(prom_window, "batch_staged_ahead_share") == got


def test_listed_for_the_stream_cell_alone():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    entry = per_layer["stream_staged_ahead_share"]
    assert entry["workloads"] == ["things-stream-sessions"]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("%", "higher", "program_counter", "server",
                                "pairs_per_s")
    assert "things-stream-sessions" not in \
        per_layer["batch_staged_ahead_share"]["workloads"]
