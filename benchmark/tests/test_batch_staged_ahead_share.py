"""The reader of ``batch_staged_ahead_share`` on windows of /metrics."""

import os

import pytest

import readers

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTER = "raft_serving_batches_staged_total"


def _read(prom_window):
    ctx = readers.RunContext(
        config={}, traffic={}, cell={}, records=[], summary={},
        prom_window=prom_window, max_batch=32, peak={},
        memory_peak_bytes=0, shapes={})
    return readers.read_metric(BENCH, "batch_staged_ahead_share", ctx)


@pytest.mark.parametrize("prom_window,want", [
    # nineteen batches of a window, the first late
    ({COUNTER + '{when="ahead"}': 18.0, COUNTER + '{when="late"}': 1.0,
      "raft_serving_device_calls_total": 19.0}, 100.0 * 18 / 19),
    # a host that is never in time: the series of "ahead" does not exist
    ({COUNTER + '{when="late"}': 13.0}, 0.0),
    ({COUNTER + '{when="ahead"}': 4.0}, 100.0),
    # a program without the counter (the parent), and an empty window
    ({"raft_serving_device_calls_total": 19.0}, None),
    ({COUNTER + '{when="ahead"}': 0.0, COUNTER + '{when="late"}': 0.0},
     None)])
def test_share_of_batches_staged_ahead(prom_window, want):
    got = _read(prom_window)
    assert got is None if want is None else got == pytest.approx(want)
