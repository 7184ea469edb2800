"""The trace reduction on a small recorded trace: the first 1.1 s of the
captured window of a ``things-sintel-closed`` run on a TPU v5e (my chip run,
PR 23), cut down to the device's ``XLA Modules`` and ``XLA Ops`` lines and the
host events of 20 us and more: two whole program runs at batch 4 and the tail
of a third."""

import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = os.path.join(BENCH, "tests", "data", "things_closed_2runs.xplane.pb")

import costs  # noqa: E402
import readers  # noqa: E402
import tracered  # noqa: E402


@pytest.fixture(scope="module")
def trace():
    return tracered.reduce_trace(TRACE)


def test_window_is_the_host_annotation_and_busy_is_the_union(trace):
    assert trace.clipped and trace.n_devices == 1
    assert trace.window_s == pytest.approx(1.0978, abs=1e-3)
    # two whole runs of 199.4 ms and 116 ms of a third; nested events (the
    # update loop CONTAINS its body's events) are not counted twice
    assert trace.busy_s() == pytest.approx(0.5150, abs=1e-3)
    assert trace.busy_s() == pytest.approx(trace.module_seconds(), rel=1e-3)
    assert trace.idle_share() == pytest.approx(0.531, abs=2e-3)
    dev = trace.devices[0]
    gaps = sum(b - a for a, b in dev["gaps"]) / 1e9
    assert gaps + trace.busy_s() == pytest.approx(trace.window_s, rel=1e-6)


def test_program_runs_and_kernels_are_found_by_name(trace):
    assert trace.module_runs() == 2
    assert trace.mean_run_seconds() == pytest.approx(0.19946, rel=1e-3)
    by = {op.name: op for op in trace.select(r"^(corr_lookup|gru|while)\.")}
    # 12 updates a run: the GRU kernel once, the lookup once per level
    assert by["gru.8"].count == 32          # 12 + 12 + 8 of the cut run
    assert {by[f"corr_lookup.{n}"].count for n in (32, 33, 34)} == {32}
    # the run cut by the window's start has lost its container event
    assert by["while.4"].count == 2
    assert trace.op_seconds(r"^while\.", whole_runs=True) / (
        2 * trace.mean_run_seconds()) == pytest.approx(0.89, abs=0.02)
    labels = [name for name, _ in trace.top_ops(5)]
    assert labels[0] == "corr_lookup.35 f32[4,7040,9,9] custom-call"
    assert not any(lbl.startswith("while") for lbl in labels)
    assert tracered.op_name("%gru.8 = bf16[4,56,128,128]{3,2,1,0} custom-call"
                            "(bf16[4] %x)") == "gru.8"


def test_idle_gaps_are_named_by_what_the_host_did(trace):
    gaps = dict(trace.top_gaps(5))
    assert gaps["Transpose @pjrt-tpu-tasks"] == pytest.approx(0.3368, abs=1e-3)
    assert sum(gaps.values()) <= trace.window_s - trace.busy_s() + 1e-9


def test_readers_on_the_recorded_trace(trace):
    cfg = {"small": False, "hidden_dim": 128, "context_dim": 128,
           "corr_levels": 4, "corr_radius": 4}
    # every batch of the window was a full batch of 4
    prom = {'raft_serving_batch_size_bucket{le="1.0"}': 0.0,
            'raft_serving_batch_size_bucket{le="2.0"}': 0.0,
            'raft_serving_batch_size_bucket{le="4.0"}': 3.0,
            "raft_serving_batch_size_sum": 12.0,
            "raft_serving_batch_size_count": 3.0}
    ctx = readers.RunContext(
        config=cfg, traffic={}, cell={}, records=[], summary={},
        prom_window=prom, max_batch=4, memory_peak_bytes=909_000_000,
        peak={"flops_per_s": 197e12, "bytes_per_s": 819e9},
        shapes=costs.grid_shapes(cfg, 440, 1024), trace=trace)
    idle = readers.read_device_trace(ctx, {"what": "idle_share"})
    assert idle == pytest.approx(53.1, abs=0.2)
    upd = readers.read_device_trace(
        ctx, {"what": "op_ms_per_pair", "match": r"^while\.", "inside": True})
    out = readers.read_device_trace(
        ctx, {"what": "op_ms_per_pair", "match": r"^while\.", "inside": False})
    assert upd + out == pytest.approx(199.46 / 4, rel=1e-3)   # ms per pair
    assert 40 < upd < 47 and 3 < out < 9
    corr = readers.read_kernel_roofline(ctx, {
        "match": r"^corr_lookup\.", "cost": "corr_lookup", "events_per_call": 4})
    gru = readers.read_kernel_roofline(ctx, {
        "match": r"^gru\.", "cost": "sep_conv_gru", "events_per_call": 1})
    # 33.5 GFLOP a pair and lookup in 11.1 ms at batch 4; 13.8 in 0.5 ms
    assert corr == pytest.approx(6.1, abs=0.3)
    assert gru == pytest.approx(56.0, abs=3.0)
    assert readers.read_memory_stats(ctx, {}) == pytest.approx(0.909)
    # a reader with nothing to read returns nothing
    ctx.trace = None
    assert readers.read_kernel_roofline(ctx, {"match": "x", "cost": "corr_lookup"}) is None
    assert readers.read_device_trace(ctx, {"what": "idle_share"}) is None
