"""The readers of the program's own tracing (stages.py), on a hand-built
``Trace`` whose every number can be checked by eye, and on a small trace
recorded on the chip by PR 24 (``things_closed_annotated.xplane.pb``: the
``--trace 1`` window of a ``things-sintel-closed`` run on a TPU v5e, cut down
to the device's ``XLA Modules`` and ``XLA Ops`` lines and the host events of
20 us and more, with ``things_closed_annotated.stages.json``, the map the
engine wrote beside its batch-32 executable in that run)."""

import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
TRACE = os.path.join(DATA, "things_closed_annotated.xplane.pb")
STAGES = os.path.join(DATA, "things_closed_annotated.stages.json")

import readers  # noqa: E402
import stages  # noqa: E402
import tracered  # noqa: E402

MS = 1e6                                        # ns


def _op(name, shape, opcode, total_ms, count=1):
    label = f"{name} {shape} {opcode}"
    return label, tracered.Op(name, label, total_ms * MS, count, total_ms * MS)


def hand_built():
    """One device, a window of 1000 ms.  Program ``jit_fn(1)`` runs 100-400
    (whole) and 700-1000 (cut by the window's end after 300 of its 300 ms:
    whole too) -- and a small program ``jit_other(2)`` runs 450-460.  The
    batcher's thread: fetch 400-440, deliver 440-450, take 460-470,
    pad 470-560, h2d 560-690, dispatch 690-700, wait 700-1000; before the
    first run: h2d 0-90, dispatch 90-100, wait 100-400."""
    # two runs of 3 iterations: the in-loop operations have 6 events, the
    # others one event a run
    ops = dict([
        _op("corr_lookup.3", "f32[32,7040,9,9]", "custom-call", 300, 6),
        _op("convert.9", "bf16[32,7040,9,9]", "convert", 60, 6),
        _op("fusion.1", "bf16[64,220,512,64]", "fusion", 90, 2),
        # the window's end cut the second run before its upsampling
        _op("fusion.2", "f32[32,440,1024,2]", "fusion", 15, 1),
        _op("add.7", "f32[32,55,128,2]", "add", 20, 6),
        _op("fusion.1", "bf16[2,8]", "fusion", 10),     # the other program's
    ])
    ops["while.4"] = tracered.Op("while.4", "while.4", 480 * MS, 2, 480 * MS)
    dev = {"busy_ns": 610 * MS,
           "gaps": [(0, 100 * MS), (400 * MS, 450 * MS),
                    (460 * MS, 700 * MS)],
           "ops": ops,
           "modules": [("jit_fn(1)", 300 * MS, True),
                       ("jit_other(2)", 10 * MS, True),
                       ("jit_fn(1)", 300 * MS, True)]}
    host = [(s * MS, e * MS, f"{name} @python3") for s, e, name in [
        (0, 90, "raft.engine.h2d"), (90, 100, "raft.engine.dispatch"),
        (100, 400, "raft.engine.wait"), (400, 440, "raft.engine.fetch"),
        (440, 450, "raft.batch.deliver"), (460, 470, "raft.batch.take"),
        (470, 560, "raft.batch.pad"), (560, 690, "raft.engine.h2d"),
        (690, 700, "raft.engine.dispatch"), (700, 1000, "raft.engine.wait"),
        # a handler's thread and the runtime: they name no gap here
        (380, 600, "raft.http.decode"), (565, 685, "Transpose @pjrt")]]
    return tracered.Trace(window_s=1.0, devices={0: dev}, host_events=host,
                          clipped=True)


MAP = {"instructions": {
    "corr_lookup.3": {"stage": "raft/corr_lookup/l0/corr_lookup", "loop": 1, "text":
                      "%corr_lookup.3 = f32[32,7040,9,9]{3,2,1,0} custom-call(%a)"},
    "convert.9": {"stage": "raft/corr_lookup/l0/corr_lookup", "loop": 1, "text":
                  "%convert.9 = bf16[32,7040,9,9]{3,2,1,0} convert(%corr_lookup.3)"},
    "fusion.1": {"stage": "raft/fnet/encoder/stem", "loop": 0, "text":
                 "%fusion.1 = bf16[64,220,512,64]{3,2,1,0} fusion(%p), kind=kLoop"},
    "fusion.2": {"stage": "raft/upsample", "loop": 0, "text":
                 "%fusion.2 = f32[32,440,1024,2]{3,2,1,0} fusion(%q), kind=kLoop"},
    "add.7": {"stage": "", "loop": 1,
              "text": "%add.7 = f32[32,55,128,2]{3,2,1,0} add(%x, %y)"},
    "while.4": {"stage": "", "loop": 0,
                "text": "%while.4 = (s32[], f32[2]) while(%t)"},
}}


@pytest.fixture()
def ctx(tmp_path):
    (tmp_path / "pair-b32.stages.json").write_text(json.dumps(MAP))
    # another executable's map, which claims nothing of this window
    other = {"instructions": {"fusion.1": {"stage": "raft/cnet", "text":
             "%fusion.1 = bf16[8,220,512,64]{3,2,1,0} fusion(%p)"}}}
    (tmp_path / "pair-b4.stages.json").write_text(json.dumps(other))
    prom = {"raft_serving_device_calls_total": 4.0,
            'raft_serving_device_rows_total{kind="real"}': 120.0,
            'raft_serving_device_rows_total{kind="padded"}': 128.0}
    for stage, s in [("batch.form", 0.04), ("batch.pad", 0.36),
                     ("engine.h2d", 0.52), ("engine.dispatch", 0.04),
                     ("engine.wait", 1.2), ("engine.fetch", 0.16),
                     ("batch.deliver", 0.08), ("batch.take", 0.04)]:
        prom[f'raft_serving_stage_seconds_total{{stage="{stage}"}}'] = s
    c = readers.RunContext(
        config={"iters": 3}, traffic={}, cell={}, records=[], summary={},
        prom_window=prom, max_batch=32, peak={}, memory_peak_bytes=0,
        shapes={}, trace=hand_built())
    c.maps = str(tmp_path / "*.stages.json")
    return c


def test_batches_are_those_of_the_main_program(ctx):
    name, ns, run_ns = stages.main_program(ctx.trace)
    assert name == "jit_fn(1)" and ns == 600 * MS and run_ns == 300 * MS
    assert stages.batches_in_window(ctx.trace) == pytest.approx(2.0)
    # tracered's own mean is over every program's runs, the small one too
    assert ctx.trace.mean_run_seconds() == pytest.approx(0.61 / 3)


@pytest.mark.parametrize("params,want", [
    # the one whole batch: from the end of the first dispatch (100) to the
    # end of the second (700); the h2d before the first run is not in it
    ({"stages": ["engine.h2d"]}, 130),
    ({"stages": ["engine.fetch"]}, 40),
    ({"stages": ["batch.take", "batch.form", "batch.pad", "batch.deliver"]},
     10 + 10 + 90),
    ({"stages": ["engine.dispatch"]}, 10),
    # of that batch's idle time, none lies under no annotation
    ({"share_unnamed": True}, 0.0),
])
def test_idle_gaps_by_stage_on_the_hand_built_trace(ctx, params, want):
    anns = stages.batcher_annotations(ctx.trace)
    assert stages.whole_batches(anns) == [(100 * MS, 700 * MS)]
    assert stages.idle_ms(ctx, params) == pytest.approx(want)


def test_a_window_with_no_whole_batch_divides_by_the_batches_in_part(ctx):
    # the second dispatch is not in the window: no whole batch; the window's
    # idle h2d (90 + 130) over the 2.0 runs it holds
    ctx.trace.host_events = [ev for ev in ctx.trace.host_events
                             if ev[:2] != (690 * MS, 700 * MS)]
    assert stages.whole_batches(stages.batcher_annotations(ctx.trace)) == []
    assert stages.idle_ms(ctx, {"stages": ["engine.h2d"]}) == \
        pytest.approx((90 + 130) / 2)


def test_idle_time_under_no_annotation_is_unnamed(ctx):
    ctx.trace.host_events = [ev for ev in ctx.trace.host_events
                             if "batch.pad" not in ev[2]]
    by_stage, total = stages.idle_by_stage(ctx.trace)
    assert total == 390 * MS and by_stage[""] == 90 * MS
    assert sum(by_stage.values()) == pytest.approx(total)
    # of the whole batch's 290 idle ms (400-450, 460-700)
    assert stages.idle_ms(ctx, {"share_unnamed": True}) == pytest.approx(
        100 * 90 / 290)


@pytest.mark.parametrize("params,want", [
    # one run: 3 iterations of a 50 ms lookup and a 10 ms convert, over the 30
    # real rows of a device call (120 rows in 4 calls)
    ({"stage": "(^|/)raft/corr_lookup(/|$)"}, 3 * (50 + 10) / 30),
    ({"stage": "(^|/)raft/(fnet|cnet)(/|$)"}, 45 / 30),
    # its one event in the window is a whole one: the cut run costs nothing
    ({"stage": "(^|/)raft/upsample(/|$)"}, 15 / 30),
    # add.7 (3 x 3.33 ms a run) has no stage; the container and the other
    # program's fusion.1 (same name, another shape) are not this program's
    ({"share_unmapped": True}, 100 * 10 / (180 + 45 + 15 + 10)),
])
def test_stage_map_joined_with_the_operations(ctx, params, want):
    assert stages.stage_ms(ctx, dict(params, maps=ctx.maps)) == \
        pytest.approx(want)


def test_counters_give_the_batchers_serial_ms(ctx):
    p = json.load(open(os.path.join(BENCH, "layer_metrics",
                                    "batcher_serial_ms.json")))["params"]
    # form + pad + h2d + dispatch + fetch + deliver = 1.2 s in 4 calls
    assert stages.serial_ms(ctx, p) == pytest.approx(300.0)


@pytest.mark.parametrize("strip", ["trace", "annotations", "maps",
                                   "counters"])
def test_a_program_without_the_source_gives_nothing(ctx, strip):
    """The parent of the PR that added a source: the reader returns None and
    does not raise, so the result line leaves the metric out."""
    if strip == "trace":
        ctx.trace = None
    elif strip == "annotations":
        ctx.trace.host_events = [ev for ev in ctx.trace.host_events
                                 if not ev[2].startswith("raft.")]
    elif strip == "counters":
        ctx.prom_window = {}
    maps = "/nonexistent/*.json" if strip == "maps" else ctx.maps
    got = {
        "idle": stages.idle_ms(ctx, {"stages": ["engine.h2d"]}),
        "unnamed": stages.idle_ms(ctx, {"share_unnamed": True}),
        "stage": stages.stage_ms(ctx, {"stage": "raft", "maps": maps}),
        "unmapped": stages.stage_ms(ctx, {"share_unmapped": True,
                                          "maps": maps}),
        "serial": stages.serial_ms(ctx, {"stages": ["batch.pad"]}),
    }
    none = {"trace": {"idle", "unnamed", "stage", "unmapped"},
            "annotations": {"idle", "unnamed"},
            "maps": {"stage", "unmapped"},
            "counters": {"stage", "serial"}}[strip]
    assert {k for k, v in got.items() if v is None} == none


# ------------------------------------------------ the trace recorded on the chip

@pytest.fixture(scope="module")
def recorded():
    trace = tracered.reduce_trace(TRACE)
    # the run's own counters over its window and drain: 18 device calls, the
    # first of 1 pair
    prom = {"raft_serving_device_calls_total": 18.0,
            'raft_serving_device_rows_total{kind="real"}': 545.0,
            'raft_serving_device_rows_total{kind="padded"}': 576.0}
    return readers.RunContext(
        config={"iters": 12}, traffic={}, cell={}, records=[], summary={},
        prom_window=prom, max_batch=32, peak={}, memory_peak_bytes=0,
        shapes={}, trace=trace)


def test_the_recording_holds_one_whole_batch_and_a_run_the_tracer_cut(recorded):
    t = recorded.trace
    assert t.clipped and t.window_s == pytest.approx(5.0006, abs=1e-3)
    # the device's tracer started 1.3 ms into the window, inside a run: that
    # run is 661 ms long as far as the trace shows, begins inside the window
    # and is flagged whole; tracered's mean run is then 1.15 s, not 1.65
    runs = [(round(ns / 1e6), whole) for _, ns, whole in t.devices[0]["modules"]]
    assert runs == [(661, True), (1646, True), (960, False)]
    assert t.mean_run_seconds() == pytest.approx(1.1537, abs=2e-3)
    name, ns, full = stages.main_program(t)
    assert full == pytest.approx(1646.14e6, rel=1e-4)
    assert stages.batches_in_window(t) == pytest.approx(1.985, abs=1e-3)
    [(a, b)] = stages.whole_batches(stages.batcher_annotations(t))
    assert (b - a) / 1e6 == pytest.approx(2517.5, abs=0.1)       # one cycle
    by_stage, idle = stages.idle_by_stage(t, within=[(a, b)])
    assert idle / 1e6 == pytest.approx(871.3, abs=0.1)
    # the device ran for the rest of it
    assert (b - a - idle) == pytest.approx(full, rel=1e-3)
    assert by_stage["batch.deliver"] / 1e6 == pytest.approx(254.3, abs=0.1)
    assert by_stage["batch.pad"] / 1e6 == pytest.approx(512.4, abs=0.1)
    assert by_stage[""] / 1e6 < 1.0                 # every gap has a name
    assert by_stage["engine.wait"] / 1e6 < 3.0      # the device runs under it


@pytest.mark.parametrize("metric,want", [
    ("idle_host_prep_ms", 766.88), ("idle_h2d_ms", 67.66),
    ("idle_fetch_ms", 34.14), ("idle_unnamed_share", 0.0783),
    ("corr_ms", 44.98), ("encoders_ms", 3.717), ("upsample_ms", 0.676),
    ("stage_unmapped_share", 0.491)])
def test_each_new_device_metric_reads_a_number_from_the_recording(
        recorded, metric, want, monkeypatch):
    monkeypatch.setattr(stages, "STAGE_MAP_GLOB", STAGES)
    stages.load_stage_maps.cache_clear()
    got = readers.read_metric(BENCH, metric, recorded)
    assert got == pytest.approx(want, rel=2e-3)


def test_the_recordings_identities(recorded, monkeypatch):
    """What PERF.md states of the run the recording is from."""
    monkeypatch.setattr(stages, "STAGE_MAP_GLOB", STAGES)
    stages.load_stage_maps.cache_clear()
    read = lambda m: readers.read_metric(BENCH, m, recorded)  # noqa: E731
    named = read("idle_host_prep_ms") + read("idle_h2d_ms") + read("idle_fetch_ms")
    assert 0.9 * 871.3 < named < 871.3
    # every stage of the map together is the whole program run, to 1 %: the
    # per-event mean times the executions of a run loses only the two
    # events that the window's edges cut short
    found = stages.staged_ops(recorded.trace, stages.load_stage_maps(STAGES), 12)
    assert sum(ns for _, ns in found) == pytest.approx(1646.14e6, rel=1e-2)
    assert read("encoders_ms") + read("upsample_ms") < read("corr_ms") / 5


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
    if f.endswith(".json")))
def test_every_layer_metric_file_loads_and_is_listed(name):
    """Each ``layer_metrics/<name>.json`` names a reader that exists (a kind
    of readers.READERS, or a ``.py`` beside it with ``read``), and the ones
    this benchmark reports are entries of BENCHMARK.json."""
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    py = os.path.join(BENCH, "layer_metrics", name + ".py")
    assert os.path.exists(py) or spec["reader"] in readers.READERS
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    kept_for_later = {"gen_late_ms", "queue_wait_ms", "tail_p95_ms"}
    assert name in listed or name in kept_for_later
