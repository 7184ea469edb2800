"""Readers of what the program's own tracing adds to a run (PR 24).

Three sources, each written by the program at ONE site per stage
(``raft_tpu/telemetry/trace.py``: ``host_stage``, ``instruction_stages``):

* ``raft.*`` host annotations in the profiler's trace, on the device's clock:
  ``idle_ms`` lays the busiest device's idle gaps over the batcher thread's
  annotations and says which stage the device was waiting for;
* ``raft_serving_stage_seconds_total{stage=}`` and
  ``raft_serving_device_calls_total``: ``serial_ms`` is the batcher thread's
  milliseconds per device batch in which it does not wait for the device;
* the instruction -> ``stage()`` map the engine writes beside each entry of
  its AOT cache: ``stage_ms`` joins it with the trace's operations, which
  carry the instruction's name and no scope.

A program that lacks a source (the parent of the PR that added it) gives a
reader nothing to read: it returns None and the metric is left out.  Every
function takes ``readers.RunContext`` (or its ``trace``) and nothing else.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

import tracered

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
# where system.start() puts the engine's AOT cache: <config>/<identity>/
STAGE_MAP_GLOB = os.path.join(BENCH_DIR, ".cache", "engine", "*", "*",
                              "*.stages.json")
# annotations of the thread that owns the device; the handler threads'
# (raft.http.*) overlap them and explain no gap
BATCHER = re.compile(r"^raft\.(batch|engine)\.(\w+)")


# ------------------------------------------------------------ device batches

def busiest(trace) -> Optional[dict]:
    if trace is None or not trace.devices:
        return None
    return max(trace.devices.values(), key=lambda d: d["busy_ns"])


def main_program(trace) -> Optional[Tuple[str, float, float]]:
    """(name, ns inside the window, ns of one whole run) of the
    program that took most of the busiest device's time.  Runs of any OTHER
    program in the window, however small, are not counted: a mean over every
    program's runs is a mean over unlike things.  One whole run is the
    LONGEST the window holds whole: the device's tracer starts some hundred
    ms after the window's annotation, so the run it starts in begins, as far
    as the trace shows, inside the window, and is flagged whole at a
    fraction of its length (my chip run, PR 24: 0.60 s beside 1.65 s)."""
    dev = busiest(trace)
    if dev is None:
        return None
    by_name: Dict[str, list] = {}
    for name, ns, whole in dev["modules"]:
        by_name.setdefault(name, []).append((ns, whole))
    if not by_name:
        return None
    name, runs = max(by_name.items(), key=lambda kv: sum(r[0] for r in kv[1]))
    whole = [ns for ns, w in runs if w]
    if not whole:
        return None
    return name, sum(ns for ns, _ in runs), max(whole)


def batches_in_window(trace) -> Optional[float]:
    """Device batches the window holds, counted whole and in part: the main
    program's nanoseconds inside the window over those of one whole run.  (A
    5 s window of a 2.7 s cycle holds one whole run and parts of two more;
    idle time divided by the whole runs alone would read twice too high.)"""
    prog = main_program(trace)
    return None if prog is None else prog[1] / prog[2]


# ------------------------------------------------------- idle gaps by stage

def batcher_annotations(trace) -> List[Tuple[float, float, str]]:
    """[(start, end, stage)] of the batcher thread, in time order; a stage
    is the annotation's name less ``raft.``: ``batch.pad``, ``engine.h2d``."""
    anns = []
    for s, e, name in trace.host_events:
        m = BATCHER.match(name)
        if m:
            anns.append((s, e, f"{m.group(1)}.{m.group(2)}"))
    anns.sort()
    return anns


def whole_batches(anns: list) -> List[Tuple[float, float]]:
    """[(start, end)] of the device batches the window holds whole.  A batch
    runs from the end of its ``engine.dispatch`` (the device starts within a
    millisecond of it) to the end of the next one: the device's run, then
    everything the host does until the next run.  (Not from ``engine.wait``:
    an annotation that is open when the capture starts or stops is not in
    the trace at all, and a ``wait`` usually is.)"""
    ends = [e for _, e, stage in anns if stage == "engine.dispatch"]
    return list(zip(ends, ends[1:]))


def idle_by_stage(trace, within: list = None):
    """({stage: idle ns of the busiest device under that annotation of the
    batcher's thread}, idle ns in all), over the whole window or over the
    intervals ``within``.  Idle time under no annotation is ``""``."""
    dev = busiest(trace)
    if dev is None:
        return None
    anns = batcher_annotations(trace)
    if not anns:
        return None
    gaps = sorted(dev["gaps"])
    if within is not None:
        gaps = [(max(a, lo), min(b, hi)) for a, b in gaps
                for lo, hi in within if min(b, hi) > max(a, lo)]
    out: Dict[str, float] = {"": 0.0}
    total = 0.0
    lo = 0
    for a, b in gaps:
        total += b - a
        named = 0.0
        while lo < len(anns) and anns[lo][1] <= a:
            lo += 1
        i = lo
        while i < len(anns) and anns[i][0] < b:
            ov = min(anns[i][1], b) - max(anns[i][0], a)
            if ov > 0:
                out[anns[i][2]] = out.get(anns[i][2], 0.0) + ov
                named += ov
            i += 1
        out[""] += max(0.0, (b - a) - named)
    return out, total


def idle_ms(ctx, params) -> Optional[float]:
    """Device-idle milliseconds per whole device batch of the window under
    the stages ``params["stages"]``; or, with ``"share_unnamed": true``, the
    percentage of those batches' idle time that lies under no ``raft.*``
    annotation.  Whole batches only, because the window's edges are not the
    program's doing: the device's tracer starts some tens of ms after the
    window's annotation, and until then the device looks idle.  A 5 s window
    of a 2.7 s cycle holds one whole batch, now and then two; where it holds
    none (one run in six, by the arithmetic), the whole window's idle time
    is taken, divided by the batches it holds in part
    (``batches_in_window``), which reads up to a sixth off."""
    if busiest(ctx.trace) is None:
        return None
    whole = whole_batches(batcher_annotations(ctx.trace)) or None
    found = idle_by_stage(ctx.trace, within=whole)
    if found is None:
        return None
    by_stage, total = found
    if params.get("share_unnamed"):
        return 100.0 * by_stage[""] / total if total else None
    n = len(whole) if whole else batches_in_window(ctx.trace)
    if not n:
        return None
    return sum(by_stage.get(s, 0.0) for s in params["stages"]) / n / 1e6


# ------------------------------------------------------------ stage seconds

def _counter(prom: dict, name: str, label: str = None) -> Optional[float]:
    vals = [v for k, v in prom.items() if k.split("{", 1)[0] == name
            and (label is None or label in k)]
    return sum(vals) if vals else None


def serial_ms(ctx, params) -> Optional[float]:
    """Sum of the stage seconds of ``params["stages"]`` over the window, per
    device call, in ms."""
    calls = _counter(ctx.prom_window, "raft_serving_device_calls_total")
    secs = [_counter(ctx.prom_window, "raft_serving_stage_seconds_total",
                     f'stage="{s}"') for s in params["stages"]]
    if not calls or any(v is None for v in secs):
        return None
    return 1e3 * sum(secs) / calls


def real_rows_per_call(ctx) -> Optional[float]:
    calls = _counter(ctx.prom_window, "raft_serving_device_calls_total")
    real = _counter(ctx.prom_window, "raft_serving_device_rows_total",
                    'kind="real"')
    return real / calls if calls and real else None


# ------------------------------------------------- instruction -> stage map

@functools.lru_cache(maxsize=4)
def load_stage_maps(pattern: str = None) -> List[Dict[str, tuple]]:
    """Every map found: {operation label as tracered makes it: (stage,
    while bodies around the instruction)}."""
    maps = []
    for path in sorted(glob.glob(pattern or STAGE_MAP_GLOB)):
        try:
            with open(path) as f:
                doc = json.load(f)
            maps.append({tracered.op_label(rec["text"]):
                         (rec["stage"], int(rec.get("loop", 0)))
                         for rec in doc["instructions"].values()})
        except (OSError, ValueError, KeyError, TypeError):
            continue
    return maps


def staged_ops(trace, maps: list, iters: int) -> Optional[List[tuple]]:
    """[(stage, device ns of one program run in that operation)] for the
    window's operations, under the map of the executable they belong to: the
    one whose instructions account for most of their time.  A label holds
    the result's shape, so the batch-32 program's map does not claim the
    batch-16 program's operations; containers (a ``while`` holds its body's
    events) and operations of other programs are left out.

    One run's time in an operation is the MEAN of its events in the window
    times the executions a run makes of it (once, or ``iters`` times inside
    the update loop: the map's ``loop``).  No count of program runs enters:
    which runs a window holds whole cannot be told from the trace alone (the
    device's tracer starts some hundred ms after the window's annotation,
    and the run it starts in looks whole and is not), and the per-event mean
    does not care."""
    if trace is None or not maps:
        return None
    ops = [op for op in trace.ops() if op.count > 0
           and not tracered.CONTAINERS.match(op.name)]
    if not ops:
        return None
    best = max(maps, key=lambda m: sum(op.total_ns for op in ops
                                       if op.label in m))
    found = [(best[op.label][0],
              op.total_ns / op.count * iters ** best[op.label][1])
             for op in ops if op.label in best]
    return found or None


def stage_ms(ctx, params) -> Optional[float]:
    """Device milliseconds per image pair in the instructions whose stage
    matches ``params["stage"]`` (a regular expression searched in the
    ``stage()`` path), per run of THAT program over the real rows a device
    call carried; or, with ``"share_unmapped": true``, the percentage of the
    program's busy time in instructions that the map gives no stage."""
    found = staged_ops(ctx.trace, load_stage_maps(params.get("maps")),
                       int(ctx.config.get("iters", 1)))
    if found is None:
        return None
    if params.get("share_unmapped"):
        total = sum(ns for _, ns in found)
        return 100.0 * sum(ns for st, ns in found if not st) / total
    rows = real_rows_per_call(ctx)
    if not rows:
        return None
    rx = re.compile(params["stage"])
    return sum(ns for st, ns in found if st and rx.search(st)) / rows / 1e6
