"""Per-layer metric readers.

A per-layer metric is a file ``layer_metrics/<metric>.json``
(``{"reader": kind, "params": {...}}``) or, where no kind below covers it, a
sibling ``layer_metrics/<metric>.py`` with one function ``read(ctx, params)``.
A reader takes the metric from what a run recorded (``RunContext``) and
returns a number, or ``None`` when it finds nothing to read: the harness then
leaves the metric out of the result line.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Optional

import costs
import loadgen


@dataclasses.dataclass
class RunContext:
    config: dict                 # the configuration file
    traffic: dict                # the traffic file
    cell: dict                   # the workload file
    records: list                # loadgen.Record of the window
    summary: dict                # loadgen.summarize of the window
    prom_window: dict            # /metrics, after minus before the window
    max_batch: int
    peak: dict                   # this device's entry of peaks.json
    memory_peak_bytes: int
    shapes: dict                 # costs.grid_shapes
    trace: Optional[object] = None   # tracered.Trace, in a --trace 1 run


def _series(prom: dict, name: str) -> dict:
    return {k: v for k, v in prom.items() if k.split("{", 1)[0] == name}


def hist_buckets(prom: dict, name: str) -> list:
    """[(le, count in that bucket alone), ...] of a histogram's difference."""
    pts = []
    for k, v in _series(prom, name + "_bucket").items():
        m = re.search(r'le="([^"]+)"', k)
        if m and m.group(1) != "+Inf":
            pts.append((float(m.group(1)), v))
    pts.sort()
    out, prev = [], 0.0
    for le, cum in pts:
        out.append((le, cum - prev))
        prev = cum
    return out


def mean_padded_batch(ctx: RunContext) -> Optional[float]:
    """Mean rows per device batch as executed (padded up to a batch step):
    the batch-size histogram's buckets sit on the batch steps."""
    b = hist_buckets(ctx.prom_window, "raft_serving_batch_size")
    n = sum(c for _, c in b)
    return sum(le * c for le, c in b) / n if n else None


def mean_real_batch(ctx: RunContext) -> Optional[float]:
    s = sum(_series(ctx.prom_window, "raft_serving_batch_size_sum").values())
    n = sum(_series(ctx.prom_window, "raft_serving_batch_size_count").values())
    return s / n if n else None


# ------------------------------------------------------------------ readers

def read_loadgen(ctx, p):
    return ctx.summary.get(p["field"])


def read_spans(ctx, p):
    """A statistic over the window's answered requests of a sum of server
    spans (``X-Raft-Timings``, ms) and of the client's own clock:
    ``client_ms`` is send-to-answer as the client saw it."""
    vals = []
    for r in ctx.records:
        if r.status != 200 or r.timings is None:
            continue
        t = dict(r.timings, client_ms=(r.done - r.sent) * 1e3)
        try:
            vals.append(sum(t[k] for k in p.get("plus", []))
                        - sum(t[k] for k in p.get("minus", [])))
        except KeyError:
            continue
    if not vals:
        return None
    return loadgen.percentile(vals, float(p.get("percentile", 50)))


def read_prometheus(ctx, p):
    prom = ctx.prom_window
    if p["what"] == "counter":
        s = _series(prom, p["name"])
        return sum(s.values()) if s else None
    if p["what"] == "batch_fill":
        m = mean_real_batch(ctx)
        return None if m is None else 100.0 * m / ctx.max_batch
    raise ValueError(f"prometheus reader: what={p['what']!r}")


def read_device_trace(ctx, p):
    """Numbers of the traced window.  ``idle_share``: the share of it in
    which no operation ran.  ``op_ms_per_pair``: device milliseconds per
    image pair spent in the instructions whose name matches (``inside``), or
    in the program runs outside them (``inside: false``): the mean whole
    program run, over the mean real batch, times the matching share of the
    whole runs' time."""
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    if p["what"] == "idle_share":
        return 100.0 * tr.idle_share()
    if p["what"] == "op_ms_per_pair":
        prog = p.get("program", "")
        run_s, rows = tr.mean_run_seconds(prog), mean_real_batch(ctx)
        if not run_s or not rows:
            return None
        share = (tr.op_seconds(p["match"], whole_runs=True)
                 / (run_s * tr.module_runs(prog)))
        if not p.get("inside", True):
            share = 1.0 - share
        return 1e3 * run_s / rows * share
    raise ValueError(f"device_trace reader: what={p['what']!r}")


def read_kernel_roofline(ctx, p):
    """Share of its roofline that a kernel reached: the least seconds for
    the calls the trace holds (cost function of one call on one pair, times
    the rows each call carried, times the calls) over the device seconds of
    the kernel's events.  One call may be several events (``events_per_call``:
    the lookup is one kernel launch per pyramid level)."""
    tr = ctx.trace
    if tr is None:
        return None
    ops = tr.select(p["match"])
    total_s = sum(o.total_ns for o in ops) / 1e9
    events = sum(o.count for o in ops)
    rows = mean_padded_batch(ctx)
    if not events or total_s <= 0 or not rows:
        return None
    calls = events / float(p.get("events_per_call", 1))
    least = costs.min_seconds(costs.COSTS[p["cost"]](ctx.shapes), ctx.peak)
    return 100.0 * least["seconds"] * rows * calls / total_s


def read_memory_stats(ctx, p):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None


READERS = {
    "loadgen": read_loadgen,
    "spans": read_spans,
    "prometheus": read_prometheus,
    "device_trace": read_device_trace,
    "kernel_roofline": read_kernel_roofline,
    "memory_stats": read_memory_stats,
}


def read_metric(bench_dir: str, name: str, ctx: RunContext):
    """The value of per-layer metric ``name`` in this run, or None."""
    base = os.path.join(bench_dir, "layer_metrics", name)
    spec = {}
    if os.path.exists(base + ".json"):
        with open(base + ".json") as f:
            spec = json.load(f)
    if os.path.exists(base + ".py"):
        mod_spec = importlib.util.spec_from_file_location(
            "layer_metric_" + re.sub(r"\W", "_", name), base + ".py")
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read(ctx, spec.get("params", {}))
    if not spec:
        raise FileNotFoundError(f"no reader for per-layer metric {name!r}: "
                                f"{base}.json or {base}.py")
    return READERS[spec["reader"]](ctx, spec.get("params", {}))
