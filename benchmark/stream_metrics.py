"""Readers of what video sessions add to the program (PR 39).

Counters of the stream path: ``raft_stream_fnet_cache_{hits,misses}_total``
(an advance served from its session's resident slot, or cold),
``raft_stream_encoder_passes_total{call=}`` (the engine's own ``encode`` and
``stream`` call counters), ``raft_stream_frames_total`` (advances served),
the three ``raft.stream.*`` host stages of a batched advance in
``raft_serving_stage_seconds_total`` (``raft_tpu/serving/stream.py::
_warm_batch``), and ``raft_serving_batch_size_count``: the device batches of
the window, which on this path are the batched advances alone (an open is a
device call of its own and is in no batch).  In the device trace: the slot
gather under the stream batch program's ``raft/stream/gather`` scope (the
engine's instruction -> stage map, ``stages.staged_ops``) and the slot
commit's own program, ``jit_slot_commit``.

A program that lacks a source (the parent of the PR that added it) gives a
reader nothing to read: it returns None and the metric is left out.
"""

from __future__ import annotations

import re
from typing import Optional

import costs
import readers
import stages
import tracered
from stages import _counter


def warm_share(ctx, params) -> Optional[float]:
    """100 x advances served from a resident slot / all advances."""
    hits = _counter(ctx.prom_window, params["hits"])
    misses = _counter(ctx.prom_window, params["misses"])
    if hits is None or misses is None or hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)


def fnet_passes_per_pair(ctx, params) -> Optional[float]:
    """Encoder passes per advance served: 1 + opens / advances in a sound
    run (2 for stateless pairs of the same frames)."""
    passes = _counter(ctx.prom_window, params["passes"])
    frames = _counter(ctx.prom_window, params["frames"])
    return passes / frames if passes is not None and frames else None


def stage_ms_per_batch(ctx, params) -> Optional[float]:
    """Wall ms of the host stage ``params["stage"]`` per device batch."""
    secs = _counter(ctx.prom_window, "raft_serving_stage_seconds_total",
                    f'stage="{params["stage"]}"')
    batches = _counter(ctx.prom_window, "raft_serving_batch_size_count")
    return 1e3 * secs / batches if secs is not None and batches else None


def _scope_ns(trace, maps: list, scope: str) -> Optional[float]:
    """Device ns of one run of the window's main program under the
    ``stage()`` scope ``scope`` (a regular expression searched in the stage
    path): the program's own instructions there (the
    map's ``loop`` 0), each by the mean of its events.  The compiler makes a
    row gather a ``while`` over the rows, and a ``while``'s event holds its
    body's: the loops are taken whole and what is inside them is not taken
    again.  (``stages.staged_ops`` leaves containers out and would take a
    body's instruction ``iters`` times, which is the update loop's count and
    not this one's.)"""
    if trace is None or not maps:
        return None
    ops = [op for op in trace.ops() if op.count > 0]
    plain = [op for op in ops if not tracered.CONTAINERS.match(op.name)]
    if not plain:
        return None
    best = max(maps, key=lambda m: sum(op.total_ns for op in plain
                                       if op.label in m))
    rx = re.compile(scope)
    found = [op.total_ns / op.count for op in ops if op.label in best
             and best[op.label][1] == 0
             and rx.search(best[op.label][0] or "")]
    return sum(found) if found else None


def slot_io_ms(ctx, params) -> Optional[float]:
    """Device ms per batched advance moving slot rows: the gather inside the
    stream batch program (the scope ``params["stage"]``) and one whole run
    of the commit program (``params["program"]``; of its executables, the one
    the window spent most time in: the batch's; an open's commits one row)."""
    tr = ctx.trace
    gather = _scope_ns(tr, stages.load_stage_maps(params.get("maps")),
                       params["stage"])
    if gather is None:
        return None
    by_name: dict = {}
    for name, ns, whole in tr._modules(params["program"]):
        by_name.setdefault(name, []).append((ns, whole))
    if not by_name:
        return None
    runs = max(by_name.values(), key=lambda rs: sum(ns for ns, _ in rs))
    whole = [ns for ns, w in runs if w]
    if not whole:
        return None
    return (gather + sum(whole) / len(whole)) / 1e6


def slot_io(s: dict) -> dict:
    """{"ops", "bytes"} of moving ONE session's slot through one advance: its
    row (the fnet and cnet maps at ``slot_itemsize`` bytes an element, the
    seed in float32) read and written by the gather, and the new row read
    and written by the commit."""
    row = s["q"] * ((s["fnet_dim"] + s["slot_channels"]) * s["slot_itemsize"]
                    + 2 * 4)
    return {"ops": 0, "bytes": 4 * row}


def slot_io_roofline(ctx, params) -> Optional[float]:
    """The least time of moving a batch's slot rows (``slot_io`` x the rows
    of a padded batch, over the chip's bandwidth) over ``slot_io_ms``."""
    ms, rows = slot_io_ms(ctx, params), readers.mean_padded_batch(ctx)
    if not ms or not rows or not ctx.peak:
        return None
    cost = costs.COSTS.setdefault("slot_io", slot_io)
    bf16 = ctx.config.get("program", {}).get("compute_dtype") == "bfloat16"
    shapes = dict(ctx.shapes, slot_itemsize=2 if bf16 else 4,
                  slot_channels=(int(ctx.config["hidden_dim"])
                                 + int(ctx.config["context_dim"])))
    least = costs.min_seconds(cost(shapes), ctx.peak)
    return 100.0 * least["seconds"] * rows / (ms / 1e3)
