"""Readers of what the int8 slot pool adds to the program (PR 45).

Three sources.  GAUGES of the pool: ``raft_stream_pool_bytes{leaf=}`` (the
device bytes of the pool's buffers, from the arrays themselves) beside
``raft_stream_slots_in_use`` / ``raft_stream_slot_capacity``.  A gauge's
difference over the window is 0, and ``run.py`` hands a reader differences
alone, so these are read where the server's metric history spills a snapshot
of every family a second and one at its stop (``<out>/metrics_ts.jsonl``,
``raft_tpu/telemetry/timeseries.py``; ``system.start`` puts ``<out>`` under
``.cache/out/<configuration>``): the LAST sample is the window's end, the
sessions still open.  ``stage()`` SCOPES in the device trace:
``raft/stream/gather/dequant`` in the batched step and
``raft/stream/commit/quant`` in the commit programs, through the engine's
instruction -> stage maps as ``slot_io_ms`` reads its own.  (``slot_io_ms``
against the least time of moving the rows as they are STORED is
``layer_metrics/slot_io_int8_roofline.py``'s, cost function and all.)

A program that lacks a source (the parent of the PR that added it) gives a
reader nothing to read: it returns None and the metric is left out.
"""

from __future__ import annotations

import json
import os
import re
from typing import Optional

import stages
import stream_metrics

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def last_sample(ctx, path: str = None,
                bench_dir: str = BENCH_DIR) -> Optional[dict]:
    """The newest snapshot of the server's metric history: {family: value,
    or {label values: value}}; None where there is no spill.  ``bench_dir``:
    the benchmark the run is made from (a reader's own file says)."""
    path = path or os.path.join(bench_dir, ".cache", "out",
                                str(ctx.config.get("name", "")),
                                "metrics_ts.jsonl")
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            # a sample is one line of some tens of KB: the file's tail holds
            # the last one whole
            f.seek(max(0, size - (4 << 20)))
            lines = f.read().splitlines()
    except OSError:
        return None
    for line in reversed(lines):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and rec.get("kind") == "sample":
            return rec.get("snap")
    return None


def _total(snap: Optional[dict], family: str) -> Optional[float]:
    """A family's children summed (a bare value as it is); None where the
    snapshot lacks the family."""
    v = (snap or {}).get(family)
    if v is None:
        return None
    return float(sum(v.values())) if isinstance(v, dict) else float(v)


def slot_pool_gb(ctx, params, bench_dir: str = BENCH_DIR) -> Optional[float]:
    """The pool's leaves on the device at the window's end, GB."""
    total = _total(last_sample(ctx, params.get("history"), bench_dir),
                   params["bytes"])
    return total / 1e9 if total else None


def slot_fill(ctx, params, bench_dir: str = BENCH_DIR) -> Optional[float]:
    """100 x slots in use / slots declared at the window's end."""
    snap = last_sample(ctx, params.get("history"), bench_dir)
    used, cap = _total(snap, params["in_use"]), _total(snap, params["capacity"])
    return 100.0 * used / cap if used is not None and cap else None


def scope_ms(ctx, params) -> Optional[float]:
    """Device ms of one run of a program under the ``stage()`` scope
    ``params["stage"]``: of the executables whose map holds the scope at
    all (the batched step's for the dequantiser, the commits' for the
    quantiser), the one the window spent most time in (the batch's: an
    open's commit is the one-row program), each instruction by the mean of
    its events (``stream_metrics._scope_ns``)."""
    rx = re.compile(params["stage"])
    maps = [m for m in stages.load_stage_maps(params.get("maps"))
            if any(rx.search(st or "") for st, _ in m.values())]
    ns = stream_metrics._scope_ns(ctx.trace, maps, params["stage"])
    return None if ns is None else ns / 1e6
