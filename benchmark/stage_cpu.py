"""Readers of the host stages' CPU seconds (PR 37).

Beside ``raft_serving_stage_seconds_total{stage=}`` the program counts
``raft_serving_stage_cpu_seconds_total{stage=}``: the same stages on their
own thread's CPU clock (``raft_tpu/telemetry/trace.py``: ``host_stage``,
``StageCounters``).  A stage's wall seconds less its CPU seconds are what its
thread spent not running; for a stage that waits for no device and no socket
that is the interpreter lock, a registry or trace lock and the run queue.

A program that lacks the family (the parent of the PR that added it) gives
nothing to read: ``None``, and the metric is left out.
"""

from __future__ import annotations

from typing import Optional

from stages import _counter

WALL = "raft_serving_stage_seconds_total"
CPU = "raft_serving_stage_cpu_seconds_total"


def _seconds(prom: dict, family: str, stages: list) -> Optional[float]:
    vals = [_counter(prom, family, f'stage="{s}"') for s in stages]
    return None if any(v is None for v in vals) else sum(vals)


def ms(ctx, params) -> Optional[float]:
    """Milliseconds of the stages ``params["stages"]`` over the window, per
    increment of the counter ``params["per"]`` (narrowed to the series that
    hold ``params["per_label"]``): their CPU seconds (``"what": "cpu"``) or
    their wall seconds less their CPU seconds (``"what": "offcpu"``)."""
    prom = ctx.prom_window
    per = _counter(prom, params["per"], params.get("per_label"))
    cpu = _seconds(prom, CPU, params["stages"])
    if not per or cpu is None:
        return None
    if params["what"] == "cpu":
        return 1e3 * cpu / per
    if params["what"] == "offcpu":
        wall = _seconds(prom, WALL, params["stages"])
        return None if wall is None else 1e3 * (wall - cpu) / per
    raise ValueError(f"stage_cpu.ms: what={params['what']!r}")
