"""Host ms a cold restart costs the batcher thread: the stage seconds of the
stages ``params["stages"]`` over the window's cold restarts."""

from stages import _counter


def read(ctx, params):
    """1e3 x Σ ``raft_serving_stage_seconds_total{stage=}`` over
    ``params["stages"]`` / Σ ``params["restarts"]`` (every cause) over the
    window; None where the program has no such stage or counter (a program
    older than PR 41) or nothing restarted."""
    restarts = _counter(ctx.prom_window, params["restarts"])
    secs = [_counter(ctx.prom_window, "raft_serving_stage_seconds_total",
                     f'stage="{s}"') for s in params["stages"]]
    if not restarts or any(v is None for v in secs):
        return None
    return 1e3 * sum(secs) / restarts
