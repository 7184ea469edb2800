"""Share of the window's cold restarts that rode their group's batched
call."""

from stages import _counter


def read(ctx, params):
    """100 x ``params["batched"]`` / ``params["restarts"]`` (every cause)
    over the window; None where the program has no such counter (before
    PR 42) or nothing restarted."""
    batched = _counter(ctx.prom_window, params["batched"])
    restarts = _counter(ctx.prom_window, params["restarts"])
    if batched is None or not restarts:
        return None
    return 100.0 * batched / restarts
