"""Share of the window's cold restarts whose cause is the one the traffic
asks for."""

from readers import _series


def read(ctx, params):
    """100 x ``params["counter"]{cause=params["cause"]}`` / the counter over
    every cause, over the window; None where the program has no such counter
    (before PR 41) or nothing restarted."""
    by_cause = _series(ctx.prom_window, params["counter"])
    total = sum(by_cause.values())
    if total <= 0:
        return None
    return 100.0 * sum(v for k, v in by_cause.items()
                       if f'cause="{params["cause"]}"' in k) / total
