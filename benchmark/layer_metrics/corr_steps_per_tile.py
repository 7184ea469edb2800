"""Grid steps the lookup's launches took a query tile a level: two counters."""

from readers import _series


def read(ctx, params):
    """steps / tiles over the window, or None where the program has no such
    counter (a program older than PR 38) or ran no lookup."""
    steps = _series(ctx.prom_window, params["steps"])
    tiles = sum(_series(ctx.prom_window, params["tiles"]).values())
    if not steps or tiles <= 0:
        return None
    return sum(steps.values()) / tiles
