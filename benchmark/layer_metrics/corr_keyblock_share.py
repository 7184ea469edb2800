"""Share of the all-blocks walk the lookup kernel still does: two counters."""

from readers import _series


def read(ctx, params):
    """100 x visited / possible key-block steps over the window, or None
    where the program has no such counters (a program older than PR 26)."""
    visited = _series(ctx.prom_window, params["visited"])
    possible = sum(_series(ctx.prom_window, params["possible"]).values())
    if not visited or possible <= 0:
        return None
    return 100.0 * sum(visited.values()) / possible
