"""Bands of key rows a query tile took a level: two counters."""

from readers import _series


def read(ctx, params):
    """visited / tiles over the window, or None where the program has no
    such counters (a program older than PR 36) or ran no lookup."""
    visited = _series(ctx.prom_window, params["visited"])
    tiles = sum(_series(ctx.prom_window, params["tiles"]).values())
    if not visited or tiles <= 0:
        return None
    return sum(visited.values()) / tiles
