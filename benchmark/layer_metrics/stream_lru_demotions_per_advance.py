"""LRU demotions per advance served."""

from stages import _counter


def read(ctx, params):
    """``params["evictions"]{reason="lru"}`` / ``params["frames"]`` over the
    window, or None where nothing was served or the program does not count
    evictions."""
    lru = _counter(ctx.prom_window, params["evictions"], 'reason="lru"')
    frames = _counter(ctx.prom_window, params["frames"])
    return lru / frames if lru is not None and frames else None
