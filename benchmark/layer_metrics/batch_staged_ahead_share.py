"""Share of the device batches the host had ready in time: one counter."""

from readers import _series


def read(ctx, params):
    """100 x ahead / (ahead + late) over the window, or None where the
    program has no such counter (a program older than PR 27) or dispatched
    no pairwise batch in the window."""
    staged = _series(ctx.prom_window, params["counter"])
    total = sum(staged.values())
    if total <= 0:
        return None
    return 100.0 * sum(v for k, v in staged.items()
                       if 'when="ahead"' in k) / total
