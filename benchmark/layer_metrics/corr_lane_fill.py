"""Share of the lookup's key lanes that held a key: one labelled counter."""

from readers import _series


def read(ctx, params):
    """100 x ``params["counter"]{kind="live"}`` / ``{kind="stored"}`` over
    the window; None where the program has no such counter (before PR 43) or
    ran no lookup."""
    by_kind = _series(ctx.prom_window, params["counter"])
    stored, live = (sum(v for k, v in by_kind.items()
                        if f'kind="{kind}"' in k)
                    for kind in ("stored", "live"))
    if stored <= 0:
        return None
    return 100.0 * live / stored
