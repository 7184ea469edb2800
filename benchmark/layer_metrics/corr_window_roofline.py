"""The lookup launches' share of the roofline of the WINDOWED algorithm.

``corr_lookup_roofline`` counts every query against every key
(``costs.corr_lookup``): right for a kernel that sets out to do that, and
flattering for one that visits only the key row-blocks a query tile's windows
touch, whose skipped blocks it would count as work done.  Here the work is
what upstream's ``alternate_corr`` does (core/corr.py AlternateCorrBlock):
per level and query, the correlations at the (2r+2)^2 integer positions its
(2r+1)^2 bilinear window reads, each a dot over C channels, and the
interpolation.  A kernel that computes more (whole row-blocks, one-hot
matmuls, padded lanes) is not credited for it, so the share can only be
overstated by miscounting here, never by skipping blocks.  It reads low.
"""

import costs
import readers


def window_lookup(s: dict) -> dict:
    """{"ops", "bytes"} of one windowed lookup of one image pair over every
    level: 2 x q x (2r+2)^2 x C products and 8 x q x (2r+1)^2 interpolation
    operations a level; bytes as ``costs.corr_lookup`` counts them (each map
    read once, coordinates in, windows out, float32)."""
    q, c, r = s["q"], s["fnet_dim"], s["radius"]
    taps, n = (2 * r + 2) ** 2, 2 * r + 1
    ops = s["levels"] * (2 * q * taps * c + 8 * q * n * n)
    return {"ops": ops, "bytes": costs.corr_lookup(s)["bytes"]}


def read(ctx, params):
    """``readers.read_kernel_roofline`` with this file's cost function."""
    costs.COSTS.setdefault("corr_window", window_lookup)
    return readers.read_kernel_roofline(ctx, dict(params, cost="corr_window"))
