"""Operations and bytes a kernel's ALGORITHM needs, from shapes alone.

A roofline share divides the least time the chip could take (the larger of
operations over the peak rate and bytes over the peak bandwidth) by the time
the trace measured.  Counting is by the algorithm, not by the implementation:
work an implementation adds (one-hot interpolation matmuls, recomputed halos,
padding to tile sizes) does not count, so a share can only be overstated by
miscounting here, never by a wasteful kernel.  Each function returns
``{"ops": ..., "bytes": ...}`` for ONE call on ONE image pair; calls scale
linearly with the batch.

``shapes`` is built from the configuration file by ``grid_shapes``.
"""

from __future__ import annotations


def grid_shapes(config: dict, height: int, width: int) -> dict:
    """The 1/8-resolution recurrence grid of a padded ``height x width``
    frame, and the configuration's widths."""
    h, w = height // 8, width // 8
    small = bool(config["small"])
    return {
        "h": h, "w": w, "q": h * w,
        "fnet_dim": 128 if small else 256,
        "hidden": int(config["hidden_dim"]),
        "motion": 82 if small else 128,
        "levels": int(config["corr_levels"]),
        "radius": int(config["corr_radius"]),
    }


def corr_lookup(s: dict) -> dict:
    """One windowed lookup over every pyramid level with the correlation
    computed on the fly (no stored volume): for each level, every query
    against every position of the pooled second feature map, then a
    (2r+1)^2 bilinear window per query.  float32 throughout."""
    q, c, n = s["q"], s["fnet_dim"], 2 * s["radius"] + 1
    ops = byts = 0
    for lvl in range(s["levels"]):
        p = (s["h"] >> lvl) * (s["w"] >> lvl)
        ops += 2 * q * p * c            # the all-pairs products
        ops += 8 * q * n * n            # 4 taps, a multiply and an add each
        byts += 4 * (q * c + p * c + 2 * q + q * n * n)
    return {"ops": ops, "bytes": byts}


def sep_conv_gru(s: dict) -> dict:
    """One SepConvGRU update with the context terms hoisted: two passes
    (1x5, 5x1), three gates each, every gate a 5-tap contraction of
    hidden + motion channels into hidden channels.  Activations cross
    memory in bfloat16: hidden state in and out, motion features in, six
    hoisted context terms in."""
    q, hid, mot = s["q"], s["hidden"], s["motion"]
    ops = 2 * 3 * 2 * 5 * q * (hid + mot) * hid
    weights = 2 * 3 * 5 * (hid + mot) * hid
    byts = 2 * q * (2 * hid + mot + 6 * hid) + 2 * weights
    return {"ops": ops, "bytes": byts}


COSTS = {"corr_lookup": corr_lookup, "sep_conv_gru": sep_conv_gru}


def min_seconds(cost: dict, peak: dict) -> dict:
    """The least time one call could take on a chip with these peaks, and
    which of the two bounds it."""
    t_ops = cost["ops"] / peak["flops_per_s"]
    t_mem = cost["bytes"] / peak["bytes_per_s"]
    return {"seconds": max(t_ops, t_mem),
            "bound": "compute" if t_ops >= t_mem else "memory"}
