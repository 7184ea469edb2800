"""The 3x3 ConvGRU's share of its roofline (RAFT-S, core/update.py ConvGRU).

The time is what ``convgru_ms`` reads: the device time a pair under the
``update/gru`` scope, over a whole program run (``iters`` updates).  The
work is the algorithm's: three gates, each a 3x3 convolution of hidden +
motion channels into hidden channels; the context channels' part of every
gate is the same in every iteration and hoisted out of the loop, so it is
neither counted here nor timed under this scope.
"""

import costs
import stages


def conv_gru(s: dict) -> dict:
    """{"ops", "bytes"} of ONE ConvGRU update of one image pair with the
    context terms hoisted: 2 x 3 gates x 9 taps x q x (hidden + motion) x
    hidden operations; activations cross memory in bfloat16: hidden state in
    and out, motion features in, three hoisted context terms in; and the
    weights."""
    q, hid, mot = s["q"], s["hidden"], s["motion"]
    ops = 2 * 3 * 9 * q * (hid + mot) * hid
    weights = 3 * 9 * (hid + mot) * hid
    byts = 2 * q * (2 * hid + mot + 3 * hid) + 2 * weights
    return {"ops": ops, "bytes": byts}


def read(ctx, params):
    if not ctx.config.get("small"):
        return None
    ms = stages.stage_ms(ctx, params)
    if not ms:
        return None
    cost = costs.COSTS.setdefault("conv_gru", conv_gru)
    least = costs.min_seconds(cost(ctx.shapes), ctx.peak)
    return 100.0 * least["seconds"] * int(ctx.config["iters"]) / (ms / 1e3)
