"""int8 slot traffic against the bandwidth, the rows counted as STORED.

``slot_io_roofline`` prices a row at the compute dtype's width, read and
written by the gather and again by the commit; under ``--quant int8`` that
credits the rows with twice their bytes.  Here the cost function counts what
crosses memory in the format it is held in.
"""

from __future__ import annotations

from typing import Optional

import costs
import readers
import stream_metrics


def slot_io_stored(s: dict) -> dict:
    """{"ops", "bytes"} of moving ONE session's slot through one advance AS
    IT IS STORED: the gather reads the int8 maps, their float32 scales and
    the float32 seed and writes the maps in the compute dtype; the commit
    reads the new maps in the compute dtype and writes int8 maps, scales
    and seed.  No operations: the quantiser's arithmetic is not what bounds
    it."""
    channels = s["fnet_dim"] + s["slot_channels"]
    maps = s["q"] * channels
    side = 4 * channels + 2 * 4 * s["q"]          # scales and seed
    return {"ops": 0,
            "bytes": 2 * (maps * (1 + s["compute_itemsize"]) + side)}


def read(ctx, params) -> Optional[float]:
    """The least time of moving a padded batch's rows as stored
    (``slot_io_stored`` x the rows, over the chip's bandwidth) over
    ``slot_io_ms``; None for a configuration whose slots are not int8."""
    program = ctx.config.get("program", {})
    if "int8" not in str(program.get("quant", "")):
        return None
    ms, rows = (stream_metrics.slot_io_ms(ctx, params),
                readers.mean_padded_batch(ctx))
    if not ms or not rows or not ctx.peak:
        return None
    shapes = dict(ctx.shapes,
                  compute_itemsize=(2 if program.get("compute_dtype")
                                    == "bfloat16" else 4),
                  slot_channels=(int(ctx.config["hidden_dim"])
                                 + int(ctx.config["context_dim"])))
    least = costs.min_seconds(slot_io_stored(shapes), ctx.peak)
    return 100.0 * least["seconds"] * rows / (ms / 1e3)
