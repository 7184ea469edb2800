"""Device time of RAFT-S's 3x3 ConvGRU: stages.stage_ms under ``update/gru``.

The full model's SepConvGRU runs under the same scope (as one fused kernel,
read by ``gru_roofline``), so the reader answers for the small model alone.
"""

import stages


def read(ctx, params):
    if not ctx.config.get("small"):
        return None
    return stages.stage_ms(ctx, params)
