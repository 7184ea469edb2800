"""Device time of RAFT-S's update block outside its ConvGRU: the motion
encoder and the flow head (``update/motion_encoder``, ``update/heads``),
by stages.stage_ms.  The full model's update block has a mask head and other
widths under the same scopes: the reader answers for the small model alone.
"""

import stages


def read(ctx, params):
    if not ctx.config.get("small"):
        return None
    return stages.stage_ms(ctx, params)
