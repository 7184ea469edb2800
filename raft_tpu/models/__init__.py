from .encoders import apply_encoder, init_encoder
from .raft import (RAFTOutput, encode_frame, forward_from_features,
                   init_raft, make_encode_fn,
                   make_inference_fn, make_stream_batch_step_fn,
                   make_stream_step_fn, raft_forward)
from .update import (apply_basic_update_block, apply_small_update_block,
                     init_basic_update_block, init_small_update_block)
