from repro.models.model import (init_params, param_specs, init_state,
                                forward_hidden, lm_loss, last_logits,
                                boundary_logits, embed_segments,
                                decode_state_init, decode_state_shapes,
                                decode_state_sharding, decode_step,
                                flush_segment, encode)
