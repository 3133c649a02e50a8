"""Whole-step utilization of decoding: useful FLOPs of the decode steps of
active slots in the traced window (each delivered token was one step, at
its in-segment position; a flush only for a slot that crossed a segment
boundary), over the window's length times the chip's peak."""
import work


def read(run):
    if run.trace is None or not run.decode_positions:
        return None
    T = run.model["armt"]["segment_len"]
    flops = 0.0
    for pos in run.decode_positions:
        flops += work.decode_token_flops(run.model, pos)
        if pos == T - 1:
            flops += work.flush_flops(run.model)
    return 100.0 * flops / (run.trace.window_s * run.trace.n_devices
                            * run.peak["bf16_flops_per_s"])
