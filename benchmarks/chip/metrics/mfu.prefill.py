"""Whole-step utilization of the prefill: useful FLOPs of the prompt tokens
prefilled in the traced window (memory tokens included, work.py), over the
window's length times the chip's peak."""
import work


def read(run):
    if run.trace is None or not run.prompts_prefilled:
        return None
    flops = sum(work.prompt_flops(run.model, p) for p in run.prompts_prefilled)
    return 100.0 * flops / (run.trace.window_s * run.trace.n_devices
                            * run.peak["bf16_flops_per_s"])
