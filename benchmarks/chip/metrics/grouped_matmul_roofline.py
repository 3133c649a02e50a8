"""The grouped_matmul kernel's share of its roofline in the diagonal prefill: the
least time the chip needs for the useful work of its calls (work.py, per
(segment, layer) cell prefilled), over the kernel's summed device time."""
import work
from names import kernel_ops


def read(run):
    if run.trace is None or not run.segments_prefilled:
        return None
    ops = kernel_ops(run.trace, "grouped_matmul")
    t = sum(o.dur for o in ops) * 1e-9
    if t <= 0:
        return None
    f, b = work.grouped_matmul_cell(run.model)
    cells = run.segments_prefilled * run.model["n_layers"]
    return 100.0 * cells * work.roofline_seconds(f, b, run.peak) / t
