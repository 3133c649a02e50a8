"""Diagonal prefill: device time of the operations under the prefill
scopes (``serve.diag_stage``, ``serve.pooled_diag_round``, ...), per full
prompt segment prefilled in the traced window."""
from names import PREFILL_SCOPES


def read(run):
    if run.trace is None or not run.segments_prefilled:
        return None
    ops = run.trace.select(PREFILL_SCOPES)
    if not ops:
        return None
    dev_ms = sum(o.dur for o in ops) * 1e-6 / run.trace.n_devices
    return dev_ms / run.segments_prefilled
