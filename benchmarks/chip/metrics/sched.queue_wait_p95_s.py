"""Scheduler queueing: the start of the scheduler's own ``admission`` span
minus the request's due time, 95th percentile over the requests admitted
in the window."""
from client import _pct


def read(run):
    waits = [run.admit_start[r.idx] - r.t_due for r in run.records
             if r.idx in run.admit_start]
    return _pct(waits, 95) if waits else None
