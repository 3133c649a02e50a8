"""Decode flush: device time of the innermost operations inside the chunk
program's conditional (the scheduler's ``lax.cond`` around the masked
``flush_segment``), per decode step run: the denominator of
``decode.device_ms_per_step``. A TPU trace carries no scope on its
operations, so there the flush is found by structure: the operations that
a ``conditional`` of ``jit_chunk_fn`` encloses in time on its device.
Where operations carry scopes instead, those under
``serve.flush_segment``."""
import numpy as np

CHUNK_MODULE = "jit_chunk_fn"
FLUSH_SCOPE = "serve.flush_segment"


def flush_ns(trace):
    """Summed device ns of the flush's operations, or None when the trace
    shows neither the chunk program's conditional nor the scope."""
    conds, leaf = [], []
    for o in trace.ops:
        if o.module != CHUNK_MODULE:
            continue
        if " conditional(" in o.name:
            conds.append((o.device, o.t0, o.t1))
        elif o.leaf:
            leaf.append(o)
    if not conds:
        ops = trace.select((FLUSH_SCOPE,))
        return sum(o.dur for o in ops) if ops else None
    # one key per (device, time): the conditionals of a device do not
    # overlap, so an operation lies inside the last one that starts before
    # it, if it ends before that one does
    conds.sort()
    span = trace.window[1] + 1
    c0 = np.array([d * span + a for d, a, _ in conds], np.int64)
    c1 = np.array([d * span + b for d, _, b in conds], np.int64)
    n = len(leaf)
    t0 = np.fromiter((o.device * span + o.t0 for o in leaf), np.int64, n)
    t1 = np.fromiter((o.device * span + o.t1 for o in leaf), np.int64, n)
    i = np.searchsorted(c0, t0, side="right") - 1
    inside = (i >= 0) & (t1 <= c1[np.maximum(i, 0)])
    return int((t1 - t0)[inside].sum())


def read(run):
    if run.trace is None or not run.decode_steps:
        return None
    ns = flush_ns(run.trace)
    if ns is None:
        return None
    return ns * 1e-6 / run.trace.n_devices / run.decode_steps
