"""Decode: device time of the operations under ``serve.decode_chunk``, per
decode step run (chunks launched in the window times the chunk length)."""
from names import DECODE_SCOPES


def read(run):
    if run.trace is None or not run.decode_steps:
        return None
    ops = run.trace.select(DECODE_SCOPES)
    if not ops:
        return None
    return sum(o.dur for o in ops) * 1e-6 / run.trace.n_devices \
        / run.decode_steps
