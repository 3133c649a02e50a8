"""Device idle share of the traced window that the host caused: the part
of the device's idle intervals (those of ``device_idle.decode``) during
which the scheduler was inside one of its phase spans other than
``sched.wait`` (``SCHED_PHASES``, ``repro.serve.telemetry``), over the
window's length. Idle inside ``sched.wait`` is a lull in arrivals, with
nothing to decode or admit; idle outside every phase span is neither."""
from tracefile import union

WAIT = "sched.wait"


def overlap_ns(a, b):
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    tot = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def read(run):
    try:
        from repro.serve.telemetry import SCHED_PHASES
    except ImportError:
        return None                     # a program without phase spans
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    host = set(SCHED_PHASES) - {WAIT}
    spans = union(sorted((s.t0, s.t1) for s in tr.spans if s.name in host))
    if not spans:
        return None
    idle = overlap_ns(tr.idle_gaps(0), spans)
    return 100.0 * idle / (tr.window[1] - tr.window[0])
