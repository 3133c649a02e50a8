"""Decode flush: the share of the rows the masked flush computed that
needed a flush. The scheduler counts both for every chunk from its host
copies (``flush_rows_needed``: rows that crossed a segment boundary;
``flush_rows_computed``: every slot at each step whose flush branch ran)
and hands them to its ``sched.drain`` span, whose profiler event carries
them as stats. Summed over the ``sched.drain`` events in the traced
window."""
DRAIN = "sched.drain"


def read(run):
    tr = run.trace
    data = getattr(tr, "source", None) if tr is not None else None
    if data is None:
        return None
    a, b = tr.window
    need = computed = 0
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            if line.name.startswith("tf_"):
                continue                # runtime threads, not the program's
            for ev in line.events:
                if ev.name != DRAIN or not a <= int(ev.start_ns) <= b:
                    continue
                st = dict(ev.stats)
                need += int(st.get("flush_rows_needed", 0))
                computed += int(st.get("flush_rows_computed", 0))
    if computed <= 0:
        return None
    return 100.0 * need / computed
