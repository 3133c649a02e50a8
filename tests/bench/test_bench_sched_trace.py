"""The readers of the scheduler's phase spans, chunk counters and flush
branch, on a small trace laid out as the JAX profiler writes a TPU one:
the device's ``XLA Ops`` named by their HLO text, with no scope (as
``enable_hlo_proto=False`` leaves them) or with one; the program's host
spans on its Python thread, ``sched.drain`` carrying the chunk's counters
as stats; a runtime thread the readers leave alone."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import run  # noqa: E402
import tracefile as trace  # noqa: E402

MS = 1_000_000
CHUNK = 8


def ev(name, t0_ms, dur_ms, **stats):
    return NS(name=name, start_ns=int(t0_ms * MS), duration_ns=int(dur_ms * MS),
              stats=list(stats.items()))


def cond(t0, dur):
    return ev("%cond.34 = (f32[24,16,384,2560]{3,2,1,0}) conditional(%p, "
              "%get-tuple-element.9, %get-tuple-element.9), "
              "branch_computations={%region_13.25, %region_14.46}", t0, dur)


def device_ops(scoped, flush=True):
    """Two chunk programs of two steps: the first step of each runs the
    flush branch (operations inside the conditional; none without
    ``flush``), the second the empty one. ``scoped`` gives each operation a
    scope stat, as a trace that keeps the HLO would."""
    def op(name, t0, dur, scope):
        return (ev(name, t0, dur, tf_op=f"serve.decode_chunk/{scope}/x")
                if scoped else ev(name, t0, dur))
    out = []
    for base in (10, 100):
        out += [
            ev("%while.120 = (s32[]) while(%tuple.1)", base, 50),
            op("%fusion.1 = bf16[16,2560] fusion(%x)", base + 1, 9,
               "serve.decode_step"),
            op("%broadcast_select_fusion.3 = f32[24,16,384,2560] "
               "fusion(%a, %b)", base + 10, 1, "serve.mask_state"),
        ]
        if flush:
            out += [
                op("%fusion.7 = f32[24,16,384,2560] fusion(%c)", base + 12,
                   8, "serve.flush_segment"),
                op("%copy.2 = f32[24,16,384,2560] copy(%fusion.7)",
                   base + 20, 4, "serve.flush_segment")]
        out += [
            op("%fusion.1 = bf16[16,2560] fusion(%x)", base + 26, 9,
               "serve.decode_step"),
            op("%broadcast_select_fusion.3 = f32[24,16,384,2560] "
               "fusion(%a, %b)", base + 35, 1, "serve.mask_state"),
            op("%argmax.2 = s32[16] reduce(%logits)", base + 37, 1,
               "serve.sample"),
        ]
        if not scoped:
            # the conditionals, each around its branch's operations
            out += [cond(base + 11, 14), cond(base + 36.2, 0.5)]
    return out


def drain(t0, needed, steps, n_slots=16):
    return ev("sched.drain", t0, 9, steps=CHUNK,
              slot_steps_active=100, flush_steps=steps,
              flush_rows_needed=needed,
              flush_rows_computed=n_slots * steps)


def host_spans():
    return [ev("bench.window", 0, 200),
            ev("sched.admit", 0, 5), ev("sched.dispatch", 5, 4),
            ev("decode_chunk", 9, 52),
            drain(61, 1, 1),
            ev("sched.wait", 70, 25),
            ev("sched.admit", 95, 2), ev("admission_round", 97, 2),
            ev("transplant", 97.5, 1), ev("sched.dispatch", 99, 1),
            ev("decode_chunk", 100, 51),
            drain(151, 3, 2),
            ev("sched.admit", 160, 1), ev("sched.dispatch", 161, 1),
            ev("decode_chunk", 162, 12),
            # drains the chunk whose device work ends the window
            drain(174, 16, 1)]


def planes(scoped=False, phases=True, flush=True):
    host = host_spans() if phases else [ev("bench.window", 0, 200),
                                        ev("decode_chunk", 9, 52),
                                        ev("decode_chunk", 100, 51)]
    return [
        NS(name="/device:TPU:0",
           lines=[NS(name="XLA Modules",
                     events=[ev("jit_chunk_fn(7)", 10, 50),
                             ev("jit_chunk_fn(7)", 100, 50),
                             ev("jit_chunk_fn(7)", 163, 9)]),
                  NS(name="XLA Ops", events=device_ops(scoped, flush) + [
                      ev("%fusion.1 = bf16[16,2560] fusion(%x)", 163, 9)])]),
        NS(name="/host:CPU",
           lines=[NS(name="python3", events=host),
                  NS(name="tf_pjrt_thread", events=[
                      ev("ReadSyncFlag", 62, 33),
                      ev("sched.drain", 64, 1, flush_rows_needed=16,
                         flush_rows_computed=16)])]),
    ]


def ctx(scoped=False, phases=True, steps=2 * CHUNK, flush=True):
    p = planes(scoped, phases, flush)
    tr = trace.reduce(p)
    tr.source = NS(planes=p)
    return NS(trace=tr, records=[], decode_steps=steps)


def test_window_and_gaps_of_the_layout():
    tr = ctx().trace
    # the window ends with the last device operation, at 172 ms
    assert tr.window == (0, 172 * MS)
    assert [(a / MS, b / MS) for a, b in tr.idle_gaps()] == [
        (0, 10), (60, 100), (150, 163)]
    assert tr.modules == {"jit_chunk_fn": 3}


@pytest.mark.parametrize("scoped", [False, True])
def test_flush_device_ms_per_step(scoped):
    """By structure on a TPU trace (the operations inside the chunk
    program's conditional), by scope where operations carry one: the
    same 12 ms of each chunk's flush step, over 16 decode steps."""
    read = run.load_metric("decode.flush_device_ms_per_step")
    assert read(ctx(scoped)) == pytest.approx(2 * 12 / 16)
    assert read(ctx(scoped, steps=0)) is None


def test_flush_device_without_a_flush_branch():
    """Conditionals that enclose nothing (the flush branch never ran in the
    window): a measured zero. Neither a conditional nor the scope: nothing
    to read."""
    read = run.load_metric("decode.flush_device_ms_per_step")
    assert read(ctx(flush=False)) == 0.0
    c = ctx()
    c.trace.ops = [o for o in c.trace.ops if " conditional(" not in o.name]
    assert read(c) is None


def test_flush_rows_useful():
    """Σ needed over Σ computed of the program's ``sched.drain`` events in
    the window: (1 + 3) / (16 + 32); the event after the window (its chunk
    was still running when the trace ends) and the runtime thread's are
    left out."""
    read = run.load_metric("decode.flush_rows_useful")
    assert read(ctx()) == pytest.approx(100 * 4 / 48)
    # a program without the counters, or a trace without its source
    assert read(ctx(phases=False)) is None
    c = ctx()
    c.trace.source = None
    assert read(c) is None


def test_host_bound_idle():
    """Idle [0, 10] under sched.admit, sched.dispatch and decode_chunk;
    idle [60, 100] under decode_chunk (1 ms), sched.drain (9), sched.wait
    (25: a lull, not the host) and sched.admit, admission_round,
    sched.dispatch (5); idle [150, 163] under decode_chunk, sched.drain,
    sched.admit, sched.dispatch and decode_chunk (13): 38 of the window's
    172 ms."""
    read = run.load_metric("device_idle.decode.host_bound")
    c = ctx()
    assert read(c) == pytest.approx(100 * 38 / 172)
    # the rest of the device's idle share is sched.wait's
    idle = run.load_metric("device_idle.decode")(c)
    assert idle == pytest.approx(100 * (38 + 25) / 172)


def test_host_bound_on_a_program_without_phases(monkeypatch):
    """Run against a program that has no ``SCHED_PHASES``: no value, and
    no error."""
    import types
    bare = types.ModuleType("repro.serve.telemetry")
    monkeypatch.setitem(sys.modules, "repro.serve.telemetry", bare)
    assert run.load_metric("device_idle.decode.host_bound")(ctx()) is None
