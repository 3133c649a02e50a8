"""The trace reduction, on a small trace laid out as the JAX profiler
writes a TPU one: a device plane with its ``XLA Ops`` line, a host plane
with the harness's window span and the program's own host spans."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import json

import pytest

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import names  # noqa: E402
import tracefile as trace  # noqa: E402

MS = 1_000_000


def ev(name, t0_ms, dur_ms, **stats):
    return NS(name=name, start_ns=int(t0_ms * MS), duration_ns=int(dur_ms * MS),
              stats=list(stats.items()))


def planes():
    ops = [
        ev("fusion.1", 5, 10, hlo_module="jit_step", tf_op="serve.diag_stage/x"),
        ev("%grouped_matmul.46 = bf16[24,1152,6912] custom-call(%fusion.42), "
           'custom_call_target="tpu_custom_call"', 15, 20,
           hlo_module="jit_step", tf_op="serve.diag_stage/gg"),
        ev("%grouped_matmul_armt_update.7 = (bf16[24,1280,2560]) "
           "custom-call(%pad.75)", 35, 5, hlo_module="jit_step",
           tf_op="serve.diag_stage/up"),
        ev("_flash_kernel", 40, 10, hlo_module="jit_step",
           tf_op="jit(step)/serve.diag_stage/jit(flash_attention)/pallas_call"),
        ev("%pad.72 = bf16[24,32,1152,128] pad(%fusion.9)", 50, 0.5,
           hlo_module="jit_step",
           tf_op="jit(step)/serve.diag_stage/jit(flash_attention)/pad"),
        ev("%while.120 = (s32[]) while(%tuple.1)", 70, 10,
           hlo_module="jit_chunk_fn", tf_op="serve.decode_chunk/while"),
        ev("%fusion.9 = bf16[16,2560] fusion(%x)", 75, 2,   # inside while.120
           hlo_module="jit_chunk_fn", tf_op="serve.decode_chunk/step"),
        ev("%fusion.10 = bf16[16,2560] fusion(%y)", 77, 3,
           hlo_module="jit_chunk_fn", tf_op="serve.decode_chunk/step"),
        ev("copy.3", 120, 10, hlo_module="jit_other"),    # outside the window
    ]
    modules = [ev("jit_step(1)", 5, 45)]
    host = [ev("bench.window", 0, 100), ev("decode_chunk", 50, 30),
            ev("admission_round", 52, 10), ev("$python frame", 0, 100)]
    return [
        NS(name="/host:metadata", lines=[]),
        NS(name="/device:TPU:0",
           lines=[NS(name="XLA Modules", events=modules),
                  NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="python3", events=host)]),
    ]


def test_window_busy_and_idle():
    tr = trace.reduce(planes())
    # the harness's span is [0, 100]; the window ends at the last device
    # operation in it, where a trace whose buffer filled stops
    assert tr.window_s == pytest.approx(0.080)
    assert tr.span_end == 100 * MS
    assert len(tr.ops) == 8                      # copy.3 lies outside
    assert [o.leaf for o in tr.ops].count(False) == 1   # the while loop
    # busy: [5, 50.5] and [70, 80] (the fusions nested in the while)
    assert tr.busy_s() == pytest.approx(0.0555)
    gaps = tr.idle_gaps()
    assert [(a / MS, b / MS) for a, b in gaps] == [(0, 5), (50.5, 70)]
    # the gap [50, 70] is labelled by the innermost host span open at 60
    assert tr.label(60 * MS) == "admission_round"
    assert tr.label(90 * MS) == "host"
    b = tr.breakdown()
    # innermost operations, by instruction name without its number
    assert b["device_ops"][:3] == [["grouped_matmul", pytest.approx(0.020)],
                                   ["fusion", pytest.approx(0.015)],
                                   ["_flash_kernel", pytest.approx(0.010)]]
    assert ["admission_round", pytest.approx(0.0195)] in b["idle_gaps"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_scopes_and_kernels():
    tr = trace.reduce(planes())
    # operations take the module that encloses them in time
    assert tr.modules == {"jit_step": 1}
    assert {o.module for o in tr.ops if 5 * MS <= o.t0 < 50 * MS} == {
        "jit_step"}
    # by the enclosing module event, or by a module stat of its own
    assert tr.select(("module=jit_step",)) == [
        o for o in tr.ops if o.leaf and (o.module == "jit_step"
                                         or "hlo_module=jit_step" in o.text)]
    pre = tr.select(names.PREFILL_SCOPES)
    dec = tr.select(names.DECODE_SCOPES)
    assert sum(o.dur for o in pre) == 45.5 * MS
    assert sum(o.dur for o in dec) == 5 * MS     # the loop's body only
    gmm = names.kernel_ops(tr, "grouped_matmul")
    assert [o.base for o in gmm] == ["grouped_matmul"]
    assert [o.base for o in names.kernel_ops(
        tr, "grouped_matmul_armt_update")] == ["grouped_matmul_armt_update"]
    # by body name; the pad beside the kernel is not the kernel
    assert [o.name for o in names.kernel_ops(tr, "flash_attention")] == [
        "_flash_kernel"]


def test_metric_readers_on_the_trace():
    import run
    tr = trace.reduce(planes())
    m = json.loads((CHIP / "configs" / "h2o-danube-1.8b-armt.json")
                   .read_text())["model"]
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = NS(trace=tr, records=[], model=m, peak=peak, admit_start={},
             segments_prefilled=2, prompts_prefilled=[2048],
             decode_positions=[0, 1, 1023], decode_steps=8)
    v = run.load_metric("prefill.device_ms_per_segment")(ctx)
    assert v == pytest.approx(45.5 / 2)
    assert run.load_metric("decode.device_ms_per_step")(ctx) == \
        pytest.approx(5 / 8)
    assert run.load_metric("device_idle.prefill")(ctx) == pytest.approx(
        100 * (1 - 55.5 / 80))
    g = run.load_metric("grouped_matmul_roofline")(ctx)
    import work
    f, b = work.grouped_matmul_cell(m)
    assert g == pytest.approx(100 * 2 * 24 * max(f / 197e12, b / 819e9)
                              / 0.020)
    assert run.load_metric("mfu.prefill")(ctx) > 0
    assert run.load_metric("mfu.decode")(ctx) > 0
    # nothing to read: nothing returned, never 0
    empty = NS(**{**vars(ctx), "segments_prefilled": 0,
                  "prompts_prefilled": [], "decode_positions": [],
                  "decode_steps": 0})
    for name in ("prefill.device_ms_per_segment", "grouped_matmul_roofline",
                 "flash_attention_roofline", "mfu.prefill", "mfu.decode",
                 "decode.device_ms_per_step", "sched.queue_wait_p95_s"):
        assert run.load_metric(name)(empty) is None, name


def test_context_keeps_what_the_trace_holds():
    """Host-side counts go with the traced window: chunks and tokens after
    the last device operation (here 80 ms after the window's start) are
    left out."""
    import run
    from client import Record
    tr = trace.reduce(planes())
    t0 = 10.0
    tel = NS(trace=NS(spans=[NS(name="decode_chunk", t0=t0 + 0.01,
                                t1=t0 + 0.05, lane=None),
                             NS(name="decode_chunk", t0=t0 + 0.07,
                                t1=t0 + 0.09, lane=None)]))
    r = Record(0, 1020, 8, "chat", t0, deliveries=[[t0 + 0.03, 4],
                                                    [t0 + 0.085, 4]],
               tokens=list(range(8)))
    late = Record(1, 100, 4, "chat", t0, deliveries=[[t0 + 0.09, 4]],
                  tokens=list(range(4)))
    m = json.loads((CHIP / "configs" / "h2o-danube-1.8b-armt.json")
                   .read_text())
    ctx = run.context([r, late], tel, tr, t0, m, {}, 4, 1024)
    assert ctx.decode_steps == 4                 # one chunk of 4 steps
    assert ctx.decode_positions == [1020, 1021, 1022, 1023]
    assert ctx.prompts_prefilled == [1020]
