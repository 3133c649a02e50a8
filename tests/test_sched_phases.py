"""The scheduler's phase spans, chunk counters and device scopes
(DESIGN.md §13), read back from a JAX profiler trace the way the on-chip
benchmark reads one (``benchmarks/chip/tracefile.py``): a tiny ARMT
scheduler whose traffic crosses segment boundaries, run under
``jax.profiler`` once for the module."""
import contextlib
import dataclasses
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import decode_state_init, init_params
from repro.serve import (ContinuousScheduler, MetricsRegistry, Request,
                         ServeEngine, Telemetry)
from repro.serve import scheduler as sched_mod
from repro.serve import telemetry as tel_mod
from repro.serve.telemetry import SCHED_PHASES, profile_start_ns

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import tracefile  # noqa: E402

N_SLOTS, CHUNK, MAX_NEW = 2, 16, 24
PROMPTS = (13, 8, 21, 7, 30, 3)
IDLE_POLLS = 30          # "nothing ready yet" answers after the first two


def _cfg():
    # wide enough that a chunk's device work, not the host's bookkeeping
    # between spans, takes the loop's time
    cfg = get_smoke_config("h2o-danube-1.8b")
    return dataclasses.replace(cfg, d_model=256, n_heads=4, d_head=64,
                               d_ff=512, vocab=1024)


def _requests(cfg):
    rng = np.random.default_rng(0)
    return [Request(f"r{i}", rng.integers(8, cfg.vocab, (p,)).astype(np.int32),
                    MAX_NEW) for i, p in enumerate(PROMPTS)]


def _source(reqs):
    """A live source: two requests, then ``IDLE_POLLS`` times "nothing
    ready yet" (the scheduler decodes them, then backs off), then the
    rest."""
    yield reqs[0]
    yield reqs[1]
    for _ in range(IDLE_POLLS):
        yield None
    yield from reqs[2:]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    reqs = _requests(cfg)
    eng = ServeEngine(params, cfg, serve_mode="armt", max_len=256,
                      telemetry=Telemetry(registry=MetricsRegistry()))
    # warm every program the traced run uses
    for _ in ContinuousScheduler(eng, n_slots=N_SLOTS, chunk=CHUNK).run(
            iter(reqs)):
        pass
    tel = Telemetry(trace=True, registry=MetricsRegistry())
    eng.telemetry = tel
    sched = ContinuousScheduler(eng, n_slots=N_SLOTS, chunk=CHUNK)
    logdir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # as the benchmark traces
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracefile.WINDOW_SPAN):
            events = list(sched.run(_source(reqs)))
    finally:
        jax.profiler.stop_trace()
    tr = tracefile.load(tracefile.find_xplane(logdir))
    return dict(cfg=cfg, reqs=reqs, sched=sched, tel=tel, events=events,
                tr=tr, logdir=logdir)


def _profile_events(tr, name=None):
    """(name, start_ns, duration_ns, stats) of the program's host events."""
    out = []
    for plane in tr.source.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            if line.name.startswith("tf_"):
                continue
            for ev in line.events:
                if name is None or ev.name == name:
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.duration_ns), dict(ev.stats)))
    return out


def test_phase_spans_tile_the_loop(served):
    """Between the first and the last chunk, the loop's wall time lies
    inside a top-level phase span, and every phase occurs."""
    spans = served["tr"].spans
    chunks = [s for s in spans if s.name == "decode_chunk"]
    a, b = chunks[0].t0, chunks[-1].t1
    phases = tracefile.union(sorted(
        (max(s.t0, a), min(s.t1, b)) for s in spans
        if s.name in SCHED_PHASES and s.t1 > a and s.t0 < b))
    covered = sum(y - x for x, y in phases)
    assert covered >= 0.99 * (b - a), covered / (b - a)
    assert {s.name for s in spans} >= set(SCHED_PHASES) - {"idle_drain_round"}
    assert served["tel"].trace.spans, "the recorder keeps the spans too"


def test_drain_counters_match_registry_and_mirror(served):
    cfg, sched = served["cfg"], served["sched"]
    seg = cfg.armt.segment_len
    drains = [st for _, _, _, st in _profile_events(served["tr"],
                                                    "sched.drain")]
    assert drains
    for st in drains:
        assert st["steps"] == CHUNK
        assert st["flush_rows_computed"] == N_SLOTS * st["flush_steps"]
        assert st["flush_rows_needed"] >= st["flush_steps"]
    total = {k: sum(st[k] for st in drains) for k in drains[0]}
    counters = served["tel"].registry.snapshot()["counters"]
    assert counters["decode_flushes_total"] == total["flush_rows_needed"]
    assert counters["decode_flush_steps_total"] == total["flush_steps"]
    assert (counters["decode_flush_rows_computed_total"]
            == total["flush_rows_computed"])
    # every served token was one active decode row
    assert total["slot_steps_active"] == len(served["events"]) \
        == MAX_NEW * len(PROMPTS)
    # a request crosses a boundary each time prompt + decoded tokens reach
    # a multiple of seg_len (the admission's tail flushes its own)
    need = sum((p % seg + MAX_NEW) // seg for p in PROMPTS)
    assert total["flush_rows_needed"] == need > 0
    # the host pos mirror agrees with the device's pos of every slot (a
    # finished row keeps its end-of-generation state)
    assert [s.pos for s in sched.slots] == np.asarray(
        sched.pool["pos"]).tolist()
    # the span's twin on the recorder carries the same arguments
    rec = [s.args for s in served["tel"].trace.spans
           if s.name == "sched.drain"]
    assert rec == drains


def test_recorder_on_the_profilers_clock(served):
    """Exported against the profile's start, each recorder span lies within
    200 µs of its TraceAnnotation twin; retroactive spans and instants are
    on the same clock."""
    tr, tel = served["tr"], served["tel"]
    origin = profile_start_ns(served["logdir"])
    assert origin is not None
    trace = tel.trace.chrome_trace(origin)
    assert tel_mod.validate_chrome_trace(trace) == []
    assert trace["otherData"]["origin_ns"] == origin
    twins = {}
    for name, t0, _, _ in _profile_events(tr):
        twins.setdefault(name, []).append(t0)
    exported = {}
    for e in trace["traceEvents"]:
        if e["ph"] == "X":
            exported.setdefault(e["name"], []).append(e["ts"])
    paired = 0
    for name, ts in exported.items():
        if name == "admission":          # retroactive: no twin
            continue
        assert len(ts) == len(twins[name]), name
        for mine, theirs in zip(sorted(ts), sorted(twins[name])):
            assert abs(mine * 1e3 - theirs) <= 200_000, (name, mine, theirs)
            paired += 1
    assert paired > len(PROMPTS)
    # the admission windows and emits fall inside the profile's window
    win = [(t0, t0 + d) for n, t0, d, _ in _profile_events(
        tr, tracefile.WINDOW_SPAN)][0]
    for e in trace["traceEvents"]:
        if e["ph"] in ("X", "i") and e["cat"] in ("admission", "emit"):
            assert win[0] <= e["ts"] * 1e3 <= win[1], e


def test_flush_rows_useful_reads_the_profile(served):
    """The benchmark's reader takes the counters from the profile's
    ``sched.drain`` stats."""
    import run
    from types import SimpleNamespace
    counters = served["tel"].registry.snapshot()["counters"]
    v = run.load_metric("decode.flush_rows_useful")(
        SimpleNamespace(trace=served["tr"]))
    assert v == pytest.approx(100.0 * counters["decode_flushes_total"]
                              / counters["decode_flush_rows_computed_total"])


def _hlo(cfg, params):
    seg = cfg.armt.segment_len
    pool = decode_state_init(cfg, N_SLOTS, serve_mode="armt", max_len=64,
                             dtype=jnp.float32, per_slot_pos=True)
    args = (params, pool, jnp.zeros(N_SLOTS, jnp.int32),
            jnp.ones(N_SLOTS, bool), jnp.full(N_SLOTS, 5, jnp.int32))
    fn = sched_mod._chunk_body_factory(cfg, "armt", seg, 4)
    lines = jax.jit(fn).lower(*args).compile().as_text().splitlines()
    # the program without its debug information: the stack-frame tables
    # between the module's header and its first computation, and each
    # instruction's metadata
    first = next(i for i, ln in enumerate(lines)
                 if ln.startswith(("%", "ENTRY")))
    text = "\n".join(lines[:1] + lines[first:])
    return text, re.sub(r",? metadata=\{[^}]*\}", "", text)


def test_chunk_program_unchanged_by_scopes(monkeypatch):
    cfg = get_smoke_config("h2o-danube-1.8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    scoped_text, scoped = _hlo(cfg, params)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_text, bare = _hlo(cfg, params)
    for scope in ("serve.decode_step", "serve.flush_segment",
                  "serve.sample"):
        assert scope in scoped_text and scope not in bare_text, scope
    assert " conditional(" in scoped
    assert scoped == bare


def test_device_memory_is_a_snapshot_probe(monkeypatch):
    """The gauges are read when a snapshot is taken, not per chunk."""
    calls = []

    class Dev:
        def memory_stats(self):
            calls.append(1)
            return {"bytes_in_use": 5, "peak_bytes_in_use": 7, "other": 1}

    monkeypatch.setattr(tel_mod, "_MEM_DEVICE", Dev())
    tel = Telemetry(registry=MetricsRegistry())
    assert not calls
    snap = tel.snapshot()
    assert snap["gauges"]["device_bytes_in_use"] == 5
    assert snap["gauges"]["device_peak_bytes_in_use"] == 7
    assert snap["probes"]["device_memory"] == {
        "device_bytes_in_use": 5, "device_peak_bytes_in_use": 7}
    assert len(calls) == 1
