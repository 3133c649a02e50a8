"""Serve-stack telemetry (DESIGN.md §13): metrics registry semantics,
Chrome-trace schema, recorder-derived serving metrics vs the pre-PR-7
bench reference implementations, the one-host-transfer-per-chunk
invariant with telemetry enabled, the jit-compile budget over mixed
prompt lengths, and the sharding-fallback counter unification."""
import json
import logging

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import init_params
from repro.serve import (ContinuousScheduler, MetricsRegistry, PrefixCache,
                         Request, ServeEngine, Telemetry, TraceRecorder,
                         default_registry, validate_chrome_trace)
from repro.serve.telemetry import SPAN_CATEGORIES, _main as telemetry_cli


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("h2o-danube-1.8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _toks(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(8, cfg.vocab, (n,)).astype(np.int32)


def _requests(cfg, lens, max_new, seed=0):
    return [Request(req_id=f"r{i}", prompt=_toks(cfg, L, seed=seed + i),
                    max_new=max_new)
            for i, L in enumerate(lens)]


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.inc("reqs_total")
    reg.inc("reqs_total", 2)
    reg.inc("reqs_total", result="hit")
    reg.inc("reqs_total", result="hit")
    reg.inc("reqs_total", result="miss")
    reg.set_gauge("occupancy", 3)
    reg.set_gauge("occupancy", 5)                       # gauges overwrite
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.observe("wait_s", v)
    snap = reg.snapshot()
    assert snap["counters"]["reqs_total"] == 3
    assert snap["counters"]["reqs_total{result=hit}"] == 2
    assert snap["counters"]["reqs_total{result=miss}"] == 1
    assert snap["gauges"]["occupancy"] == 5
    h = snap["histograms"]["wait_s"]
    assert h["count"] == 4 and h["sum"] == 10.0 and h["max"] == 4.0
    assert h["p50"] == 2.5
    # the snapshot is JSON-able as-is (the artifact contract)
    json.dumps(snap)


def test_registry_remove_series_and_reset_hooks():
    reg = MetricsRegistry()
    reg.inc("fallbacks", kind="param", dim=1)
    reg.inc("fallbacks", kind="state", dim=2)
    reg.inc("other")
    reg.remove_series("fallbacks")
    assert reg.counters == {"other": 1}
    fired = []
    reg.register_reset_hook(lambda: fired.append(1))
    reg.register_reset_hook(lambda: fired.append(1))    # dedup is by identity
    reg.reset()
    assert reg.counters == {} and len(fired) >= 1


def test_registry_probes_sampled_at_snapshot():
    reg = MetricsRegistry()
    state = {"n": 0}
    reg.register_probe("live", lambda: state["n"])
    reg.register_probe("broken", lambda: 1 / 0)
    state["n"] = 7
    snap = reg.snapshot()
    assert snap["probes"]["live"] == 7                  # sampled now, not at
    assert "error" in snap["probes"]["broken"]          # registration time


# ---------------------------------------------------------------------------
# Trace schema
# ---------------------------------------------------------------------------

def test_chrome_trace_schema_valid_and_lanes():
    rec = TraceRecorder(t0=0.0)
    with rec.span("decode_chunk", "decode", steps=4):
        pass
    rec.add_span("admission", "admission", 0.1, 0.2, lane="r0", slot=1)
    rec.instant("segment_flush", "flush", t=0.15, lane="r0")
    rec.emit("r0", 0.2, 3)
    trace = rec.chrome_trace()
    assert validate_chrome_trace(trace) == []
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"scheduler", "req:r0"} <= names
    # every non-metadata event carries a known category
    assert all(e.get("cat") in SPAN_CATEGORIES
               for e in trace["traceEvents"] if e["ph"] in ("X", "i"))


def test_chrome_trace_schema_rejects_malformed():
    assert validate_chrome_trace({"nope": 1})
    bad = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "x", "cat": "decode",
         "ts": 0.0, "dur": -1.0},                       # negative duration
        {"ph": "i", "pid": 1, "tid": 0, "name": "y", "cat": "not-a-cat",
         "ts": 1.0},                                    # unknown category
        {"ph": "Z", "pid": 1, "tid": 0, "name": "z"},   # unknown phase
    ]}
    errs = validate_chrome_trace(bad)
    assert any("dur" in e for e in errs)
    assert any("not-a-cat" in e for e in errs)
    assert any("ph" in e for e in errs)
    assert any("thread_name" in e for e in errs)        # tid 0 never named


def test_telemetry_cli_gate(tmp_path):
    rec = TraceRecorder(t0=0.0)
    with rec.span("decode_chunk", "decode"):
        pass
    rec.instant("segment_flush", "flush", t=0.1)
    path = str(tmp_path / "trace.json")
    rec.export(path)
    assert telemetry_cli([path, "--require-cats", "decode,flush"]) == 0
    # instants alone satisfy a category, but a missing one still fails
    assert telemetry_cli([path, "--require-cats", "decode,session"]) == 1
    assert telemetry_cli([path, "--min-spans", "5"]) == 1


# ---------------------------------------------------------------------------
# Derived serving metrics == the pre-PR-7 bench reference implementations
# ---------------------------------------------------------------------------
# Verbatim copies of benchmarks/bench_serve.py's deleted helpers: the old
# path scanned per-token StreamEvent.t_emit stamps; the recorder stores one
# (t, n) entry per (request, chunk). The derivations must agree exactly.

def _ref_itl_stats(emit_times):
    itls = []
    for times in emit_times.values():
        itls += [b - a for a, b in zip(times, times[1:])]
    if not itls:
        return 0.0, 0.0
    return (float(np.percentile(itls, 50)), float(np.percentile(itls, 99)))


def _ref_admission_stall(windows, emit_times):
    times = sorted({t for ts in emit_times.values() for t in ts})
    gaps = [(a, b) for a, b in zip(times, times[1:])]
    stall = 0.0
    for (w0, w1) in windows:
        for (a, b) in gaps:
            if a <= w1 and b >= w0:
                stall = max(stall, b - a)
    return stall


def test_derivations_match_reference_synthetic():
    chunks = {"a": [(0.00, 3), (0.10, 3), (0.50, 2)],
              "b": [(0.05, 1), (0.60, 4)],
              "c": [(0.70, 1)]}                  # single chunk: no ITL at all
    windows = [(0.08, 0.45), (0.55, 0.58)]
    rec = TraceRecorder(t0=0.0)
    for rid, cs in chunks.items():
        for t, n in cs:
            rec.emit(rid, t, n)
    for (w0, w1) in windows:
        rec.add_span("admission", "admission", w0, w1)
    # the old per-token view: every token of a chunk shares its stamp
    emit_times = {rid: [t for (t, n) in cs for _ in range(n)]
                  for rid, cs in chunks.items()}
    assert sorted(rec.itl_values()) == sorted(
        [b - a for ts in emit_times.values() for a, b in zip(ts, ts[1:])])
    assert rec.itl_percentiles() == _ref_itl_stats(emit_times)
    assert rec.admission_stall_s() == pytest.approx(
        _ref_admission_stall(windows, emit_times))
    assert rec.admission_windows() == windows


def test_derivations_match_reference_live_run(setup):
    """A real scheduler run: the recorder's ITL percentiles and admission
    stall equal the old bench derivation applied to the per-token
    ``StreamEvent.t_emit`` stream + ``sched.admission_windows`` — the
    agreement that justified deleting the bench-local scan."""
    cfg, params = setup
    seg = cfg.armt.segment_len
    eng = ServeEngine(params, cfg, serve_mode="armt", max_len=256,
                      telemetry=Telemetry(trace=True,
                                          registry=MetricsRegistry()))
    reqs = _requests(cfg, [seg, seg + seg // 2, seg, seg + seg // 2, seg],
                     max_new=10)
    sched = ContinuousScheduler(eng, n_slots=2, chunk=4,
                                max_concurrent_admissions=2)
    emit_times = {}
    for ev in sched.run(iter(reqs)):
        emit_times.setdefault(ev.req_id, []).append(ev.t_emit)
    rec = eng.telemetry.trace
    assert rec.itl_percentiles() == _ref_itl_stats(emit_times)
    assert rec.admission_stall_s() == pytest.approx(
        _ref_admission_stall(sched.admission_windows, emit_times))
    # the recorder's windows ARE the scheduler's (same stamps)
    assert rec.admission_windows() == sched.admission_windows


# ---------------------------------------------------------------------------
# Span coverage + schema on a live serve run
# ---------------------------------------------------------------------------

def test_serve_run_span_coverage_and_counters(setup):
    cfg, params = setup
    seg = cfg.armt.segment_len
    tel = Telemetry(trace=True, registry=MetricsRegistry())
    eng = ServeEngine(params, cfg, serve_mode="armt", max_len=256,
                      telemetry=tel)
    # first request is long and admissions advance one group per round, so
    # the cold pool drains it in the idle tight loop; max_new crosses a
    # segment boundary so in-graph flushes surface as host instants
    reqs = _requests(cfg, [6 * seg, seg + seg // 2, seg, seg + seg // 2, seg],
                     max_new=seg + 2)
    sched = ContinuousScheduler(eng, n_slots=2, chunk=4,
                                prefill_groups_per_chunk=1,
                                max_concurrent_admissions=4)
    n_tok = sum(1 for _ in sched.run(iter(reqs)))
    assert n_tok == 5 * (seg + 2)
    trace = tel.trace.chrome_trace()
    assert validate_chrome_trace(trace) == []
    cats = {e.get("cat") for e in trace["traceEvents"]
            if e.get("ph") in ("X", "i")}
    # decode chunks, admission windows+rounds, transplants, host-derived
    # segment flushes, idle-drain rounds and per-chunk token emits all
    # present on one burst-y run
    assert {"decode", "admission", "transplant", "flush", "idle",
            "emit"} <= cats
    snap = tel.registry.snapshot()
    assert snap["counters"]["admissions_total"] == 5
    assert snap["counters"]["decode_flushes_total"] == 5   # one per request
    # the gauge is sampled at chunk boundaries (before the chunk's tokens
    # free any slot), so the last sample still shows the final occupant
    assert 1 <= snap["gauges"]["pool_occupancy"] <= 2
    assert snap["histograms"]["chunk_queue_depth"]["count"] > 0
    assert snap["histograms"]["queue_wait_s"]["count"] == 5
    # per-request lanes: every request got its own named thread
    lane_names = {e["args"]["name"] for e in trace["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {f"req:r{i}" for i in range(5)} <= lane_names


# ---------------------------------------------------------------------------
# Zero-sync: one host transfer per chunk, telemetry fully on
# ---------------------------------------------------------------------------

class _CountingNp:
    """numpy proxy counting ``asarray`` calls whose argument is a device
    array — i.e. actual device->host transfers issued by the scheduler."""

    def __init__(self, real):
        self._real = real
        self.device_transfers = 0

    def __getattr__(self, name):
        return getattr(self._real, name)

    def asarray(self, x, *a, **kw):
        if isinstance(x, jax.Array):
            self.device_transfers += 1
        return self._real.asarray(x, *a, **kw)


def test_one_host_transfer_per_chunk_with_telemetry(setup, monkeypatch):
    """The telemetry hard constraint, regression-tested: with trace +
    metrics fully enabled, the scheduler performs exactly TWO
    device->host conversions per decode chunk (the token block and the
    mask block that always existed) — emit stamps, flush instants and
    gauges are all derived from those host copies."""
    import repro.serve.scheduler as sched_mod
    cfg, params = setup
    seg = cfg.armt.segment_len
    proxy = _CountingNp(np)
    monkeypatch.setattr(sched_mod, "np", proxy)
    tel = Telemetry(trace=True, registry=MetricsRegistry())
    eng = ServeEngine(params, cfg, serve_mode="armt", max_len=256,
                      telemetry=tel)
    reqs = _requests(cfg, [seg, seg + seg // 2, seg], max_new=seg + 2)
    sched = ContinuousScheduler(eng, n_slots=2, chunk=4)
    n_tok = sum(1 for _ in sched.run(iter(reqs)))
    assert n_tok == 3 * (seg + 2)
    n_chunks = sum(1 for s in tel.trace.spans if s.name == "decode_chunk")
    assert n_chunks > 0
    assert proxy.device_transfers == 2 * n_chunks


# ---------------------------------------------------------------------------
# Compile budget over mixed prompt lengths (the O(log) claim, measured)
# ---------------------------------------------------------------------------

def test_compile_budget_mixed_prompt_lengths(setup):
    """pow2 bucketing: prompts spanning many lengths share O(log)
    compiled programs — the engine's jit caches grow with the number of
    DISTINCT pow2 buckets, and a second wave of new lengths inside the
    same buckets adds zero entries."""
    cfg, params = setup
    seg = cfg.armt.segment_len
    eng = ServeEngine(params, cfg, serve_mode="armt", max_len=512,
                      telemetry=Telemetry(trace=False,
                                          registry=MetricsRegistry()))
    def run(lens, seed):
        sched = ContinuousScheduler(eng, n_slots=2, chunk=4)
        for _ in sched.run(iter(_requests(cfg, lens, 4, seed=seed))):
            pass
    # 6 distinct lengths covering the pow2 buckets: full-segment diagonal
    # groups {1, 2, 4} and descending-pow2 tail pieces {16, 8, 4, 2, 1}
    # (a 31-token tail decomposes into all five)
    run([seg, seg + 31, 2 * seg, 2 * seg + seg // 2,
         3 * seg, 4 * seg], seed=0)
    budget = eng.compile_counts()
    # new lengths inside the same buckets (tails decompose into already-
    # compiled pieces, segment counts stay <= 4): nothing recompiles
    run([seg + 12, 2 * seg + 9, 3 * seg + 16, 2 * seg + 11], seed=9)
    after = eng.compile_counts()
    assert after == budget, (budget, after)
    # the whole mixed workload fits an O(log) program budget: at most
    # log2(seg)+1 tail-piece steppers plus per-bucket scheduler/prefill
    # entries, far below one-program-per-length (10 distinct lengths)
    assert after["decode_step"] <= seg.bit_length(), after
    assert after["total"] <= 16, after
    assert after["scheduler_fns"] <= 4                  # <= 1 + #buckets


def test_generation_result_metrics(setup):
    cfg, params = setup
    seg = cfg.armt.segment_len
    eng = ServeEngine(params, cfg, serve_mode="armt", max_len=256,
                      telemetry=Telemetry(registry=MetricsRegistry()),
                      prefix_cache=PrefixCache(seg, max_bytes=1 << 20))
    res = eng.generate(_toks(cfg, seg)[None], 4)
    assert res.metrics is not None
    probes = res.metrics["probes"]
    assert probes["engine_compile_counts"]["total"] >= 1
    assert probes["prefix_cache"]["misses"] >= 0
    assert "prefix_probe_total{result=miss}" in res.metrics["counters"]
    assert "generate_ttft_s" in res.metrics["histograms"]
    # disabled telemetry: no snapshot, generation still works
    eng_off = ServeEngine(params, cfg, serve_mode="armt", max_len=256,
                          telemetry=Telemetry.disabled())
    res_off = eng_off.generate(_toks(cfg, seg)[None], 4)
    assert res_off.metrics is None
    np.testing.assert_array_equal(np.asarray(res.tokens),
                                  np.asarray(res_off.tokens))


def test_disabled_telemetry_is_noop():
    tel = Telemetry.disabled()
    assert not tel.on and tel.snapshot() is None
    tel.inc("x")
    tel.observe("y", 1.0)
    tel.set_gauge("z", 2.0)
    tel.add_span("a", "decode", 0.0, 1.0)
    tel.instant("b", "flush")
    tel.emit("r", 0.0, 1)
    with tel.span("c", "decode") as sp:
        sp.set(n=1)


# ---------------------------------------------------------------------------
# Sharding fallbacks route through the registry (satellite)
# ---------------------------------------------------------------------------

def test_sharding_fallback_counter_and_unified_reset(caplog):
    from repro.parallel import sharding as shd
    shd.reset_fallback_warnings()
    reg = default_registry()

    def count():
        return sum(v for k, v in reg.counters.items()
                   if k.startswith("sharding_fallback_total"))

    with caplog.at_level(logging.WARNING, logger="repro.parallel.sharding"):
        shd.param_leaf_spec(["pattern", "attn", "wq"], (30, 30), 16)
        shd.param_leaf_spec(["pattern", "attn", "wq"], (30, 30), 16)
    # the log line stays deduped (one line per distinct fallback) but the
    # counter counts every occurrence
    recs = [r for r in caplog.records if "sharding-fallback" in r.getMessage()]
    assert len(recs) == 1
    assert count() == 2
    key = [k for k in reg.counters
           if k.startswith("sharding_fallback_total")][0]
    assert "kind=param" in key and "leaf=pattern.attn.wq" in key \
        and "dim=1" in key and "axis=model" in key
    # one reset clears both views...
    shd.reset_fallback_warnings()
    assert count() == 0
    with caplog.at_level(logging.WARNING, logger="repro.parallel.sharding"):
        shd.param_leaf_spec(["pattern", "attn", "wq"], (30, 30), 16)
    assert len([r for r in caplog.records
                if "sharding-fallback" in r.getMessage()]) == 2
    # ...and so does the registry's own reset (the dedup set is a hook)
    reg.reset()
    assert count() == 0
    with caplog.at_level(logging.WARNING, logger="repro.parallel.sharding"):
        shd.param_leaf_spec(["pattern", "attn", "wq"], (30, 30), 16)
    assert len([r for r in caplog.records
                if "sharding-fallback" in r.getMessage()]) == 3
    shd.reset_fallback_warnings()
