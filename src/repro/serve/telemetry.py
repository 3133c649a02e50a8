"""Serve-stack telemetry: unified metrics registry + chunk-granular trace
timeline (DESIGN.md §13).

Two halves, bundled by :class:`Telemetry` and threaded through the whole
serve stack (engine, scheduler, prefill pipeline, state stores, sharding
fallbacks, launch driver, benchmarks):

* :class:`MetricsRegistry` — labelled counters / gauges / histograms plus
  *probes* (callables sampled at snapshot time — the engine registers its
  jit-cache sizes and store stats this way, so a snapshot is always
  current without per-call bookkeeping). A process-wide default registry
  (:func:`default_registry`) collects cross-cutting series: XLA backend
  compiles (via ``jax.monitoring``) and ``parallel/sharding.py``'s
  replication-fallback counter.

* :class:`TraceRecorder` — host-clock spans with per-request lanes,
  exportable as Chrome-trace / Perfetto JSON (``chrome://tracing``,
  https://ui.perfetto.dev) on the JAX profiler's host clock. The
  scheduler's loop is tiled by the phase spans of :data:`SCHED_PHASES`;
  it also emits spans for every transplant, host-visible segment flush,
  session restore and prefix-cache probe. The recorder is also the
  single source of truth for the serving metrics previously re-derived
  ad hoc in ``benchmarks/bench_serve.py``:
  :meth:`TraceRecorder.itl_values` / :meth:`TraceRecorder.itl_percentiles`
  (inter-token latencies off the per-chunk emit stamps) and
  :meth:`TraceRecorder.admission_stall_s` (max decode gap overlapping an
  admission window).

Hard constraint (carried from PR 2): telemetry is HOST-SIDE ONLY and
piggybacks on the existing once-per-chunk host transfer. Nothing here
calls ``block_until_ready``, converts a ``jax.Array``, or adds per-token
work inside a jitted graph — span/metric arguments are host scalars the
scheduler already owns (slot mirrors, cursors, queue lengths), and the
one-host-transfer-per-chunk invariant is regression-tested with telemetry
enabled (tests/test_telemetry.py). ``jax.named_scope`` annotations inside
the traced bodies and ``jax.profiler.TraceAnnotation`` around dispatches
cost nothing at runtime unless an XLA profile is being captured — they
exist so profiler timelines of the jitted launches line up with the
scheduler's host spans.
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["MetricsRegistry", "TraceRecorder", "Telemetry", "SCHED_PHASES",
           "default_registry", "profile_start_ns", "validate_chrome_trace"]


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def _series_key(name: str, labels: Dict[str, Any]) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return f"{name}{{{inner}}}"


class MetricsRegistry:
    """Labelled counters / gauges / histograms with a JSON-able snapshot.

    Series are keyed Prometheus-style — ``name{label=value,...}`` — so the
    snapshot is a flat, diffable dict. Histograms keep their raw values
    (these registries are per-run / reset-per-drive, not long-lived
    daemons) and summarize to count/sum/mean/p50/p99/max at snapshot time.

    ``register_probe(name, fn)`` attaches a callable sampled at snapshot
    time under ``probes[name]`` — the engine publishes jit-cache sizes and
    state-store stats this way, so they are always current and cost
    nothing per chunk. ``register_reset_hook(fn)`` runs ``fn`` on
    ``reset()`` — ``parallel/sharding.py`` hooks its warning-dedup set in,
    unifying the old ``reset_fallback_warnings`` test hook with the
    registry's reset.
    """

    def __init__(self):
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.histograms: Dict[str, List[float]] = {}
        self._probes: Dict[str, Callable[[], Any]] = {}
        self._reset_hooks: List[Callable[[], None]] = []

    # -- write paths (cheap: one dict op each) ----------------------------
    def inc(self, name: str, n: float = 1, **labels) -> None:
        k = _series_key(name, labels)
        self.counters[k] = self.counters.get(k, 0) + n

    def set_gauge(self, name: str, value: float, **labels) -> None:
        self.gauges[_series_key(name, labels)] = value

    def observe(self, name: str, value: float, **labels) -> None:
        self.histograms.setdefault(_series_key(name, labels), []).append(
            float(value))

    # -- probes / reset ---------------------------------------------------
    def register_probe(self, name: str, fn: Callable[[], Any]) -> None:
        self._probes[name] = fn

    def register_reset_hook(self, fn: Callable[[], None]) -> None:
        if fn not in self._reset_hooks:
            self._reset_hooks.append(fn)

    def remove_series(self, name: str) -> None:
        """Drop every series of ``name`` (any labels) from all kinds."""
        for store in (self.counters, self.gauges, self.histograms):
            for k in [k for k in store
                      if k == name or k.startswith(name + "{")]:
                del store[k]

    def reset(self) -> None:
        self.counters.clear()
        self.gauges.clear()
        self.histograms.clear()
        for fn in self._reset_hooks:
            fn()

    # -- read path --------------------------------------------------------
    @staticmethod
    def _summarize(values: List[float]) -> Dict[str, float]:
        arr = np.asarray(values, np.float64)
        return {"count": int(arr.size), "sum": float(arr.sum()),
                "mean": float(arr.mean()), "p50": float(np.percentile(arr, 50)),
                "p99": float(np.percentile(arr, 99)), "max": float(arr.max())}

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able view: counters/gauges verbatim, histograms summarized,
        probes sampled now. Probe failures surface as an ``error`` string
        instead of killing the snapshot (a metrics read must never take
        the serve loop down)."""
        probes = {}
        for name, fn in self._probes.items():
            try:
                probes[name] = fn()
            except Exception as e:            # pragma: no cover - defensive
                probes[name] = {"error": f"{type(e).__name__}: {e}"}
        return {
            "counters": dict(self.counters),
            "gauges": dict(self.gauges),
            "histograms": {k: self._summarize(v)
                           for k, v in self.histograms.items()},
            "probes": probes,
        }


# -- process-wide default registry + XLA compile listener -------------------

_DEFAULT: Optional[MetricsRegistry] = None
_compile_listener_installed = False


def _install_compile_listener() -> None:
    """Count actual XLA backend compiles (and their total seconds) into the
    default registry via ``jax.monitoring`` — ground truth under the
    jit-cache-size probes: pow2 bucketing claims O(log) compiled programs,
    and this counter is what finally verifies it end to end (a retrace
    that silently recompiles an existing cache entry still shows up
    here)."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    import jax.monitoring

    def on_duration(event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            reg = default_registry()
            reg.inc("xla_backend_compiles_total")
            reg.inc("xla_backend_compile_secs_total", duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    _compile_listener_installed = True


def default_registry() -> MetricsRegistry:
    """The process-wide registry for cross-cutting series: XLA backend
    compile counts/seconds and sharding replication fallbacks. Engines
    default their :class:`Telemetry` to this registry, so one snapshot
    carries scheduler metrics and the global series together; tests
    wanting isolation pass ``Telemetry(registry=MetricsRegistry())``."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = MetricsRegistry()
    _install_compile_listener()
    return _DEFAULT


# ---------------------------------------------------------------------------
# Trace recorder (Chrome trace / Perfetto)
# ---------------------------------------------------------------------------

# span categories the scheduler/engine emit — the schema check validates
# category membership so a typo'd span name cannot silently vanish from
# timeline queries
SPAN_CATEGORIES = ("decode", "admission", "prefill", "flush", "transplant",
                   "session", "cache", "idle", "generate", "emit")

# The top-level phases of one ``ContinuousScheduler.run`` iteration. At
# every instant of the loop the scheduler's thread is inside exactly one
# of them (``admission_round`` also nests inside ``idle_drain_round``):
#   sched.admit       start work: pull from the source, ``_start``/``_admit``
#   admission_round   one fairness round over the in-flight admissions
#   sched.dispatch    launch of the jitted decode chunk
#   decode_chunk      the chunk's token and mask blocks copied to the host
#                     (the wait for the device)
#   sched.drain       the host loop over the chunk's tokens, including the
#                     consumer's handling of each yielded event
#   idle_drain_round  admissions drained while no slot decodes
#   sched.wait        back-off: nothing active, nothing admitting
SCHED_PHASES = ("sched.admit", "admission_round", "sched.dispatch",
                "decode_chunk", "sched.drain", "idle_drain_round",
                "sched.wait")


@dataclass
class _Span:
    name: str
    cat: str
    t0: float                   # perf_counter seconds
    t1: float
    lane: Optional[str]         # None -> the scheduler lane
    args: Dict[str, Any] = field(default_factory=dict)


class _SpanCtx:
    """Hot-path span context: stamps the host clock and enters a
    ``jax.profiler.TraceAnnotation`` (a ~ns-cost TraceMe — when a profile
    is being captured, the XLA timeline gets a host span lining up with
    the recorder's: same name, same interval, its arguments as the
    event's stats)."""

    __slots__ = ("rec", "name", "cat", "lane", "args", "t0", "_ann")

    def __init__(self, rec: "TraceRecorder", name: str, cat: str,
                 lane: Optional[str], args: Dict[str, Any]):
        self.rec, self.name, self.cat = rec, name, cat
        self.lane, self.args = lane, args

    def __enter__(self):
        self._ann = _profiler().TraceAnnotation(self.name, **self.args)
        self.t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def set(self, **args) -> None:
        """Arguments known only once the span is open (the chunk counters
        of ``sched.drain``): added to the span and to its twin's stats."""
        self.args.update(args)
        self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self.rec.spans.append(_Span(self.name, self.cat, self.t0,
                                    time.perf_counter(), self.lane,
                                    self.args))
        return False


_PROFILER = None


def _profiler():
    global _PROFILER
    if _PROFILER is None:
        import jax.profiler
        _PROFILER = jax.profiler
    return _PROFILER


def _clock_pair() -> Tuple[float, int]:
    """One paired stamp: ``time.perf_counter()`` (the recorder's clock)
    against the clock the JAX profiler stamps host events with — the
    system's real-time clock in ns (``time.time_ns``); a profile's planes
    hold it relative to their ``profile_start_time``."""
    a = time.perf_counter()
    ns = time.time_ns()
    b = time.perf_counter()
    return (a + b) / 2, ns


def profile_start_ns(logdir: str) -> Optional[int]:
    """``profile_start_time`` of the newest profile under ``logdir`` (a
    ``jax.profiler.start_trace`` directory): the origin of that profile's
    timestamps on the profiler's host clock, or None when it has none."""
    import glob
    import os
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        return None
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(found[-1]).planes:
        for k, v in plane.stats:
            if k == "profile_start_time":
                return int(v)
    return None


class TraceRecorder:
    """Host-clock span/instant recorder with per-request lanes.

    Lanes map to Chrome-trace threads: lane ``None`` is the scheduler's
    own timeline (tid 0); every distinct lane string (request ids, mostly)
    gets its own tid with a ``thread_name`` metadata record, so Perfetto
    shows one swimlane per request under the scheduler track.

    ``emit(req_id, t, n)`` records the per-chunk token emissions the
    derived serving metrics are computed from — one entry per (request,
    chunk boundary), NOT per token; expansion to per-token stamps happens
    only inside :meth:`itl_values` (every token of a chunk shares the
    chunk-boundary host stamp, by design — chunk-granular latency).

    Stamps are ``time.perf_counter()`` seconds, the client's clock too.
    The recorder takes one paired stamp against the profiler's host clock
    at construction, and :meth:`chrome_trace` writes times on that clock.
    """

    def __init__(self, t0: Optional[float] = None):
        self.t0 = time.perf_counter() if t0 is None else t0
        self._pc_ref, self._prof_ref_ns = _clock_pair()
        self.spans: List[_Span] = []
        self.instants: List[_Span] = []
        # req_id -> [(t_emit, n_tokens), ...] per chunk boundary
        self.emits: Dict[Any, List[Tuple[float, int]]] = {}

    # -- recording --------------------------------------------------------
    def span(self, name: str, cat: str, lane: Optional[str] = None, **args):
        # hand-rolled context manager: this sits on the per-chunk hot path,
        # and a contextlib generator costs several µs per entry — enough to
        # show up in the paired overhead ratio at smoke model scale
        return _SpanCtx(self, name, cat, lane, args)

    def add_span(self, name: str, cat: str, t0: float, t1: float,
                 lane: Optional[str] = None, **args) -> None:
        """Retroactive span from host stamps already taken (e.g. an
        admission window stamped at start and transplant time)."""
        self.spans.append(_Span(name, cat, t0, t1, lane, args))

    def instant(self, name: str, cat: str, t: Optional[float] = None,
                lane: Optional[str] = None, **args) -> None:
        t = time.perf_counter() if t is None else t
        self.instants.append(_Span(name, cat, t, t, lane, args))

    def emit(self, req_id, t: float, n_tokens: int) -> None:
        self.emits.setdefault(req_id, []).append((t, n_tokens))
        self.instants.append(_Span("tokens", "emit", t, t, str(req_id),
                                   {"n": n_tokens}))

    # -- derived serving metrics (one source of truth for the bench) ------
    def itl_values(self) -> List[float]:
        """Pooled per-request inter-token latencies. Every token of a chunk
        carries the chunk-boundary stamp, so a chunk of n tokens
        contributes n-1 zero gaps plus one inter-chunk gap — identical to
        the per-token ``StreamEvent.t_emit`` scan the bench used to do."""
        itls: List[float] = []
        for chunks in self.emits.values():
            prev_t = None
            for (t, n) in chunks:
                if prev_t is not None:
                    itls.append(t - prev_t)
                itls.extend([0.0] * (n - 1))
                prev_t = t
        return itls

    def itl_percentiles(self) -> Tuple[float, float]:
        itls = self.itl_values()
        if not itls:
            return 0.0, 0.0
        return (float(np.percentile(itls, 50)),
                float(np.percentile(itls, 99)))

    def admission_windows(self) -> List[Tuple[float, float]]:
        return [(s.t0, s.t1) for s in self.spans if s.name == "admission"]

    def admission_stall_s(self) -> float:
        """Max decode gap (between consecutive chunk-boundary emit stamps,
        any request) whose interval overlaps an admission window — the
        head-of-line stall an admission inflicts on already-decoding
        slots. 0.0 when no admission overlapped active decode."""
        times = sorted({t for chunks in self.emits.values()
                        for (t, _n) in chunks})
        gaps = list(zip(times, times[1:]))
        stall = 0.0
        for (w0, w1) in self.admission_windows():
            for (a, b) in gaps:
                if a <= w1 and b >= w0:
                    stall = max(stall, b - a)
        return stall

    # -- export -----------------------------------------------------------
    def _profiler_ns(self, t: float) -> int:
        """A ``perf_counter`` stamp on the profiler's host clock (ns)."""
        return self._prof_ref_ns + round((t - self._pc_ref) * 1e9)

    def chrome_trace(self, origin_ns: Optional[int] = None) -> Dict[str, Any]:
        """The recorder's timeline as a Chrome-trace JSON object (Perfetto
        and chrome://tracing both load it). Times are microseconds on the
        profiler's host clock since ``origin_ns``: by default the
        recorder's ``t0``; given a profile's start (:func:`profile_start_ns`)
        they are that profile's own times, so the two timelines overlay
        (the retroactive ``admission`` spans and the instants included).
        Spans are complete ("X") events, instants "i", lanes become named
        threads of pid 1."""
        if origin_ns is None:
            origin_ns = self._profiler_ns(self.t0)

        def ts(t: float) -> float:
            return (self._profiler_ns(t) - origin_ns) * 1e-3
        lanes: Dict[Optional[str], int] = {None: 0}
        events: List[Dict[str, Any]] = [
            {"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
             "args": {"name": "repro.serve"}},
            {"ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
             "args": {"name": "scheduler"}},
        ]

        def tid(lane: Optional[str]) -> int:
            if lane not in lanes:
                lanes[lane] = len(lanes)
                events.append({"ph": "M", "pid": 1, "tid": lanes[lane],
                               "name": "thread_name",
                               "args": {"name": f"req:{lane}"}})
            return lanes[lane]

        for s in self.spans:
            events.append({"ph": "X", "pid": 1, "tid": tid(s.lane),
                           "name": s.name, "cat": s.cat, "ts": ts(s.t0),
                           "dur": max((s.t1 - s.t0) * 1e6, 0.0),
                           "args": s.args})
        for s in self.instants:
            events.append({"ph": "i", "pid": 1, "tid": tid(s.lane),
                           "name": s.name, "cat": s.cat, "s": "t",
                           "ts": ts(s.t0), "args": s.args})
        events.sort(key=lambda e: e.get("ts", -1.0))
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"clock": "profiler host clock (time.time_ns)",
                              "origin_ns": origin_ns}}

    def export(self, path: str, origin_ns: Optional[int] = None) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(origin_ns), f)
            f.write("\n")


def validate_chrome_trace(trace: Any) -> List[str]:
    """Schema check for an emitted trace (CI gate): ``trace`` is a path or
    an already-loaded object. Returns a list of problems — empty means
    valid. Checks the Chrome-trace envelope, per-event required fields,
    category membership for X/i events, and that every referenced tid has
    a ``thread_name`` metadata record."""
    if isinstance(trace, str):
        with open(trace) as f:
            trace = json.load(f)
    errs: List[str] = []
    if not isinstance(trace, dict) or "traceEvents" not in trace:
        return ["top level must be an object with a 'traceEvents' list"]
    events = trace["traceEvents"]
    if not isinstance(events, list) or not events:
        return ["'traceEvents' must be a non-empty list"]
    named_tids = set()
    used_tids = set()
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            errs.append(f"event {i}: not an object")
            continue
        ph = e.get("ph")
        if ph not in ("X", "i", "M", "C"):
            errs.append(f"event {i}: unknown ph {ph!r}")
            continue
        for k in ("name", "pid", "tid"):
            if k not in e:
                errs.append(f"event {i} ({e.get('name')!r}): missing {k!r}")
        if ph == "M":
            if e.get("name") == "thread_name":
                named_tids.add((e.get("pid"), e.get("tid")))
            continue
        used_tids.add((e.get("pid"), e.get("tid")))
        if "ts" not in e:
            errs.append(f"event {i} ({e.get('name')!r}): missing 'ts'")
        elif not isinstance(e["ts"], (int, float)) or e["ts"] < 0:
            errs.append(f"event {i} ({e.get('name')!r}): bad ts {e['ts']!r}")
        if e.get("cat") not in SPAN_CATEGORIES:
            errs.append(f"event {i} ({e.get('name')!r}): unknown cat "
                        f"{e.get('cat')!r}")
        if ph == "X":
            dur = e.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errs.append(f"event {i} ({e.get('name')!r}): bad dur {dur!r}")
    for t in used_tids - named_tids:
        errs.append(f"tid {t} used but never named via thread_name metadata")
    return errs


# ---------------------------------------------------------------------------
# Telemetry bundle
# ---------------------------------------------------------------------------

# memory_stats() probe cache: None = unprobed, False = backend has no
# stats (CPU), otherwise the device to sample
_MEM_DEVICE: Any = None


def _sample_device_memory(reg: MetricsRegistry) -> Dict[str, int]:
    """Set and return the device-memory gauges (``device_bytes_in_use``,
    ``device_peak_bytes_in_use``). Sampled at snapshot time as a probe
    (:class:`Telemetry` registers it), so the serving loop never pays for
    it. ``Device.memory_stats()`` is a host-side query (no sync), absent
    on CPU, where this returns {} (the first empty query remembers the
    backend as statless)."""
    global _MEM_DEVICE
    if _MEM_DEVICE is False:
        return {}
    if _MEM_DEVICE is None:
        import jax
        dev = jax.local_devices()[0]
        if not dev.memory_stats():
            _MEM_DEVICE = False
            return {}
        _MEM_DEVICE = dev
    stats = _MEM_DEVICE.memory_stats() or {}
    out = {f"device_{k}": int(stats[k])
           for k in ("bytes_in_use", "peak_bytes_in_use") if k in stats}
    for k, v in out.items():
        reg.set_gauge(k, v)
    return out


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL = _NullCtx()


class Telemetry:
    """What the serve stack actually holds: a registry (metrics) and an
    optional trace recorder, with every write path guarded so a disabled
    instance is a handful of attribute checks per CHUNK (never per token).

    * ``Telemetry()`` — metrics into the process default registry, no
      trace. The engine's default.
    * ``Telemetry(trace=True)`` — adds the span recorder (``--trace-out``,
      bench drives).
    * ``Telemetry.disabled()`` — everything off (the overhead baseline in
      EXPERIMENTS.md §Observability).
    """

    def __init__(self, *, metrics: bool = True, trace: bool = False,
                 registry: Optional[MetricsRegistry] = None):
        self.registry = (registry if registry is not None
                         else (default_registry() if metrics else None))
        self.trace: Optional[TraceRecorder] = (TraceRecorder() if trace
                                               else None)
        reg = self.registry
        if reg is not None:
            reg.register_probe("device_memory",
                               lambda: _sample_device_memory(reg))

    @classmethod
    def disabled(cls) -> "Telemetry":
        return cls(metrics=False, trace=False)

    @property
    def on(self) -> bool:
        return self.registry is not None or self.trace is not None

    # -- metrics (no-ops without a registry) ------------------------------
    def inc(self, name: str, n: float = 1, **labels) -> None:
        if self.registry is not None:
            self.registry.inc(name, n, **labels)

    def set_gauge(self, name: str, value: float, **labels) -> None:
        if self.registry is not None:
            self.registry.set_gauge(name, value, **labels)

    def observe(self, name: str, value: float, **labels) -> None:
        if self.registry is not None:
            self.registry.observe(name, value, **labels)

    # -- spans (no-ops without a recorder) --------------------------------
    def span(self, name: str, cat: str, lane: Optional[str] = None, **args):
        if self.trace is None:
            return _NULL
        return self.trace.span(name, cat, lane=lane, **args)

    def add_span(self, name: str, cat: str, t0: float, t1: float,
                 lane: Optional[str] = None, **args) -> None:
        if self.trace is not None:
            self.trace.add_span(name, cat, t0, t1, lane=lane, **args)

    def instant(self, name: str, cat: str, t: Optional[float] = None,
                lane: Optional[str] = None, **args) -> None:
        if self.trace is not None:
            self.trace.instant(name, cat, t=t, lane=lane, **args)

    def emit(self, req_id, t: float, n_tokens: int) -> None:
        if self.trace is not None:
            self.trace.emit(req_id, t, n_tokens)

    def snapshot(self) -> Optional[Dict[str, Any]]:
        return self.registry.snapshot() if self.registry is not None else None


# ---------------------------------------------------------------------------
# CLI: python -m repro.serve.telemetry trace.json  (CI schema gate)
# ---------------------------------------------------------------------------

def _main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Validate a Chrome-trace JSON emitted by "
                    "launch/serve.py --trace-out (CI schema gate)")
    ap.add_argument("trace", help="path to the trace JSON")
    ap.add_argument("--min-spans", type=int, default=1,
                    help="fail unless at least this many X spans exist")
    ap.add_argument("--require-cats", default="",
                    help="comma list of categories that must appear")
    args = ap.parse_args(argv)
    errs = validate_chrome_trace(args.trace)
    with open(args.trace) as f:
        obj = json.load(f)
    events = obj.get("traceEvents", []) if isinstance(obj, dict) else []
    spans = [e for e in events if isinstance(e, dict) and e.get("ph") == "X"]
    if len(spans) < args.min_spans:
        errs.append(f"only {len(spans)} spans, need >= {args.min_spans}")
    # instants count toward category coverage (in-graph segment flushes are
    # host-derived instants, not spans)
    cats = {e.get("cat") for e in events
            if isinstance(e, dict) and e.get("ph") in ("X", "i")}
    for c in filter(None, args.require_cats.split(",")):
        if c not in cats:
            errs.append(f"required category {c!r} absent (have {sorted(cats)})")
    if errs:
        for e in errs:
            print(f"TRACE-INVALID: {e}")
        return 1
    print(f"trace OK: {len(spans)} spans, {len(events)} events, "
          f"categories={sorted(c for c in cats if c)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
