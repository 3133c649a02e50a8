"""On-chip benchmark of ARMT serving: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``configs/<name>.json``) under a traffic mix (``traffic/<mix>.json``). The
run makes the weights on the device from the seed, warms up every program
the mix uses (set-up), then drives ``ContinuousScheduler.run`` from the
client side for ``--seconds`` (the window), and after the window checks a
sample of the served tokens against the float32 reference
(``reference.py``). ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the window with the JAX profiler and reports its
per-layer metrics (``metrics/<name>.py``), the device's busy time and a
breakdown.

The last line of standard output is one JSON object; the numbers the
check compared, each beside its limit, are the last lines of standard
error and the last key of that object. Without a TPU (or with fewer chips
than the cell asks for) it exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import client  # noqa: E402
import traffic  # noqa: E402

# where the run keeps what it writes: the compile cache and the trace,
# inside the checkout at fixed paths (the cache's path is part of its key)
OUT = ROOT / ".bench"
CACHE_DIR = OUT / "jax_cache"
TRACE_DIR = OUT / "trace"


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Discovery: everything is found by the name BENCHMARK.json gives it
# ---------------------------------------------------------------------------

def load_bench(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(name: str, here: Path = HERE) -> Dict:
    return json.loads((here / "configs" / f"{name}.json").read_text())


def load_cell(name: str, here: Path = HERE) -> Dict:
    return json.loads((here / "cells" / f"{name}.json").read_text())


def load_metric(name: str, here: Path = HERE):
    """The reader ``metrics/<name>.py`` (its ``read(run)``)."""
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: Dict, workload: str, kind: str) -> List[Dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def peak_for(kind: str, here: Path = HERE) -> Dict:
    table = json.loads((here / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def build_cfg(conf: Dict):
    """The program's ArchConfig for a configuration file: the registry
    entry with every size the file states."""
    from repro.configs import ARMTConfig, get_config
    m = dict(conf["model"])
    armt = ARMTConfig(**m.pop("armt"))
    cfg = dataclasses.replace(get_config(conf["registry"]), armt=armt, **m)
    cfg.validate()
    return cfg


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts XLA backend compiles and persistent-cache loads (both
    ``jax.monitoring`` events) from the moment it is armed."""

    def __init__(self):
        import jax
        self.n = {"backend_compile": 0, "cache_hit": 0}
        self.armed = False

        def on_duration(event: str, duration: float, **kw) -> None:
            if self.armed and event.endswith("backend_compile_duration"):
                self.n["backend_compile"] += 1

        def on_event(event: str, **kw) -> None:
            if self.armed and event.endswith("/compilation_cache/cache_hits"):
                self.n["cache_hit"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def require_chip(chips: int, allow_cpu: bool):
    import jax
    devs = jax.devices()
    plat = devs[0].platform
    if not allow_cpu and (plat != "tpu" or len(devs) < chips):
        log(f"run.py: needs {chips} TPU chip(s); JAX finds {len(devs)} "
            f"{plat} device(s)")
        raise SystemExit(3)
    return devs


def setup_cache() -> None:
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def free_device(min_bytes: int = 1 << 26) -> None:
    """Free the device memory of the objects the caller dropped: collect
    them, clear the compiled programs, and delete any large array still
    alive (the program keeps only small tables, such as its rotary cos/sin,
    in caches of its own, and those must survive)."""
    import jax
    gc.collect()
    jax.clear_caches()
    for a in jax.live_arrays():
        if a.nbytes >= min_bytes:
            a.delete()
    gc.collect()


def prepare(entry: Dict, conf: Dict, mix: Dict, seed: int, seconds: float,
            trace: bool, allow_cpu: bool = False, fault: Optional[str] = None):
    """Set-up: the weights from the seed, the engine and scheduler as the
    configuration sets them, and one request served for every prompt
    length of the mix, so every program the window runs is compiled (or
    loaded from the cache) and warm."""
    import jax
    if not allow_cpu:
        setup_cache()
    devs = require_chip(entry["chips"], allow_cpu)
    dev = devs[0]
    peak = (peak_for(dev.device_kind) if not allow_cpu
            else {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})

    from repro.models import init_params
    from repro.serve import ContinuousScheduler, ServeEngine
    from repro.serve.telemetry import MetricsRegistry, Telemetry
    import weights

    cfg = build_cfg(conf)
    seg_len = cfg.armt.segment_len
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    params = weights.make(shapes, conf["weights"], seed)
    jax.block_until_ready(params)
    # a registry of the run's own: the engine's probes go with the engine
    tel = Telemetry(trace=trace, registry=MetricsRegistry())
    engine = ServeEngine(params, cfg, telemetry=tel, **conf["engine"])
    sched = ContinuousScheduler(engine, **conf["scheduler"])
    chunk = conf["scheduler"]["chunk"]
    if fault is not None:
        plant(fault, sched)
    counter = CompileCounter()
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 99])
    warm = [traffic.Req(i, rng.integers(0, cfg.vocab, w["prompt"])
                        .astype(np.int32), w["max_new"], 0.0, "warm")
            for i, w in enumerate(traffic.warm_shapes(mix, seg_len, seconds,
                                                      chunk))]
    # closed loop: one at a time (no pooled admission the window never
    # runs); open loop: all due at once
    client.drive(sched, iter(warm), mix["loop"], 1e9)
    if tel.trace is not None:
        tel.trace.spans.clear()
    return SimpleNamespace(dev=dev, n_devices=len(devs), peak=peak,
                           cfg=cfg, seg_len=seg_len,
                           shapes=shapes, params=params, tel=tel,
                           engine=engine, sched=sched, chunk=chunk,
                           counter=counter)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: Optional[Dict] = None, conf: Optional[Dict] = None,
             mix: Optional[Dict] = None, cell: Optional[Dict] = None,
             allow_cpu: bool = False, fault: Optional[str] = None,
             control: bool = False, t_start: float = T_START) -> Dict:
    """One run of one cell; returns the result object. ``bench``, ``conf``,
    ``mix`` and ``cell`` default to the files found by name; tests pass
    their own, with ``allow_cpu`` and a planted ``fault``; ``control``
    (control.py) also reads the float8 control's gaps."""
    bench = bench or load_bench()
    entry = next(w for w in bench["workloads"] if w["name"] == workload)
    conf = conf or load_config(entry["config"])
    mix = mix or traffic.load(entry["traffic"])
    cell = cell or load_cell(workload)

    import jax
    from repro.serve.telemetry import default_registry
    ses = prepare(entry, conf, mix, seed, seconds, trace, allow_cpu, fault)
    dev, engine, sched, tel = ses.dev, ses.engine, ses.sched, ses.tel
    cfg, seg_len, chunk, counter = ses.cfg, ses.seg_len, ses.chunk, ses.counter
    counts0 = engine.compile_counts()
    setup_s = time.perf_counter() - t_start

    # ---- the window
    reqs = traffic.requests(mix, seed, seconds, seg_len, cfg.vocab)
    counter.armed = True
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-call Python events
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        recs, t0, t_close, t_end = client.drive(sched, reqs, mix["loop"],
                                                seconds)
    t_stop = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    counter.armed = False
    grown = {k: v - counts0.get(k, 0)
             for k, v in engine.compile_counts().items()
             if v != counts0.get(k, 0)}
    n_compiles = counter.n["backend_compile"] + counter.n["cache_hit"]
    log(f"window: {len(recs)} requests, {t_end - t0:.3f} s "
        f"(closed at {t_close - t0:.3f} s); compiles in window "
        f"{n_compiles} ({counter.n}); engine programs added {grown}")
    disp = {k: int(v) for k, v in default_registry().counters.items()
            if k.startswith("kernel_dispatch_total")}
    log(f"kernel dispatch: {disp}")
    log("client side: " + ", ".join(
        f"{n} {client.end_to_end(n, recs, t0)}" for n in client.END_TO_END))
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    result = {"correct": False, "attempted": len(recs),
              "failed": sum(1 for r in recs if r.error or not r.done)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": ses.n_devices, "memory_peak_bytes": peak_bytes}
    metrics: Dict[str, Dict] = {}
    if not trace:
        for m in cell_metrics(bench, workload, "end_to_end"):
            if m["name"] == "setup_s":
                v = setup_s
            else:
                v = client.end_to_end(m["name"], recs, t0)
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from tracefile import find_xplane, load as load_trace, describe
        t_read = time.perf_counter()
        tr = load_trace(find_xplane(str(TRACE_DIR)))
        log(describe(tr, 25))
        runctx = context(recs, tel, tr, t0, conf, ses.peak, chunk, seg_len)
        for m in cell_metrics(bench, workload, "per_layer"):
            v = load_metric(m["name"])(runctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = tr.breakdown()
        log(f"trace: written in {t_read - t_stop:.1f} s, read and reduced "
            f"in {time.perf_counter() - t_read:.1f} s")
    if n_compiles:
        log(f"WARNING: {n_compiles} programs compiled or loaded in the window")

    # ---- correctness, after the program's state is freed
    shapes = ses.shapes
    del sched, engine, tel, ses
    free_device()
    check = verify(recs, conf, cell, mix, seed, shapes, control)
    result["correct"] = bool(check.pop("_ok")) and result["failed"] == 0
    if control:
        # the float8 control judged by the same test as the program
        result["control_correct"] = bool(check.pop("_control_ok", False))
    result["metrics"] = metrics
    result["device"] = device
    result["check"] = check
    return result


def context(recs, tel, tr, t0, conf, peak, chunk, seg_len):
    """What the per-layer readers read: the reduced trace and the run's own
    records, restricted to the traced window (the host clock's ``t0`` is
    the window's start; the trace may end before the harness's span)."""
    t_end = t0 + tr.window_s
    spans = tel.trace.spans if tel.trace is not None else []
    admit = {}
    for s in spans:
        if s.name == "admission" and s.lane is not None:
            try:
                admit[int(s.lane)] = s.t0
            except ValueError:
                pass
    done = [r for r in recs if r.t_first is not None and r.t_first <= t_end]
    prompts = [r.prompt_len for r in done]
    positions = []
    for r in recs:
        j = 0
        for t, n in r.deliveries:
            if t <= t_end:
                positions += [(r.prompt_len + j + i) % seg_len
                              for i in range(int(n))]
            j += int(n)
    n_chunks = sum(1 for s in spans
                   if s.name == "decode_chunk" and s.t1 <= t_end)
    return SimpleNamespace(
        trace=tr, records=recs, model=conf["model"], peak=peak,
        admit_start=admit,
        segments_prefilled=sum(p // seg_len for p in prompts),
        prompts_prefilled=prompts, decode_positions=positions,
        decode_steps=n_chunks * chunk)


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

def sample(recs, mix: Dict, seed: int, seg_len: int) -> List:
    """The finished requests the reference checks: the longest, then others
    drawn from the seed while the segment budget allows."""
    fin = [r for r in recs if r.done and not r.error]
    if not fin:
        return []
    budget = mix["check"]["max_segments"]
    fin.sort(key=lambda r: (-(r.prompt_len + r.max_new), r.idx))
    out = [fin[0]]
    used = -(-(fin[0].prompt_len + fin[0].max_new) // seg_len)
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 11])
    for i in rng.permutation(len(fin) - 1) + 1:
        r = fin[i]
        cost = -(-(r.prompt_len + r.max_new) // seg_len)
        if used + cost <= budget:
            out.append(r)
            used += cost
    return out


def readings(chosen, gaps: List[np.ndarray], seg_len: int) -> Dict:
    """The numbers compared, from each sampled request's per-token gaps:
    the widest gap, and the mean gap over the tokens whose position lies
    past the first full segment: the ones that read a memory that a
    segment wrote (in decode, a segment flush)."""
    past = np.concatenate([
        g[r.prompt_len - 1 + np.arange(g.shape[0]) >= seg_len]
        for r, g in zip(chosen, gaps)])
    return {"max_gap": float(max(g.max() for g in gaps)),
            "memory_mean_gap": (float(past.mean()) if past.size
                                 else math.inf),
            "memory_tokens": int(past.size)}


def passes(read: Dict, lim: Dict) -> bool:
    return (math.isfinite(read["max_gap"])
            and read["max_gap"] <= lim["max_gap"]
            and read["memory_tokens"] >= 1
            and read["memory_mean_gap"] <= lim["memory_mean_gap"])


def verify(recs, conf: Dict, cell: Dict, mix: Dict, seed: int, shapes,
           control: bool = False) -> Dict:
    """Replay the sampled requests through the reference; ``control`` also
    runs the float8 control at the same positions (control.py only) and
    judges it by the same limits."""
    import reference
    import weights
    seg_len = conf["model"]["armt"]["segment_len"]
    chosen = sample(recs, mix, seed, seg_len)
    lim = cell["limits"]
    if not chosen:
        log("check: no finished request to compare")
        return {"_ok": False, "compared_tokens": {"value": 0, "limit": 1}}
    params = weights.make(shapes, conf["weights"], seed)
    ref = reference.Reference(conf["model"], params)
    ctl = (reference.Reference(conf["model"], params, quant="fp8")
           if control else None)
    t = time.perf_counter()
    gaps, cgaps = [], []
    for r in chosen:
        served = np.asarray(r.tokens[:r.max_new], np.int32)
        gaps.append(reference.served_gaps(ref, r.prompt, served))
        if ctl is not None:
            cgaps.append(reference.control_gaps(ref, ctl, r.prompt, served))
    n_tok = sum(r.max_new for r in chosen)
    read = readings(chosen, gaps, seg_len)
    log(f"check: {len(chosen)} requests (prompts "
        f"{[r.prompt_len for r in chosen]}), {n_tok} served tokens, "
        f"reference {time.perf_counter() - t:.1f} s; per request max gap "
        + " ".join(f"{g.max():.4f}" for g in gaps))
    check = {"max_gap": {"value": read["max_gap"], "limit": lim["max_gap"]},
             "memory_mean_gap": {"value": read["memory_mean_gap"],
                                  "limit": lim["memory_mean_gap"]},
             "compared_tokens": {"value": n_tok, "limit": 1},
             "memory_tokens": {"value": read["memory_tokens"], "limit": 1}}
    check["_ok"] = passes(read, lim) and n_tok >= 1
    if cgaps:
        cread = readings(chosen, cgaps, seg_len)
        log("control: per request max gap "
            + " ".join(f"{g.max():.4f}" for g in cgaps))
        for k in ("max_gap", "memory_mean_gap"):
            check["control_" + k] = {"value": cread[k], "limit": lim[k]}
        check["_control_ok"] = passes(cread, lim)
    del ref, ctl, params
    free_device()
    return check


# ---------------------------------------------------------------------------
# Planted faults (the tests, and control.py's fault readings)
# ---------------------------------------------------------------------------

def plant(fault: str, sched) -> None:
    """Break the timed path underneath the harness: 'token' alters a token
    where the decode chunk produces it; 'state' makes the decode chunk
    return the slots' state unchanged; 'memory' makes the decode chunk's
    segment flush leave the memory (A, z) as it was, while the segment's
    cache is reset as usual."""
    inner = sched._chunk_fn
    if fault == "token":
        def chunk_fn(params, pool, tok, active, remaining):
            out = inner(params, pool, tok, active, remaining)
            toks = out[4].at[-1].add(1)
            return out[:4] + (toks,) + out[5:]
    elif fault == "state":
        def chunk_fn(params, pool, tok, active, remaining):
            out = inner(params, pool, tok, active, remaining)
            return (pool,) + out[1:]
    elif fault == "memory":
        from repro.models import model as program_model

        def chunk_fn(params, pool, tok, active, remaining):
            # the chunk is traced on its first call, inside this swap
            keep = program_model.mem_update
            program_model.mem_update = (
                lambda p, st, m, acfg: {"A": st["A"], "z": st["z"]})
            try:
                return inner(params, pool, tok, active, remaining)
            finally:
                program_model.mem_update = keep
    else:
        raise ValueError(f"unknown fault {fault!r}")
    sched._chunk_fn = chunk_fn


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    res = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    for k, v in res["check"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
