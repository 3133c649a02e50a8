"""Serving driver: ``python -m repro.launch.serve --arch <id> --smoke``.

Two modes:
  * single (default): one fixed-shape batch, prefilled with diagonal
    batching, decoded on-device against constant-size ARMT state. With
    ``--session-store`` it runs a two-turn session demo (turn 2 resumes
    from the stored state instead of re-prefilling turn 1).
  * ``--continuous``: a stream of requests with heterogeneous prompt
    lengths through the continuous-batching scheduler
    (serve/scheduler.py) — tokens stream back per request as they are
    produced. Scheduler rejections (queue-full, invalid request, evicted
    session) arrive as structured ``RequestError`` events on the same
    stream and are printed, never raised out of the iterator mid-serve.
    With ``--prefix-cache`` the requests share a system prompt and
    admission transplants the cached boundary snapshot (state store,
    DESIGN.md §9).

Both modes accept ``--mesh data=2,model=4[,stage=..]`` (launch/mesh.py
``parse_mesh``) for mesh-native serving (DESIGN.md §10): params shard over
'model' (and the stacked pattern over 'stage'), decode slots over 'data',
and the whole serve stack stays single jitted graphs with GSPMD inserting
the collectives. Sharded serving is token-identical to single-device
(tests/test_serve_sharded.py).

Observability (DESIGN.md §13): ``--trace-out trace.json`` records the
span timeline — the scheduler's phases (``SCHED_PHASES``: admit,
admission round, dispatch, decode chunk, drain, idle drain, wait), each
chunk's counters (steps, active rows, flush steps, flush rows needed and
computed) on its ``sched.drain`` span, per-request admissions, emits and
segment flushes — as Chrome-trace JSON for https://ui.perfetto.dev
(schema-validated on export, gated in CI via
``python -m repro.serve.telemetry``); ``--metrics`` / ``--metrics-out``
dump the metrics-registry snapshot (admissions, flush rows and steps,
queue waits, pool occupancy, device memory, jit-compile counts, XLA
backend compiles); ``--profile-dir`` captures a ``jax.profiler`` trace,
in which every recorder span opened around live work has a
``TraceAnnotation`` twin carrying its arguments. Both files time events
on the profiler's host clock: with ``--profile-dir`` the ``--trace-out``
times are µs since that profile's start, the origin of the profile's own
trace, so merging the two files' events gives one timeline.
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--serve-mode", default="armt", choices=["armt", "cache"])
    ap.add_argument("--schedule", default="diagonal",
                    choices=["diagonal", "sequential"])
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 samples on device")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over --requests requests")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--max-queue", type=int, default=None,
                    help="push model: drain the source into a backlog of at "
                         "most this many requests and reject (structured "
                         "queue_full event) beyond it; default is the pull "
                         "model (requests pulled lazily between chunks)")
    ap.add_argument("--prefill-groups-per-chunk", type=int, default=4,
                    help="interleaved admission (DESIGN.md §11): advance "
                         "the admitting request's diagonal prefill this "
                         "many groups per decode chunk instead of blocking "
                         "every slot for the whole prompt; 0 = legacy "
                         "blocking admission")
    ap.add_argument("--fused-admission", action="store_true",
                    help="run the admissions' diagonal groups inside the "
                         "same jitted launch as the decode chunk (one "
                         "dispatch per chunk interval)")
    ap.add_argument("--max-concurrent-admissions", type=int, default=None,
                    help="pooled concurrent admissions (DESIGN.md §12): up "
                         "to this many interleaved admissions in flight at "
                         "once, same-signature prefill carries batched into "
                         "one pooled launch per round; default None bounds "
                         "the pool only by free slots, 1 restores the "
                         "single-admission behavior")
    ap.add_argument("--admission-fairness", default="round_robin",
                    choices=["round_robin", "oldest_first"],
                    help="group-budget policy across in-flight admissions: "
                         "round_robin advances every carry k groups per "
                         "round; oldest_first is head-of-line")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="segment-granular prefix cache: requests share a "
                         "system prompt; admission transplants the cached "
                         "boundary state instead of re-prefilling it")
    ap.add_argument("--prefix-cache-mb", type=float, default=64.0,
                    help="prefix-cache LRU byte budget")
    ap.add_argument("--session-store", action="store_true",
                    help="multi-turn session demo: turn 2 resumes from the "
                         "stored end-of-turn-1 state")
    ap.add_argument("--session-mb", type=float, default=128.0)
    ap.add_argument("--store-dir", default=None,
                    help="disk-spill directory for evicted store entries "
                         "(checkpoint-manager named blobs)")
    ap.add_argument("--mesh", default=None, metavar="AXES",
                    help="mesh-native serving (DESIGN.md §10): comma list of "
                         "axis[=size] from {pod,data,model,stage}, e.g. "
                         "'data=2,model=4' or 'data,model=2' (one size may "
                         "be omitted). Params shard over 'model'/'stage', "
                         "decode slots over 'data'; GSPMD does the "
                         "collectives")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="observability (DESIGN.md §13): write the serve "
                         "run's chunk-granular trace timeline as Chrome-"
                         "trace/Perfetto JSON (load in ui.perfetto.dev)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the engine's metrics snapshot (compile "
                         "counts, store stats, serving histograms) as JSON "
                         "after the run")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics snapshot JSON to a file")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler XLA trace of the run into "
                         "this directory (TensorBoard/Perfetto); the "
                         "scheduler's TraceAnnotation spans line up with "
                         "the device timeline")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import json

    import jax
    import numpy as np
    from repro.configs import get_config, get_smoke_config
    from repro.models import init_params
    from repro.serve import (PrefixCache, Request, RequestError, ServeEngine,
                             SessionStore, Telemetry, validate_chrome_trace)
    from repro.serve.telemetry import profile_start_ns
    from repro.utils import use_compile_cache
    use_compile_cache()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    if args.continuous and (args.temperature > 0 or args.top_k > 0):
        ap.error("--continuous streams greedy tokens; --temperature/--top-k "
                 "apply to single-batch mode only")
    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import parse_mesh
        mesh = parse_mesh(args.mesh)
        print(f"mesh: {dict(mesh.shape)} over {mesh.devices.size} "
              f"{mesh.devices.flat[0].platform} device(s)")
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    seg = cfg.armt.segment_len if cfg.armt else 64
    prefix_cache = (PrefixCache(seg, max_bytes=int(args.prefix_cache_mb * 2**20),
                                spill_dir=args.store_dir)
                    if args.prefix_cache else None)
    session_store = (SessionStore(max_bytes=int(args.session_mb * 2**20),
                                  spill_dir=args.store_dir)
                     if args.session_store else None)
    # headroom for the longer of the two continuous prompt buckets
    tel = Telemetry(trace=args.trace_out is not None)
    eng = ServeEngine(params, cfg, serve_mode=args.serve_mode,
                      schedule=args.schedule,
                      max_len=args.prompt_len + seg // 2 + args.max_new,
                      prefix_cache=prefix_cache, session_store=session_store,
                      mesh=mesh, telemetry=tel)

    def emit_telemetry():
        """--trace-out / --metrics[-out] epilogue shared by both modes."""
        if args.trace_out:
            # on the profile's clock and origin when there is a profile
            origin = (profile_start_ns(args.profile_dir)
                      if args.profile_dir else None)
            tel.trace.export(args.trace_out, origin_ns=origin)
            errs = validate_chrome_trace(args.trace_out)
            n = len(tel.trace.spans)
            if errs:
                raise SystemExit(f"trace schema check failed: {errs}")
            print(f"trace: {n} spans -> {args.trace_out}")
        if args.metrics or args.metrics_out:
            snap = eng.metrics_snapshot()
            if args.metrics:
                print("metrics:", json.dumps(snap, indent=2, default=str))
            if args.metrics_out:
                with open(args.metrics_out, "w") as f:
                    json.dump(snap, f, indent=2, default=str)
                print(f"metrics -> {args.metrics_out}")

    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)

    if args.continuous:
        rng = np.random.default_rng(args.seed + 1)
        # two prompt-length buckets: heterogeneous segment phases without a
        # fresh prefill compile per request (cf. benchmarks/bench_serve.py)
        lens = [args.prompt_len if i % 2 == 0
                else max(1, args.prompt_len + seg // 2)
                for i in range(args.requests)]
        if prefix_cache is not None:
            # shared system prompt: every request begins with the same full
            # segments, so admissions after the first hit the cache
            n_sys = max(seg, (args.prompt_len // (2 * seg)) * seg)
            sys_prompt = rng.integers(8, cfg.vocab, (n_sys,)).astype("int32")
            reqs = [Request(
                req_id=f"r{i}",
                prompt=np.concatenate([
                    sys_prompt,
                    rng.integers(8, cfg.vocab,
                                 (max(1, L - n_sys),)).astype("int32")]),
                max_new=args.max_new)
                for i, L in enumerate(lens)]
        else:
            reqs = [Request(req_id=f"r{i}",
                            prompt=rng.integers(8, cfg.vocab,
                                                (L,)).astype("int32"),
                            max_new=args.max_new)
                    for i, L in enumerate(lens)]
        t0 = time.perf_counter()
        n_tok = 0
        outs = {r.req_id: [] for r in reqs}
        metrics = {}
        for ev in eng.serve(
                reqs, n_slots=args.slots, chunk=args.chunk,
                max_queue=args.max_queue,
                prefill_groups_per_chunk=args.prefill_groups_per_chunk,
                fused_admission=args.fused_admission,
                max_concurrent_admissions=args.max_concurrent_admissions,
                admission_fairness=args.admission_fairness):
            if isinstance(ev, RequestError):
                print(f"{ev.req_id}: REJECTED [{ev.code}] {ev.message}")
                continue
            n_tok += 1
            outs[ev.req_id].append(ev.token)
            if ev.done:
                metrics[ev.req_id] = (ev.ttft_s, ev.tok_s)
                print(f"{ev.req_id}: done ({ev.index + 1} tokens, "
                      f"ttft={ev.ttft_s:.2f}s, {ev.tok_s:.1f} tok/s) "
                      f"first 8: {outs[ev.req_id][:8]}")
        dt = time.perf_counter() - t0
        k = args.prefill_groups_per_chunk
        n_conc = args.max_concurrent_admissions
        adm = ("blocking" if k == 0 else
               "blocking(jitted stepper, whole stage per advance)" if k < 0
               else f"interleaved(k={k}"
                    f", N={'slots' if n_conc is None else n_conc}"
                    f", {args.admission_fairness}"
                    f"{', fused' if args.fused_admission else ''})")
        print(f"arch={cfg.name} mode={args.serve_mode} slots={args.slots} "
              f"requests={args.requests} admission={adm}")
        print(f"{n_tok} tokens in {dt:.2f}s ({n_tok / dt:.1f} tok/s)")
        if prefix_cache is not None:
            st = prefix_cache.stats.as_dict()
            print(f"prefix-cache: {st['hits']} hits / {st['misses']} misses, "
                  f"{len(prefix_cache)} entries, "
                  f"{st['bytes_in_ram'] / 2**10:.1f} KiB, "
                  f"{st['evictions']} evictions ({st['spills']} spilled)")
        if args.profile_dir:
            jax.profiler.stop_trace()
            print(f"xla profile -> {args.profile_dir}")
        emit_telemetry()
        return

    prompts = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                 (args.batch, args.prompt_len), 8, cfg.vocab)
    if session_store is not None:
        # two-turn session demo on row 0: turn 2 feeds only the new tokens
        turn2 = jax.random.randint(jax.random.PRNGKey(args.seed + 2),
                                   (1, max(8, seg // 2)), 8, cfg.vocab)
        r1 = eng.generate(prompts[:1], args.max_new, session_id="demo")
        r2 = eng.generate(turn2, args.max_new, session_id="demo")
        print(f"arch={cfg.name} session demo: turn1 ttft={r1.ttft_s:.2f}s "
              f"({prompts.shape[1]} prompt tokens), turn2 resumed="
              f"{r2.resumed} ttft={r2.ttft_s:.2f}s "
              f"({turn2.shape[1]} new tokens, history never recomputed)")
        print("turn2 first 8:", r2.tokens[0, :8].tolist())
        if args.profile_dir:
            jax.profiler.stop_trace()
            print(f"xla profile -> {args.profile_dir}")
        emit_telemetry()
        return

    t0 = time.perf_counter()
    res = eng.generate(prompts, args.max_new, temperature=args.temperature,
                       top_k=args.top_k, seed=args.seed)
    dt = time.perf_counter() - t0
    print(f"arch={cfg.name} mode={args.serve_mode} schedule={res.schedule} "
          f"prefill_segments={res.prefill_segments}")
    print(f"generated {res.tokens.shape} tokens in {dt:.2f}s "
          f"(ttft={res.ttft_s:.2f}s, decode {res.tok_s:.1f} tok/s)")
    print("first row:", res.tokens[0].tolist())
    if args.profile_dir:
        jax.profiler.stop_trace()
        print(f"xla profile -> {args.profile_dir}")
    emit_telemetry()


if __name__ == "__main__":
    main()
