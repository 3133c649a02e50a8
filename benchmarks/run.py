# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure:

  Fig. 4   bench_grouped_gemm       grouped GEMM group-size scaling
  Fig. 5   bench_attention          attention group-as-batch scaling
  Tab. 1/5/6/7 bench_inference_scaling  full vs sequential vs diagonal
  Tab. 2   bench_error_accumulation logits drift vs segments (fp32/bf16)
  Tab. 3/4 bench_babilong           needle-QA accuracy + speed
  §Roofline bench_roofline          dry-run artifact aggregation
  §Perf    bench_diagonal           sequential vs diagonal-vmap vs
                                    diagonal-fused -> BENCH_diagonal.json
  §Kernels bench_kernels            per-op autotune sweep + dispatch
                                    decisions -> BENCH_kernels.json
  §Serving bench_serve              continuous-batching + prefix-cache +
                                    session workloads -> BENCH_serve.json
  §Long-context bench_longctx       bounded-memory streaming prefill:
                                    memory curve + 1M-token run ->
                                    BENCH_longctx.json

``QUICK=0 python -m benchmarks.run`` for full sizes.
``python -m benchmarks.run --only serve`` (repeatable, comma-ok) runs a
subset — e.g. just the serve benches in CI, whose JSON is uploaded as a
workflow artifact.
"""
import argparse
import os
import sys
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", action="append", default=None,
                    metavar="NAME",
                    help="run only these benches (by short name: "
                         "grouped_gemm, attention, inference_scaling, "
                         "error_accumulation, babilong, roofline, diagonal, "
                         "serve, kernels, longctx); repeatable or "
                         "comma-separated")
    args = ap.parse_args(argv)

    quick = os.environ.get("QUICK", "1") != "0"
    from repro.utils import use_compile_cache
    use_compile_cache()
    import benchmarks.bench_grouped_gemm as g
    import benchmarks.bench_attention as a
    import benchmarks.bench_inference_scaling as i
    import benchmarks.bench_error_accumulation as e
    import benchmarks.bench_babilong as b
    import benchmarks.bench_roofline as r
    import benchmarks.bench_diagonal as d
    import benchmarks.bench_serve as sv
    import benchmarks.bench_kernels as kn
    import benchmarks.bench_longctx as lc

    by_name = {"grouped_gemm": g, "attention": a, "inference_scaling": i,
               "error_accumulation": e, "babilong": b, "roofline": r,
               "diagonal": d, "serve": sv, "kernels": kn, "longctx": lc}
    mods = list(by_name.values())
    if args.only:
        names = [n.strip() for part in args.only for n in part.split(",")]
        unknown = [n for n in names if n not in by_name]
        if unknown:
            ap.error(f"unknown bench(es) {unknown}; "
                     f"choose from {sorted(by_name)}")
        mods = [by_name[n] for n in names]

    print("name,us_per_call,derived")
    failures = 0
    for mod in mods:
        try:
            mod.main(quick=quick)
        except Exception:
            failures += 1
            print(f"{mod.__name__},0.0,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
