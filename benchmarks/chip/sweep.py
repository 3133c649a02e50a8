"""Rate sweep of an open-loop cell, on the chip: finds the knee once, for
the fixed rate its traffic file states.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates <r> [<r> ...]

One set-up, then one window per offered rate (requests per second). For
each rate it prints the requests sent and finished, the TTFT and ITL
percentiles, and how the wait before admission grew across the window:
the mean wait of the last third of the requests over that of the first.
The knee is the highest rate whose wait does not grow over the window.
"""
from __future__ import annotations

import argparse
import copy
import json
import statistics
import sys
import time

import numpy as np

import client
import run
import traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = run.load_bench()
    entry = next(w for w in bench["workloads"] if w["name"] == args.workload)
    conf = run.load_config(entry["config"])
    mix = traffic.load(entry["traffic"])
    t = time.perf_counter()
    ses = run.prepare(entry, conf, mix, args.seed, args.seconds, False)
    run.log(f"set-up {time.perf_counter() - t:.1f} s")
    for rate in args.rates:
        m = copy.deepcopy(mix)
        m["rate_rps"] = rate
        # this rate's prompt lengths, warm before its window
        warm = [traffic.Req(i, np.zeros(w["prompt"], np.int32), w["max_new"],
                            0.0, "warm")
                for i, w in enumerate(traffic.warm_shapes(
                    m, ses.seg_len, args.seconds, ses.chunk))]
        client.drive(ses.sched, iter(warm), m["loop"], 1e9)
        reqs = traffic.requests(m, args.seed, args.seconds, ses.seg_len,
                                ses.cfg.vocab)
        recs, t0, t_close, t_end = client.drive(ses.sched, reqs, m["loop"],
                                                args.seconds)
        recs.sort(key=lambda r: r.t_due)
        waits = [r.t_sent - r.t_due for r in recs]
        k = max(1, len(waits) // 3)
        first, last = statistics.mean(waits[:k]), statistics.mean(waits[-k:])
        ttft = client.ttft_s(recs)
        itl = client.itl_ms(recs)
        print(json.dumps({
            "rate_rps": rate, "sent": len(recs),
            "finished": sum(r.done for r in recs),
            "drain_s": t_end - t_close,
            "ttft_p50_s": client._pct(ttft, 50),
            "ttft_p95_s": client._pct(ttft, 95),
            "itl_p50_ms": client._pct(itl, 50),
            "itl_p95_ms": client._pct(itl, 95),
            "wait_first_s": first, "wait_last_s": last}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
