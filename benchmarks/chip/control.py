"""The correctness check's control and its readings, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

For each seed: one run of the cell (no trace), then, on the same sampled
prompts and served tokens, the reference's gaps (the program's reading)
and the float8 control's (the reference computed in the precision below
the configuration's bfloat16: the gap of the token it puts first), each
judged by the run's own limit test (``correct``, ``control_correct``).
``--fault`` plants a fault in the timed path instead (``run.plant``), for
its reading. Prints one JSON line per seed. The limit in
``cells/<cell>.json`` is set between the largest program reading over a
dozen seeds and the smallest control reading (PERF.md). The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    ap.add_argument("--fault", choices=("token", "state", "memory"),
                    help="run the cell with this fault planted in the "
                    "timed path (run.plant): its reading")
    args = ap.parse_args(argv)
    for seed in args.seeds:
        t = time.perf_counter()
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           control=bool(args.control), fault=args.fault,
                           t_start=t)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": res["correct"],
                          "control_correct": res.get("control_correct"),
                          "check": res["check"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
