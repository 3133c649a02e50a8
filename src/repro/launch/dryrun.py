"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and record memory/cost/roofline artifacts.

MUST set the host-device override before any other import touches jax.
This is a CPU-only tool: it forces 512 host devices at import and compiles
for them. Keep it off the chip path — on a TPU host it would hold the chip
for a run that measures nothing there.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("PREPEND_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path # noqa: E402

import jax               # noqa: E402

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config   # noqa: E402
from repro.launch.mesh import make_production_mesh             # noqa: E402
from repro.launch.specs import build_cell                      # noqa: E402
from repro.roofline import HloAnalyzer, model_flops, roofline_terms  # noqa: E402


def measure(mesh, cell, mf: float, record: dict, *, save_dir: Path = None,
            save_hlo: bool = False, tag: str = "") -> dict:
    """Lower + compile a Cell on `mesh`; fill `record` with cost/memory/
    roofline artifacts."""
    t0 = time.time()
    with mesh:
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        cost = compiled.cost_analysis() or {}
        try:
            ma = compiled.memory_analysis()
            mem = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                "generated_code_bytes":
                    getattr(ma, "generated_code_size_in_bytes", None),
            }
        except Exception as e:   # CPU backend may not support it
            mem = {"error": str(e)}
        hlo = compiled.as_text()
        n_dev = mesh.size
        analyzer = HloAnalyzer(hlo, n_dev)
        coll = analyzer.collectives()
        roof = roofline_terms(analyzer, n_dev, mf)
        record.update({
            "ok": True,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "cost": {k: cost.get(k) for k in
                     ("flops", "bytes accessed", "transcendentals")},
            "memory": mem,
            "collectives": {"wire_bytes": coll.total_wire_bytes,
                            "per_op": coll.per_op, "count": coll.count},
            "roofline": roof.to_dict(),
            "hlo_bytes": len(hlo),
        })
        if save_hlo and save_dir:
            (save_dir / f"{record['arch']}__{record['shape']}__"
             f"{record['mesh']}{tag}.hlo.txt").write_text(hlo)
    return record


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             schedule=None, serve_mode=None, microbatches=None,
             save_dir: Path = None, tag: str = "", verbose: bool = True,
             save_hlo: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()
    record = {"arch": arch, "shape": shape_name,
              "mesh": "x".join(str(s) for s in mesh.devices.shape),
              "n_devices": n_dev, "tag": tag, "ok": False}
    try:
        with mesh:
            cell = build_cell(arch, shape_name, mesh, schedule=schedule,
                              serve_mode=serve_mode, microbatches=microbatches)
        record["meta"] = cell.meta
        mf = model_flops(get_config(arch), SHAPES[shape_name])
        measure(mesh, cell, mf, record, save_dir=save_dir,
                save_hlo=save_hlo, tag=tag)
    except Exception as e:
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    record["total_s"] = round(time.time() - t0, 2)
    if save_dir:
        save_dir.mkdir(parents=True, exist_ok=True)
        name = f"{arch}__{shape_name}__{record['mesh']}{tag}.json"
        (save_dir / name).write_text(json.dumps(record, indent=1, default=str))
    if verbose:
        status = "OK " if record["ok"] else "FAIL"
        extra = ""
        if record["ok"]:
            r = record["roofline"]
            extra = (f" compile={record['compile_s']:.0f}s"
                     f" comp={r['compute_s']:.3e}s mem={r['memory_s']:.3e}s"
                     f" coll={r['collective_s']:.3e}s dom={r['dominant']}"
                     f" useful={r['useful_ratio']:.2f}")
        else:
            extra = " " + record.get("error", "")[:160]
        print(f"[{status}] {arch:22s} {shape_name:12s} mesh={record['mesh']}"
              f"{extra}", flush=True)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id, comma list, or 'all'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--schedule", default=None)
    ap.add_argument("--serve-mode", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--tag", default="")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    out = Path(args.out)

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, multi_pod=mp,
                               schedule=args.schedule,
                               serve_mode=args.serve_mode,
                               microbatches=args.microbatches,
                               save_dir=out, tag=args.tag,
                               save_hlo=args.save_hlo)
                n_fail += 0 if rec["ok"] else 1
    print(f"dry-run complete; failures: {n_fail}", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
