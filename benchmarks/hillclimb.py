"""§Perf hillclimb driver — lowers cell *variants* and records the roofline
deltas (hypothesis -> change -> before -> after in EXPERIMENTS.md §Perf).

Variants:
  slot_shard   diagonal-as-pipeline: slots sharded over a 'stage' axis,
               per-layer weights fully local, shift -> collective-permute.
               Mesh (data, stage) replaces (data, model).
  slot_tp      hybrid: (data, stage, model) — slots over stage, residual TP
               over a small model axis (for archs whose dims need it).
  seq_prefill  prefill with the sequential schedule (paper baseline ARMT).

Usage:
  PYTHONPATH=src python -m benchmarks.hillclimb --exp danube_slot8

A CPU-only tool: it forces 512 host devices at import. Keep it off the chip
path — on a TPU host it would hold the chip for a run that measures nothing
there.
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
import jax.numpy as jnp  # noqa: E402
from jax import ShapeDtypeStruct as SDS                      # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P   # noqa: E402

from repro.configs import SHAPES, get_config                 # noqa: E402
from repro.launch.dryrun import measure                      # noqa: E402
from repro.launch.specs import Cell, build_cell              # noqa: E402
from repro.models import forward_hidden, last_logits         # noqa: E402
from repro.models.model import param_specs as mps            # noqa: E402
from repro.parallel import sharding as shd                   # noqa: E402
from repro.roofline import model_flops                       # noqa: E402

OUT = Path("artifacts/hillclimb")


def _run(name: str, mesh, cell: Cell, mf: float):
    record = {"arch": cell.arch, "shape": cell.shape, "tag": name,
              "mesh": "x".join(str(s) for s in mesh.devices.shape),
              "n_devices": mesh.size, "ok": False, "meta": cell.meta}
    t0 = time.time()
    try:
        measure(mesh, cell, mf, record)
    except Exception as e:
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-3000:]
    record["total_s"] = round(time.time() - t0, 2)
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"{name}.json").write_text(json.dumps(record, indent=1,
                                                 default=str))
    if record["ok"]:
        r = record["roofline"]
        print(f"[OK ] {name}: comp={r['compute_s']:.3e} mem={r['memory_s']:.3e}"
              f" coll={r['collective_s']:.3e} dom={r['dominant']}"
              f" frac={r['roofline_fraction']:.4f}", flush=True)
    else:
        print(f"[FAIL] {name}: {record.get('error', '')[:200]}", flush=True)
    return record


def slot_shard_prefill(arch: str, *, stage: int, data: int,
                       tp: int = 1, schedule: str = "diagonal",
                       attn_impl: str = "dense",
                       moe_dispatch: str = None,
                       name: str = "") -> dict:
    """Prefill cell with the slot dim sharded over a 'stage' axis."""
    import dataclasses
    cfg = dataclasses.replace(get_config(arch), attn_impl=attn_impl)
    if moe_dispatch and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch=moe_dispatch))
    shape = SHAPES["prefill_32k"]
    axes = [("data", data), ("stage", stage)]
    if tp > 1:
        axes.append(("model", tp))
    assert data * stage * tp == 256, (data, stage, tp)
    mesh = jax.make_mesh(tuple(s for _, s in axes), tuple(a for a, _ in axes))

    dp = "data"
    slot_spec = P("stage", dp if shape.global_batch % data == 0 else None,
                  None, None)

    def prefill(params, batch):
        hidden, fin = forward_hidden(params, cfg, batch["tokens"],
                                     schedule=schedule, slot_spec=slot_spec)
        return last_logits(params, cfg, hidden), fin

    pshape = mps(cfg)
    with mesh:
        pspecs = shd.param_specs(pshape, mesh, stacked_axis="stage")
        batch = {"tokens": SDS((shape.global_batch, shape.seq_len), jnp.int32)}
        bspecs = {"tokens": NamedSharding(
            mesh, P(dp if shape.global_batch % data == 0 else None, None))}
    cell = Cell(arch, "prefill_32k", prefill, (pshape, batch),
                (pspecs, bspecs), None,
                {"kind": "prefill", "schedule": schedule,
                 "variant": f"slot_shard stage={stage} data={data} tp={tp}"})
    return _run(name or f"{arch}__prefill32k__slot{stage}", mesh, cell,
                model_flops(cfg, shape))


EXPERIMENTS = {
    # cell 3 (paper-representative): danube prefill, diagonal schedule
    "danube_base": lambda: _baseline("h2o-danube-1.8b", "prefill_32k",
                                     schedule="diagonal"),
    "danube_seq": lambda: _baseline("h2o-danube-1.8b", "prefill_32k",
                                    schedule="sequential"),
    "danube_slot8": lambda: slot_shard_prefill(
        "h2o-danube-1.8b", stage=8, data=32),
    "danube_slot8_tp2": lambda: slot_shard_prefill(
        "h2o-danube-1.8b", stage=8, data=16, tp=2),
    "danube_slot8_chunked": lambda: slot_shard_prefill(
        "h2o-danube-1.8b", stage=8, data=32, attn_impl="chunked",
        name="h2o-danube-1.8b__prefill32k__slot8_chunked"),
    "qwen32b_slot16": lambda: slot_shard_prefill(
        "qwen2.5-32b", stage=16, data=16),
    "chameleon_slot16": lambda: slot_shard_prefill(
        "chameleon-34b", stage=16, data=16),
    # MoE under slot sharding: each stage owns whole layers => expert
    # weights AND dispatch fully local (no EP all-to-all at all)
    "qwen2moe_slot8": lambda: slot_shard_prefill(
        "qwen2-moe-a2.7b", stage=8, data=32),
    "qwen2moe_slot8_perrow": lambda: slot_shard_prefill(
        "qwen2-moe-a2.7b", stage=8, data=32, moe_dispatch="per_row",
        name="qwen2-moe-a2.7b__prefill32k__slot8_perrow"),
    "qwen2moe_slot8_einsum": lambda: slot_shard_prefill(
        "qwen2-moe-a2.7b", stage=8, data=32, moe_dispatch="einsum",
        name="qwen2-moe-a2.7b__prefill32k__slot8_einsum"),
    "minitron_slot16": lambda: slot_shard_prefill(
        "minitron-8b", stage=16, data=16),
    "whisper_slot8": lambda: slot_shard_prefill(
        "whisper-medium", stage=8, data=32),
    # cell 1: kimi train — v2 sweep already applies fsdp/factored/microbatch;
    # variants probed here
    "kimi_train_mb16": lambda: _baseline("kimi-k2-1t-a32b", "train_4k",
                                         microbatches=16),
    # cell 2: falcon train — ssm method comparison is in the main sweep
    "falcon_prefill_slot16": lambda: slot_shard_prefill(
        "falcon-mamba-7b", stage=16, data=16),
}


def _baseline(arch, shape, **kw):
    from repro.launch.dryrun import run_cell
    return run_cell(arch, shape, multi_pod=False,
                    save_dir=OUT, tag="_" + "_".join(
                        f"{k}={v}" for k, v in kw.items()) if kw else "_base",
                    **kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", required=True,
                    help=f"one of {sorted(EXPERIMENTS)} or comma list")
    args = ap.parse_args()
    for e in args.exp.split(","):
        EXPERIMENTS[e]()


if __name__ == "__main__":
    main()
