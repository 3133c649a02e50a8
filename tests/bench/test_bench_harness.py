"""The on-chip benchmark's harness driven end to end on the CPU at a tiny
size: the run is correct on the program as it is, and ``correct`` comes out
false when the timed path is broken underneath (a token altered where it
is produced, a decode step that returns its state unchanged, a segment
flush that leaves the memory unchanged) and when the float8 control stands
in for the program."""
import copy
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import run  # noqa: E402
import traffic  # noqa: E402

LIMIT = 0.05
LIMITS = {"max_gap": LIMIT, "memory_mean_gap": 0.01}


def tiny(conf_name="h2o-danube-1.8b-armt", **model):
    conf = run.load_config(conf_name)
    sizes = dict(n_layers=3, d_model=64, n_heads=4, d_head=16, d_ff=128,
                 vocab=256, dtype="float32")
    sizes.update(model)
    conf["model"].update(sizes)
    conf["model"]["n_kv_heads"] = min(conf["model"]["n_kv_heads"], 2)
    conf["model"]["armt"].update(segment_len=32, num_mem_tokens=4, d_mem=8)
    conf["scheduler"] = {"n_slots": 4, "chunk": 4}
    return conf


def tiny_mix(name):
    mix = traffic.load(name)
    if name == "longdoc":
        mix["classes"][0].update(segments=[1, 2, 3], tail=8, max_new=6)
        mix["check"]["max_segments"] = 10
    else:
        mix["classes"][0]["tokens"].update(median=20, min=4, max=31)
        mix["classes"][0]["max_new"].update(median=10, min=4, max=40)
        mix["rate_rps"] = 8.0
    return mix


def bench(workload, cfg_name, mix_name):
    b = run.load_bench()
    b = copy.deepcopy(b)
    b["workloads"] = [{"name": workload, "config": cfg_name,
                       "traffic": mix_name, "chips": 1}]
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    return b


def drive(mix_name, fault=None, conf=None, seconds=1.0, seed=2 ** 40 + 3):
    conf = conf or tiny()
    return run.run_cell(
        "t", seed, seconds, False, bench=bench("t", conf["name"], mix_name),
        conf=conf, mix=tiny_mix(mix_name), cell={"limits": LIMITS},
        allow_cpu=True, fault=fault)


@pytest.mark.parametrize("mix", ["longdoc", "chat"])
def test_run_is_correct(mix):
    res = drive(mix)
    assert res["correct"], res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert res["check"]["compared_tokens"]["value"] >= 1
    assert res["check"]["memory_tokens"]["value"] >= 1
    assert res["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["token", "state", "memory"])
def test_planted_fault_is_not_correct(fault):
    conf = tiny()
    # at this size a memory written at the configuration's gain barely
    # moves the logits; at 0.01 a skipped write shows in every reading
    conf["weights"]["mem_wv_gain"] = 0.01
    res = drive("chat", fault=fault, conf=conf)
    assert not res["correct"]
    assert res["check"]["max_gap"]["value"] > LIMIT
    assert res["check"]["memory_mean_gap"]["value"] > LIMITS[
        "memory_mean_gap"]


def test_glm_shapes_run_correct():
    conf = tiny("chatglm3-6b-armt-14l")
    res = drive("longdoc", conf=conf)
    assert res["correct"], res["check"]


def test_control_fails_the_limit():
    """The float8 control, at the same prompts and served tokens, reads a
    gap above the limit that the program meets."""
    import jax
    import reference
    import weights
    from repro.models import init_params
    conf = tiny(d_model=128, d_ff=256)
    # the embedding's std scaled so the logits spread as at d_model 2560
    # (0.02 * sqrt(2560 / 128)): the gaps are in logits
    conf["weights"]["embed"] = 0.09
    cfg = run.build_cfg(conf)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    params = weights.make(shapes, conf["weights"], 5)
    ref = reference.Reference(conf["model"], params)
    ctl = reference.Reference(conf["model"], params, quant="fp8")
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 70).astype(np.int32)
    stream = np.concatenate([prompt, np.zeros(24, np.int32)])
    served = []
    for j in range(24):          # greedy tokens of the reference itself
        lg = ref.logits_at(stream[:70 + j], [69 + j])[0]
        served.append(int(lg.argmax()))
        stream[70 + j] = served[-1]
    served = np.asarray(served, np.int32)
    assert reference.served_gaps(ref, prompt, served).max() == 0.0
    g = reference.control_gaps(ref, ctl, prompt, served)
    assert g.max() > LIMIT


def test_control_run_is_not_correct():
    """Through the harness's own limit test, the program's run is correct
    and the float8 control's reading at the same tokens is not."""
    conf = tiny()
    res = run.run_cell(
        "t", 2 ** 40 + 5, 1.0, False, bench=bench("t", conf["name"], "chat"),
        conf=conf, mix=tiny_mix("chat"), cell={"limits": LIMITS},
        allow_cpu=True, control=True)
    assert res["correct"], res["check"]
    assert res["control_correct"] is False, res["check"]
    assert (res["check"]["control_max_gap"]["value"] > LIMIT
            or res["check"]["control_memory_mean_gap"]["value"]
            > LIMITS["memory_mean_gap"])


def test_no_tpu_exits_nonzero_without_result():
    out = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload",
         "danube.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        run.peak_for("TPU v9 imaginary")
    assert run.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
