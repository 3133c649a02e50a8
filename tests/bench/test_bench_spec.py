"""BENCHMARK.json against the benchmark's contract (keys, names, units,
cells), and discovery: every configuration, traffic mix, cell and
per-layer metric is a file found by its name, so a new one needs no edit
of an existing file."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import client  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    return run.load_bench()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["command"][0] == "python3"
    for p in bench["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert (ROOT / bench["command"][1]).is_file()


def test_names_units_and_entries(bench):
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for kind, want in keys.items():
        names = [e["name"] for e in bench[kind]]
        assert len(names) == len(set(names))
        for e in bench[kind]:
            assert set(e) - {"workloads"} == want, e
            assert NAME.match(e["name"]), e["name"]
            for k in ("why", "layer", "source"):
                if k in e:
                    assert TEXT.match(e[k]), e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_is_whole(bench):
    pairs = set()
    cfgs = {c["name"] for c in bench["configs"]}
    used = set()
    four = 0
    for w in bench["workloads"]:
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        four += w["chips"] == 4
        pair = (w["config"], w["traffic"])
        assert pair not in pairs
        pairs.add(pair)
        used.add(w["config"])
        # found by name
        run.load_config(w["config"])
        traffic.load(w["traffic"])
        assert set(run.load_cell(w["name"])["limits"]) == {
            "max_gap", "memory_mean_gap"}
        e2e = run.cell_metrics(bench, w["name"], "end_to_end")
        per = run.cell_metrics(bench, w["name"], "per_layer")
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert per, w["name"]
        for m in per:
            assert m["moves"] in names, (w["name"], m["name"])
            run.load_metric(m["name"])
        assert names - {"setup_s"} <= set(client.END_TO_END)
    assert used == cfgs
    assert four <= len(bench["workloads"]) // 2 or four <= 1


def test_configs_state_published_sizes(bench):
    """Each configuration file runs the sizes it states, and differs from
    its published config only in the keys its entry lists as reduced."""
    pub_map = {"num_hidden_layers": "n_layers", "num_layers": "n_layers",
               "hidden_size": "d_model", "intermediate_size": "d_ff",
               "ffn_hidden_size": "d_ff", "num_attention_heads": "n_heads",
               "num_key_value_heads": "n_kv_heads",
               "multi_query_group_num": "n_kv_heads",
               "vocab_size": "vocab", "padded_vocab_size": "vocab",
               "kv_channels": "d_head", "add_qkv_bias": "qkv_bias",
               "tie_word_embeddings": "tie_embeddings"}
    for c in bench["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"].startswith(c["source"])
        changed = set()
        for k, v in conf["published"].items():
            if k in pub_map and conf["model"][pub_map[k]] != v:
                changed.add(k)
        assert changed == set(c["reduced"]), (c["name"], changed)
        cfg = run.build_cfg(conf)
        assert cfg.d_model == conf["model"]["d_model"]
        assert cfg.armt.segment_len == conf["model"]["armt"]["segment_len"]


def test_discovery_in_a_new_directory(tmp_path):
    """A configuration, a mix, a cell and a metric dropped into a directory
    are found by name, with no edit of an existing file."""
    for d in ("configs", "traffic", "metrics", "cells"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "new-model.json").write_text(
        json.dumps({"name": "new-model", "model": {"d_model": 8}}))
    (tmp_path / "traffic" / "burst.json").write_text(
        json.dumps({"loop": "closed", "classes": []}))
    (tmp_path / "cells" / "new.burst.json").write_text(
        json.dumps({"limits": {"max_gap": 0.1}}))
    (tmp_path / "metrics" / "new.layer_ms.py").write_text(
        "def read(run):\n    return None if run is None else 1.5\n")
    assert run.load_config("new-model", here=tmp_path)["model"]["d_model"] == 8
    assert traffic.load("burst", root=tmp_path)["loop"] == "closed"
    assert run.load_cell("new.burst", here=tmp_path)["limits"]["max_gap"] == 0.1
    read = run.load_metric("new.layer_ms", here=tmp_path)
    assert read(object()) == 1.5 and read(None) is None
