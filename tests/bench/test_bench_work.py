"""Operation and byte counts of the benchmark, against hand counts for one
(segment, layer) cell of each configuration."""
import json
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import work  # noqa: E402


def model(name):
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())["model"]


def test_danube_cell_by_hand():
    m = model("h2o-danube-1.8b-armt")
    R = 1024 + 128
    # Q 2560x2560, K and V 2560x640, O 2560x2560, gate/up/down 2560x6912
    proj = 2 * (2560 * 2560 + 2 * 2560 * 640 + 2560 * 2560 + 3 * 2560 * 6912)
    assert work.proj_flops_per_row(m) == proj
    f, b = work.grouped_matmul_cell(m)
    # the down projection rides the ARMT-update kernel
    assert f == 2 * R * (2560 * 2560 + 2 * 2560 * 640 + 2560 * 2560
                         + 2 * 2560 * 6912)
    gmm_bytes = 2 * (R * 2560 + 2560 * 2560 + R * 2560
                     + 2 * (R * 2560 + 2560 * 640 + R * 640)
                     + R * 2560 + 2560 * 2560 + R * 2560
                     + 2 * (R * 2560 + 2560 * 6912 + R * 6912))
    assert b == gmm_bytes
    fa, ba = work.flash_attention_cell(m)
    assert fa == 4 * 32 * 80 * R * (R + 1) / 2
    assert ba == 2 * R * (2 * 32 + 2 * 8) * 80
    # one segment: R rows x 24 layers, plus attention, read and update
    read = 2 * (2560 * 64 + 384 * 2560 + 384)
    upd = 2 * (2560 * 64 + 2560 * 2560 + 2560 + 2 * 384 * 2560 + 3 * 384)
    seg = 24 * (R * (proj + read) + fa + 128 * upd)
    assert work.segment_flops(m) == pytest.approx(seg, rel=1e-12)
    # about 3.9 GFLOP per prompt token with its memory tokens
    assert 3.7e9 < work.segment_flops(m) / 1024 < 4.1e9


def test_glm_cell_by_hand():
    m = model("chatglm3-6b-armt-14l")
    R = 1152
    f, b = work.grouped_matmul_cell(m)
    assert f == 2 * R * (4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096
                         + 2 * 4096 * 13696)
    # the QKV bias rows are read once per call
    assert b == 2 * (R * 4096 + 4096 * 4096 + R * 4096 + 4096
                     + 2 * (R * 4096 + 4096 * 256 + R * 256 + 256)
                     + R * 4096 + 4096 * 4096 + R * 4096
                     + 2 * (R * 4096 + 4096 * 13696 + R * 13696))
    fa, _ = work.flash_attention_cell(m)
    assert fa == 4 * 32 * 128 * R * (R + 1) / 2


def test_prompt_and_decode_counts():
    m = model("h2o-danube-1.8b-armt")
    p = 32 * 1024 + 512
    assert work.prompt_flops(m, p) == pytest.approx(
        32 * work.segment_flops(m) + work.tail_flops(m, 512)
        + work.head_flops(m))
    d0 = work.decode_token_flops(m, 0)
    d9 = work.decode_token_flops(m, 9)
    assert d9 - d0 == pytest.approx(24 * work.attn_flops(m, 9))
    assert work.head_flops(m) == 2 * 2560 * 32000
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.roofline_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    assert work.roofline_seconds(1.0, 819e9, peak) == pytest.approx(1.0)
