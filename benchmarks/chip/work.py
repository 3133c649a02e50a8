"""Operations and bytes of the work a cell asks for, from shapes alone.

Started from the program's ``roofline/model_math.model_flops`` (2 N per
token for inference) and extended to what an ARMT step computes: the
memory tokens of every full segment, causal attention scores, and the
associative memory's read (every position) and update (memory tokens of
every full segment). A count is of useful work: the same whatever
implements an op, and no padded or repeated cell is counted.

``m`` is a configuration file's ``model`` section.
"""
from __future__ import annotations

from typing import Dict

BF16 = 2


def _dims(m: Dict):
    a = m["armt"]
    D = m["d_model"]
    return dict(D=D, H=m["n_heads"], Hk=m["n_kv_heads"], hd=m["d_head"],
                F=m["d_ff"], V=m["vocab"], L=m["n_layers"],
                T=a["segment_len"], M=a["num_mem_tokens"], dm=a["d_mem"],
                P=2 * a["nu"] * a["d_mem"], Dv=a["d_val"] or D)


def proj_flops_per_row(m: Dict) -> float:
    """Q, K, V, O and the SwiGLU FFN for one row in one layer."""
    d = _dims(m)
    qkv = d["D"] * (d["H"] + 2 * d["Hk"]) * d["hd"]
    o = d["H"] * d["hd"] * d["D"]
    ffn = 3 * d["D"] * d["F"]
    return 2.0 * (qkv + o + ffn)


def read_flops_per_row(m: Dict) -> float:
    """Memory read (eq. 6): q = x W_Q, phi(q) A, phi(q) z."""
    d = _dims(m)
    return 2.0 * (d["D"] * d["dm"] + d["P"] * d["Dv"] + d["P"])


def update_flops_per_row(m: Dict) -> float:
    """Memory update (eqs. 3-5) per memory token: k, v, beta, vbar, the
    rank-one write into A and the z terms."""
    d = _dims(m)
    return 2.0 * (d["D"] * d["dm"] + d["D"] * d["Dv"] + d["D"]
                  + 2 * d["P"] * d["Dv"] + 3 * d["P"])


def attn_flops(m: Dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query, visible key) pairs, all heads."""
    d = _dims(m)
    return 4.0 * d["H"] * d["hd"] * pairs


def head_flops(m: Dict) -> float:
    d = _dims(m)
    return 2.0 * d["D"] * d["V"]


def segment_flops(m: Dict) -> float:
    """One full segment through every layer: its tokens and memory tokens,
    causal attention over them, read on every row, update."""
    d = _dims(m)
    R = d["T"] + d["M"]
    per_layer = (R * (proj_flops_per_row(m) + read_flops_per_row(m))
                 + attn_flops(m, R * (R + 1) / 2)
                 + d["M"] * update_flops_per_row(m))
    return d["L"] * per_layer


def tail_flops(m: Dict, n: int, start: int = 0) -> float:
    """``n`` tokens of a partial segment after ``start`` tokens of it: no
    memory tokens, no update."""
    d = _dims(m)
    pairs = n * start + n * (n + 1) / 2
    return d["L"] * (n * (proj_flops_per_row(m) + read_flops_per_row(m))
                     + attn_flops(m, pairs))


def prompt_flops(m: Dict, prompt_len: int) -> float:
    """A prompt's prefill: its full segments, its tail, and the logits of
    its last position."""
    T = _dims(m)["T"]
    s, t = divmod(prompt_len, T)
    return s * segment_flops(m) + tail_flops(m, t) + head_flops(m)


def decode_token_flops(m: Dict, pos: int) -> float:
    """One decode step of one slot whose token sits at in-segment position
    ``pos`` (it sees pos + 1 keys), with its logits."""
    return tail_flops(m, 1, pos) + head_flops(m)


def flush_flops(m: Dict) -> float:
    """One slot's segment flush: the memory tokens against the T cached
    keys and each other, read, update."""
    d = _dims(m)
    M, T = d["M"], d["T"]
    per_layer = (M * (proj_flops_per_row(m) + read_flops_per_row(m))
                 + attn_flops(m, M * T + M * (M + 1) / 2)
                 + M * update_flops_per_row(m))
    return d["L"] * per_layer


# ---------------------------------------------------------------------------
# Kernels of the fused diagonal prefill, per (segment, layer) cell
# ---------------------------------------------------------------------------

def _gmm(rows: int, din: int, dout: int, bias: bool):
    f = 2.0 * rows * din * dout
    b = BF16 * (rows * din + din * dout + rows * dout + (dout if bias else 0))
    return f, b


def grouped_matmul_cell(m: Dict):
    """(flops, bytes) of the plain grouped_matmul calls of one cell with a
    batch of one: Q, K, V (with their bias where the model has one), O,
    and the FFN's gate and up projections. The down projection rides the
    fused ARMT-update kernel and is not counted here."""
    d = _dims(m)
    R = d["T"] + d["M"]
    b = bool(m.get("qkv_bias"))
    calls = [(d["D"], d["H"] * d["hd"], b), (d["D"], d["Hk"] * d["hd"], b),
             (d["D"], d["Hk"] * d["hd"], b), (d["H"] * d["hd"], d["D"], False),
             (d["D"], d["F"], False), (d["D"], d["F"], False)]
    out = [_gmm(R, i, o, bb) for i, o, bb in calls]
    return sum(f for f, _ in out), sum(b for _, b in out)


def flash_attention_cell(m: Dict):
    """(flops, bytes) of one cell's causal flash attention: the visible
    (query, key) pairs only; q, k, v read and o written once."""
    d = _dims(m)
    R = d["T"] + d["M"]
    f = attn_flops(m, R * (R + 1) / 2)
    b = BF16 * R * (2 * d["H"] + 2 * d["Hk"]) * d["hd"]
    return f, b


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
