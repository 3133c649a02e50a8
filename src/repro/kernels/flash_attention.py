"""Grouped flash attention — Pallas TPU kernel.

The diagonal-batching hot spot: attention over a *group* of layer-slots
(paper §4.2 batches attention across the group dim to reach batch-scaling
FLOPs). Layout: q [N, Hq, T, hd], k/v [N, Hkv, S, hd] where N = group*batch.
GQA is handled by the BlockSpec index map (kv head = q head // rep) — no
materialized head repetition. Causal and sliding-window masks supported.

VMEM tiling: queries in [block_q, hd] tiles; K/V streamed in [block_k, hd]
tiles with online softmax (running max/sum), fp32 accumulators. hd is padded
to the 128-lane MXU width by the wrapper (ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, sm_scale: float,
                  causal: bool, window: int, block_k: int, kv_len: int,
                  skip_blocks: bool):
    # q_ref: [block_q, hd]; k_ref/v_ref: [kv_len, hd]; o_ref: [block_q, hd]
    block_q, hd = q_ref.shape
    start_q = pl.program_id(2) * block_q

    q = q_ref[...].astype(jnp.float32) * sm_scale
    m_i = jnp.full((block_q,), NEG_INF, jnp.float32)
    l_i = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, hd), jnp.float32)

    q_pos = start_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(ik, carry):
        m_i, l_i, acc = carry
        start_k = ik * block_k
        k = k_ref[pl.dslice(start_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.dslice(start_k, block_k), :].astype(jnp.float32)
        s = q @ k.T                                        # [bq, bk]
        k_pos = start_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > (q_pos - window)
            if not causal:
                # symmetric window: keys beyond qpos + window are masked
                # (causal mode already bounds above via k_pos <= q_pos)
                mask &= k_pos < (q_pos + window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_i, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + p.sum(axis=1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    n_k = pl.cdiv(kv_len, block_k)
    if causal and skip_blocks:
        # skip fully-masked k blocks beyond the diagonal
        n_k_eff = jnp.minimum(
            n_k, (start_q + block_q + block_k - 1) // block_k)
    elif window > 0 and not causal and skip_blocks:
        # symmetric-window upper bound: the latest key any query in this
        # block attends to is start_q + block_q - 1 + window - 1
        n_k_eff = jnp.minimum(
            n_k, (start_q + block_q + window - 2) // block_k + 1)
    else:
        n_k_eff = n_k
    if window > 0 and skip_blocks:
        # skip fully-masked k blocks below the sliding window: the earliest
        # key any query in this block attends to is start_q - window + 1
        k_start = jnp.maximum(0, (start_q - window + 1) // block_k)
    else:
        k_start = 0
    m_i, l_i, acc = jax.lax.fori_loop(k_start, n_k_eff, body, (m_i, l_i, acc))
    o_ref[...] = (acc / jnp.maximum(l_i, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret",
                     "skip_blocks"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False, skip_blocks: bool = True):
    """q: [N, Hq, T, hd]; k/v: [N, Hkv, S, hd] -> [N, Hq, T, hd].

    ``skip_blocks=False`` disables the causal / sliding-window block-skip
    bounds and visits every k tile, relying on the mask alone — the debug
    reference for the masked-vs-skipped equivalence test (the two must
    agree bitwise; a skipped block that wasn't fully masked would not)."""
    N, Hq, T, hd = q.shape
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0
    rep = Hq // Hkv
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    sm_scale = hd ** -0.5

    # zero-pad ragged T/S up to a block multiple: the last k tile would
    # otherwise be read through a clamped dslice (shifted data under the
    # unshifted k_pos mask); pad keys are masked via the real kv_len and
    # pad query rows are sliced off below
    T_pad = pl.cdiv(T, block_q) * block_q
    S_pad = pl.cdiv(S, block_k) * block_k
    if T_pad != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, T_pad - T), (0, 0)))
    if S_pad != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))

    grid = (N, Hq, T_pad // block_q)
    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, window=window,
        block_k=block_k, kv_len=S, skip_blocks=skip_blocks)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda n, h, iq: (n, h, iq, 0)),
            pl.BlockSpec((None, None, S_pad, hd),
                         lambda n, h, iq: (n, h // rep, 0, 0)),
            pl.BlockSpec((None, None, S_pad, hd),
                         lambda n, h, iq: (n, h // rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda n, h, iq: (n, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :T, :] if T_pad != T else out
