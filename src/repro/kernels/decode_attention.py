"""Single-token decode attention — Pallas TPU kernel (the serve hot path).

One query token per row against a paged-in KV cache prefix: q [B, Hq, hd]
vs. cache k/v [B, S, Hkv, hd] (the serve-stack cache layout) with per-row
valid lengths [B]. Grid (B, Hkv, S / block_k): each program takes the
``rep = Hq // Hkv`` query heads that share one kv head (GQA) as a [rep, hd]
tile, and online softmax streams that head's cache in [block_k, hd] tiles,
accumulating in VMEM scratch across the last grid axis.

Every block's last two dims are a legal TPU tile: q and the output ride as
[B, Hkv, rep, hd] (rep is the whole dim), the cache as [B, S, Hkv * hd]
(a [block_k, hd] block at lane offset h * hd; ops.py pads hd to 128), and
the lengths sit in scalar memory (scalar prefetch). The k/v index maps clamp
the time block into the row's visible range, so a tile past the valid
prefix (or below the sliding window) is neither fetched again nor computed:
a 32-token-deep slot in a 64k-slot cache reads one tile, not 512.

Sliding window masks keys below ``qpos - window`` (qpos = length - 1) —
decode is causal by construction, so there is no upper bound to mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _visible_blocks(length, window: int, block_k: int):
    """[lo, hi) range of time blocks holding a key the query can see."""
    lo = jnp.maximum(0, (length - window) // block_k) if window > 0 else 0
    return lo, pl.cdiv(length, block_k)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, sm_scale: float, window: int, block_k: int):
    # len: [B] (SMEM); q/o: [rep, hd]; k/v: [block_k, hd];
    # scratch m/l: [rep, 1], acc: [rep, hd] (fp32, live across time blocks)
    ik = pl.program_id(2)
    length = len_ref[pl.program_id(0)]
    lo, hi = _visible_blocks(length, window, block_k)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((ik >= lo) & (ik < hi))
    def _step():
        q = q_ref[...].astype(jnp.float32) * sm_scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [rep, bk]
        k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < length
        if window > 0:
            mask &= k_pos > (length - 1 - window)
        s = jnp.where(mask, s, NEG_INF)
        m_i = m_ref[...]
        m_new = jnp.maximum(m_i, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_i - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_new

    @pl.when(ik == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention(q, k, v, lengths, *, window: int = 0,
                     block_k: int = 128, interpret: bool = False):
    """q: [B, Hq, hd]; k/v: [B, S, Hkv, hd] (cache layout); lengths: [B]
    int32 (valid prefix incl. the current token) -> [B, Hq, hd]."""
    B, Hq, hd = q.shape
    _, S, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    rep = Hq // Hkv
    block_k = min(block_k, S)
    sm_scale = hd ** -0.5
    lengths = lengths.astype(jnp.int32).reshape(B)

    # zero-pad a ragged cache length so the last tile is a whole block;
    # pad keys are masked via the per-row length
    S_pad = pl.cdiv(S, block_k) * block_k
    if S_pad != S:
        k = jnp.pad(k, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    k = k.reshape(B, S_pad, Hkv * hd)
    v = v.reshape(B, S_pad, Hkv * hd)
    q = q.reshape(B, Hkv, rep, hd)

    def kv_index(b, h, ik, lens):
        lo, hi = _visible_blocks(lens[b], window, block_k)
        return b, jnp.clip(ik, lo, jnp.maximum(hi - 1, lo)), h

    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               window=window, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, S_pad // block_k),
            in_specs=[
                pl.BlockSpec((None, None, rep, hd),
                             lambda b, h, ik, lens: (b, h, 0, 0)),
                pl.BlockSpec((None, block_k, hd), kv_index),
                pl.BlockSpec((None, block_k, hd), kv_index),
            ],
            out_specs=pl.BlockSpec((None, None, rep, hd),
                                   lambda b, h, ik, lens: (b, h, 0, 0)),
            scratch_shapes=[pltpu.VMEM((rep, 1), jnp.float32),
                            pltpu.VMEM((rep, 1), jnp.float32),
                            pltpu.VMEM((rep, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, hd), q.dtype),
        name="decode_attention",
        interpret=interpret,
    )(lengths, q, k, v)
    return out.reshape(B, Hq, hd)
