"""Attention: GQA/MHA, sliding-window, cross-attention, KV caches.

The jnp path here is the reference the Pallas flash kernel (kernels/) is
validated against; the model can route the segment-attention hot spot through
the kernel via ``use_kernel`` (TPU) while CPU tests keep the jnp path.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import (apply_rope, rope_cos_sin,
                                 rope_cos_sin_cached, rmsnorm)

NEG_INF = -1e30


def attn_param_init(key, cfg, dtype, *, cross: bool = False) -> Dict:
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, nq * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, nkv * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, nkv * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (nq * hd, d)) * (nq * hd) ** -0.5).astype(dtype),
    }
    if cfg.qkv_bias or cfg.norm == "layernorm":   # whisper/chatglm/qwen biases
        p["bq"] = jnp.zeros((nq * hd,), dtype)
        p["bk"] = jnp.zeros((nkv * hd,), dtype)
        p["bv"] = jnp.zeros((nkv * hd,), dtype)
    if cfg.qk_norm:
        p["qn"] = {"w": jnp.ones((hd,), dtype)}
        p["kn"] = {"w": jnp.ones((hd,), dtype)}
    return p


def _project_qkv(x, p, cfg):
    B, T, _ = x.shape
    hd = cfg.head_dim
    q = jnp.einsum("btd,de->bte", x, p["wq"]) + p.get("bq", 0)
    k = jnp.einsum("btd,de->bte", x, p["wk"]) + p.get("bk", 0)
    v = jnp.einsum("btd,de->bte", x, p["wv"]) + p.get("bv", 0)
    q = q.reshape(B, T, cfg.n_heads, hd)
    k = k.reshape(B, T, cfg.n_kv_heads, hd)
    v = v.reshape(B, T, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["qn"])
        k = rmsnorm(k, p["kn"])
    return q, k, v


def rope_qk(q, k, cfg, positions=None, *, cached_tables: bool = False):
    """Apply RoPE to q/k [..., T, H, hd] from one shared cos/sin table.
    Used by both the reference attention path and the fused grouped-block
    path (models/grouped_blocks.py) so the rotary math is bit-identical.

    cached_tables: with segment-local positions, take the cos/sin table
    from the eager per-shape cache (rope_cos_sin_cached) so it embeds as
    one on-device constant shared by every compiled step body — what the
    banded diagonal driver's single-step phase programs need. The values
    are bitwise-identical, but a constant table changes XLA's fusion
    choices, which perturbs ulps elsewhere in the program — so the flag
    stays off on the reference/training paths to keep their compiled
    programs exactly as before (the fused path re-verifies equivalence
    against them at fp32 tolerance, tests/test_grouped_blocks.py)."""
    if not cfg.use_rope:
        return q, k
    d_rot = int(cfg.head_dim * cfg.rope_fraction)
    if positions is None and cached_tables:
        cos, sin = rope_cos_sin_cached(q.shape[-3], d_rot - d_rot % 2,
                                       cfg.rope_theta)
    else:
        if positions is None:
            positions = jnp.arange(q.shape[-3])[None]
        cos, sin = rope_cos_sin(positions, d_rot - d_rot % 2, cfg.rope_theta)
    return (apply_rope(q, cos, sin, cfg.rope_fraction),
            apply_rope(k, cos, sin, cfg.rope_fraction))


def sdpa(q, k, v, mask=None) -> jax.Array:
    """q: [B,T,Hq,hd], k/v: [B,S,Hkv,hd] (GQA expanded by repeat), fp32 softmax."""
    B, T, Hq, hd = q.shape
    Hkv = k.shape[2]
    if Hq != Hkv:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = hd ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bshd->bthd", w, v)


def sdpa_chunked(q, k, v, *, causal: bool = True, window: int = 0,
                 block: int = 512) -> jax.Array:
    """Flash-style attention in pure jnp: scan over key blocks with an
    online softmax — no [T, S] score tensor is ever materialized (the HLO
    mirror of kernels/flash_attention.py; used by the roofline cells)."""
    B, T, Hq, hd = q.shape
    S = k.shape[1]
    Hkv = k.shape[2]
    if Hq != Hkv:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    block = min(block, S)
    n_blk = (S + block - 1) // block
    pad = n_blk * block - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = hd ** -0.5
    q32 = q.astype(jnp.float32) * scale
    kb = k.reshape(B, n_blk, block, Hq, hd).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(B, n_blk, block, Hq, hd).transpose(1, 0, 3, 2, 4)
    qpos = jnp.arange(T)[:, None]

    def step(carry, inp):
        m_i, l_i, acc = carry
        kc, vc, ib = inp                      # [B,H,block,hd] x2, scalar
        s = jnp.einsum("bthd,bhsd->bhts", q32, kc.astype(jnp.float32))
        kpos = (ib * block + jnp.arange(block))[None, :]
        mask = kpos < S
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > (qpos - window)
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m_i, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhts,bhsd->bhtd", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, Hq, T), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hq, T), jnp.float32)
    a0 = jnp.zeros((B, Hq, T, hd), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                      (kb, vb, jnp.arange(n_blk)))
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]      # [B,H,T,hd]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def causal_mask(T: int, S: int, *, offset: int = 0,
                window: int = 0) -> jax.Array:
    """[1,1,T,S] boolean; query t attends key s iff s <= t+offset
    (and within sliding window if window>0)."""
    qpos = jnp.arange(T)[:, None] + offset
    kpos = jnp.arange(S)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > (qpos - window)
    return m[None, None]


def attention(x, p, cfg, *, positions=None, mask=None, bidirectional=False):
    """Self-attention over x [B,T,D] (full segment/sequence, no cache)."""
    B, T, _ = x.shape
    q, k, v = _project_qkv(x, p, cfg)
    q, k = rope_qk(q, k, cfg, positions)
    impl = getattr(cfg, "attn_impl", "dense")
    if impl == "chunked":
        o = sdpa_chunked(q, k, v, causal=not bidirectional,
                         window=cfg.sliding_window)
    elif impl == "pallas":
        # the TPU flash kernel (kernels/flash_attention.py); interpret mode
        # (the kernel body on CPU, for validation) only where the caller
        # asked for it with kernel_backend='pallas_interpret'
        from repro.kernels import ops as kops
        o = kops.segment_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
            causal=not bidirectional, window=cfg.sliding_window,
            use_kernel=True,
            interpret=cfg.kernel_backend == "pallas_interpret").swapaxes(1, 2)
    else:
        if mask is None and not bidirectional:
            mask = causal_mask(T, T, window=cfg.sliding_window)
        o = sdpa(q, k, v, mask)
    o = o.reshape(B, T, cfg.n_heads * cfg.head_dim)
    return jnp.einsum("bte,ed->btd", o, p["wo"])


def cross_attention(x, p, ck, cv, cfg):
    """x: [B,T,D]; ck/cv: precomputed encoder K/V [B,F,Hkv,hd]."""
    B, T, _ = x.shape
    q = jnp.einsum("btd,de->bte", x, p["wq"]) + p.get("bq", 0)
    q = q.reshape(B, T, cfg.n_heads, cfg.head_dim)
    o = sdpa(q, ck, cv, None)
    o = o.reshape(B, T, cfg.n_heads * cfg.head_dim)
    return jnp.einsum("bte,ed->btd", o, p["wo"])


def cross_kv(enc_out, p, cfg):
    """Precompute cross-attention K/V from encoder output [B,F,D]."""
    B, F, _ = enc_out.shape
    k = (jnp.einsum("bfd,de->bfe", enc_out, p["wk"]) + p.get("bk", 0))
    v = (jnp.einsum("bfd,de->bfe", enc_out, p["wv"]) + p.get("bv", 0))
    return (k.reshape(B, F, cfg.n_kv_heads, cfg.head_dim),
            v.reshape(B, F, cfg.n_kv_heads, cfg.head_dim))


# ---------------------------------------------------------------------------
# Decode (single token against a cache)
# ---------------------------------------------------------------------------

def kv_cache_init(batch: int, max_len: int, cfg, dtype) -> Dict:
    hd = cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
    }


def _decode_positions(pos, Tq: int) -> jax.Array:
    """Positions of Tq decode rows starting at ``pos``: [B, Tq] for a
    per-row int32 [B] ``pos``, [1, Tq] for a scalar."""
    if getattr(pos, "ndim", 0) == 1:
        return pos[:, None] + jnp.arange(Tq)[None, :]
    return (pos + jnp.arange(Tq))[None]


def decode_qkv(x, p, cfg, pos):
    """q, k, v of Tq >= 1 decode rows (Tq=1: autoregressive decode; Tq>1:
    chunked prefill / ARMT memory-token flush), rotated to their positions.
    x: [B,Tq,D]; pos: scalar int32 = number of tokens already in the cache,
    or int32 [B] vector of per-row positions (continuous-batching slots at
    heterogeneous phases). The caller writes k, v into its cache at pos
    and attends with ``decode_attend``."""
    q, k, v = _project_qkv(x, p, cfg)
    q, k = rope_qk(q, k, cfg, _decode_positions(pos, x.shape[1]))
    return q, k, v


def decode_attend(q, ck, cv, p, cfg, pos):
    """Tq queries at ``pos`` against a cache [B,S,Hkv,hd] that already
    holds their own rows; causal (and windowed) per row. -> [B,Tq,D]."""
    B, Tq = q.shape[:2]
    qpos = _decode_positions(pos, Tq)[:, :, None]                 # [.,Tq,1]
    kpos = jnp.arange(ck.shape[1])[None, None, :]                  # [1,1,S]
    mask = kpos <= qpos
    if cfg.sliding_window > 0:
        mask &= kpos > (qpos - cfg.sliding_window)
    if getattr(cfg, "attn_impl", "dense") == "pallas" and Tq == 1:
        # single-token serve hot path: the dedicated decode kernel
        # (kernels/decode_attention.py) reads only the valid cache prefix
        from repro.kernels import ops as kops
        lens = jnp.broadcast_to(pos, (B,)).astype(jnp.int32) + 1
        o = kops.decode_attention(
            q[:, 0], ck, cv, lens, window=cfg.sliding_window,
            use_kernel=True,
            interpret=cfg.kernel_backend == "pallas_interpret")[:, None]
    else:
        # sdpa's math with GQA as a grouped contraction: query head h reads
        # KV head h // g straight from the cache, which is never repeated
        # to n_heads (that would write and read it g times over a step)
        Hkv, hd = ck.shape[2], ck.shape[3]
        g = cfg.n_heads // Hkv
        logits = jnp.einsum("btkgd,bskd->bkgts",
                            q.reshape(B, Tq, Hkv, g, hd), ck)
        logits = jnp.where(mask[:, None, None],
                           logits.astype(jnp.float32) * hd ** -0.5, NEG_INF)
        w = jax.nn.softmax(logits, axis=-1).astype(cv.dtype)
        o = jnp.einsum("bkgts,bskd->btkgd", w, cv)
    o = o.reshape(B, Tq, cfg.n_heads * cfg.head_dim)
    return jnp.einsum("bte,ed->btd", o, p["wo"])
