"""Unified config-driven LM with three execution modes:

  * full       — plain transformer forward (the paper's Llama baseline)
  * segmented  — PRMT/ARMT recurrence, sequential schedule (paper baseline ARMT)
  * segmented + diagonal schedule — the paper's contribution

plus a serving path (`decode_step`) that runs one token against carried state:
'cache' mode (full KV cache — standard decoding) or 'armt' mode (associative
memory + current-segment cache — constant memory in sequence length).

Decode has its own layer loop (`_decode_layers`): a scan over the stacked
layer params that updates the pooled state in place, so the HLO stays
scan-compact for 61-72-layer archs and a step writes only what it changes.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.core.memory import mem_read, mem_update
from repro.core.schedule import StackLayout
from repro.core.sequential import run_sequential
from repro.core.diagonal import boundary_states_from_capture, run_diagonal
from repro.models.attention import (cross_attention, cross_kv, decode_attend,
                                    decode_qkv)
from repro.models.blocks import (block_param_init, block_state_init,
                                 make_apply_block, _is_attn)
from repro.models.layers import ffn, norm, norm_init
from repro.models.mamba import mamba_mixer
from repro.models.moe import moe_ffn


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_params(cfg: ArchConfig, key: jax.Array, dtype=None) -> Dict:
    dtype = jnp.dtype(dtype or cfg.dtype)
    keys = jax.random.split(key, 16)
    layout = StackLayout.from_config(cfg)
    params: Dict = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab, cfg.d_model))
                  * 0.02).astype(dtype),
        "final_norm": norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(keys[1], (cfg.d_model, cfg.vocab))
                          * cfg.d_model ** -0.5).astype(dtype)
    if cfg.armt is not None and cfg.armt.num_mem_tokens > 0:
        params["mem_tokens"] = (jax.random.normal(
            keys[2], (cfg.armt.num_mem_tokens, cfg.d_model)) * 0.02).astype(dtype)
    if not cfg.use_rope and cfg.encoder is not None:
        params["pos_embed"] = (jax.random.normal(
            keys[3], (cfg.max_position, cfg.d_model)) * 0.02).astype(dtype)

    prelude = []
    for j, t in enumerate(layout.prelude):
        prelude.append(block_param_init(jax.random.fold_in(keys[4], j), t, cfg,
                                        dtype, prelude=True))
    params["prelude"] = tuple(prelude)

    pattern = []
    for p_i, t in enumerate(layout.pattern):
        sub = jax.random.split(jax.random.fold_in(keys[5], p_i), layout.n_super)
        stacked = jax.vmap(
            lambda k, _t=t: block_param_init(k, _t, cfg, dtype))(sub)
        pattern.append(stacked)
    params["pattern"] = tuple(pattern)

    if cfg.encoder is not None:
        ek = jax.random.split(keys[6], cfg.encoder.n_layers)
        params["enc"] = {
            "blocks": jax.vmap(
                lambda k: block_param_init(k, "enc", cfg, dtype))(ek),
            "final_norm": norm_init(cfg.norm, cfg.d_model, dtype),
            "pos": (jax.random.normal(keys[7], (cfg.encoder.n_frames,
                                                cfg.d_model)) * 0.02).astype(dtype),
        }
    return params


def param_specs(cfg: ArchConfig, dtype=None):
    """Shape/dtype tree without allocation (for dry-runs of 1T-param archs)."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k, dtype), jax.random.PRNGKey(0))


def decode_state_shapes(cfg: ArchConfig, batch: int, *, serve_mode: str,
                        max_len: int, dtype, per_slot_pos: bool = False):
    """Shape/dtype tree of a decode state without allocation — the
    serve-side sibling of ``param_specs`` above."""
    return jax.eval_shape(
        lambda: decode_state_init(cfg, batch, serve_mode=serve_mode,
                                  max_len=max_len, dtype=dtype,
                                  per_slot_pos=per_slot_pos))


def decode_state_sharding(cfg: ArchConfig, mesh, batch: int, *,
                          serve_mode: str, max_len: int, dtype,
                          per_slot_pos: bool = False,
                          stacked_axis: Optional[str] = None):
    """NamedSharding tree for a decode state on ``mesh``: slots/batch over
    the DP axes, heads/d_model over 'model', pattern-stacked leaves
    optionally over ``stacked_axis`` — the placement the mesh-native serve
    stack (DESIGN.md §10) derives its pools, transplants, and snapshot
    restores from."""
    from repro.parallel import sharding as shd
    shapes = decode_state_shapes(cfg, batch, serve_mode=serve_mode,
                                 max_len=max_len, dtype=dtype,
                                 per_slot_pos=per_slot_pos)
    return shd.decode_state_specs(shapes, mesh, batch,
                                  stacked_axis=stacked_axis)


def init_state(cfg: ArchConfig, batch: int, mode: str, dtype) -> Dict:
    layout = StackLayout.from_config(cfg)
    state: Dict = {"prelude": tuple(
        block_state_init(t, cfg, batch, mode, dtype) for t in layout.prelude)}
    pattern = []
    for t in layout.pattern:
        st = block_state_init(t, cfg, batch, mode, dtype)
        pattern.append(jax.tree_util.tree_map(
            lambda a: jnp.zeros((layout.n_super,) + a.shape, a.dtype), st))
    state["pattern"] = tuple(pattern)
    return state


# forward_hidden takes an `init_state` *argument* (resume from a carried
# state) which shadows the function above inside its body — alias it.
_init_exec_state = init_state


# ---------------------------------------------------------------------------
# Encoder (whisper) — frontend is a stub: callers pass frame *embeddings*
# ---------------------------------------------------------------------------

def encode(params: Dict, cfg: ArchConfig, frames: jax.Array) -> jax.Array:
    """frames: [B, F, d_model] precomputed frame embeddings -> [B, F, d_model]."""
    x = frames + params["enc"]["pos"][None, :frames.shape[1]].astype(frames.dtype)
    apply = make_apply_block(cfg, mode="full")

    def step(h, blk_p):
        y, _ = apply("enc", blk_p, h, {})
        return y, None

    x, _ = jax.lax.scan(step, x, params["enc"]["blocks"])
    return norm(cfg.norm, x, params["enc"]["final_norm"])


def _fill_cross_kv(params: Dict, cfg: ArchConfig, state: Dict,
                   enc_out: jax.Array) -> Dict:
    """Compute per-decoder-layer cross K/V from encoder output into state."""
    new_pattern = []
    for p_i, t in enumerate(tuple(cfg.block_pattern)):
        st = state["pattern"][p_i]
        if t == "dec":
            ck, cv = jax.vmap(
                lambda xp: cross_kv(enc_out, xp, cfg))(params["pattern"][p_i]["xattn"])
            st = dict(st)
            st["ck"], st["cv"] = ck, cv
        new_pattern.append(st)
    return {"prelude": state["prelude"], "pattern": tuple(new_pattern)}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _resolve_seg_len(cfg: ArchConfig, seg_len: Optional[int],
                     total: Optional[int] = None) -> int:
    if not seg_len:
        seg_len = cfg.armt.segment_len if cfg.armt is not None else 1024
    if total is not None:
        seg_len = min(seg_len, total)
    return seg_len


def embed_segments(params: Dict, cfg: ArchConfig, tokens: jax.Array,
                   seg_len: int, with_mem: bool) -> jax.Array:
    """tokens: [B, S_total] -> [n_seg, B, seg_len (+M), D]."""
    B, total = tokens.shape
    assert total % seg_len == 0, (total, seg_len)
    S = total // seg_len
    segs = tokens.reshape(B, S, seg_len).transpose(1, 0, 2)      # [S,B,T]
    x = params["embed"][segs]                                     # [S,B,T,D]
    if with_mem and "mem_tokens" in params:
        M = params["mem_tokens"].shape[0]
        mem = jnp.broadcast_to(params["mem_tokens"][None, None],
                               (S, B, M, x.shape[-1]))
        x = jnp.concatenate([x, mem], axis=2)
    if "pos_embed" in params:
        x = x + params["pos_embed"][None, None, :x.shape[2]].astype(x.dtype)
    return x


def forward_hidden(params: Dict, cfg: ArchConfig, tokens: jax.Array, *,
                   schedule: str = "diagonal", mode: str = "segmented",
                   seg_len: Optional[int] = None,
                   enc_frames: Optional[jax.Array] = None,
                   ssm_method: str = "assoc",
                   slot_spec=None,
                   grouped_impl: Optional[str] = None,
                   init_state: Optional[Dict] = None,
                   capture_states: bool = False):
    """Returns (hidden [S, B, T, D] — memory-token positions stripped,
    final executor state); with capture_states=True a third output holds
    the recurrent state at every segment boundary (leaves lead with [S];
    boundary c at index c-1) — the capture path for the serving state
    store (serve/state_store.py).

    init_state: resume the executor from a carried state instead of zeros —
    a prefix-cache snapshot or the final state of an earlier forward over a
    prefix of the same stream. The recurrence is layer-local, so splitting
    one long token stream into several forward_hidden calls with the state
    threaded through is exact (per-(layer, segment) applications see
    identical inputs in identical order).

    grouped_impl: 'vmap' | 'fused' override of cfg.grouped_impl — 'fused'
    routes the diagonal executor's per-step grouped launch through the
    Pallas grouped kernels (models/grouped_blocks.py); only meaningful for
    schedule='diagonal'."""
    B = tokens.shape[0]
    dtype = params["embed"].dtype
    if mode == "full":
        seg_len = tokens.shape[1]
        with_mem = False
    else:
        seg_len = _resolve_seg_len(cfg, seg_len, tokens.shape[1])
        with_mem = cfg.armt is not None and cfg.armt.num_mem_tokens > 0

    x = embed_segments(params, cfg, tokens, seg_len, with_mem)
    layout = StackLayout.from_config(cfg)
    if schedule == "auto":
        # Paper Table 9: diagonal wins once the grid is deep in segments; fall
        # back to sequential when the diagonal would be mostly fill/drain.
        schedule = "diagonal" if x.shape[0] >= layout.n_layers else "sequential"
    if init_state is not None:
        state0 = init_state
    else:
        state0 = _init_exec_state(cfg, B, mode, dtype)
        if cfg.encoder is not None:
            assert enc_frames is not None, \
                "whisper needs enc_frames (stub frontend)"
            enc_out = encode(params, cfg, enc_frames)
            state0 = _fill_cross_kv(params, cfg, state0, enc_out)

    block_mode = mode if mode == "full" else "segmented"
    apply = make_apply_block(cfg, mode=block_mode, ssm_method=ssm_method)
    exec_params = {"prelude": params["prelude"], "pattern": params["pattern"]}
    kw = {"remat": cfg.remat != "none", "capture_states": capture_states}
    if schedule == "diagonal":
        run = run_diagonal
        kw["buf_spec"] = slot_spec
        from repro.models.grouped_blocks import resolve_grouped_apply
        ga = resolve_grouped_apply(cfg, grouped_impl, mode=block_mode,
                                   ssm_method=ssm_method,
                                   remat=cfg.remat != "none")
        if ga is not None:
            kw["grouped_apply"] = ga
    else:
        run = run_sequential
    if capture_states:
        ys, fin, captured = run(layout, exec_params, state0, x, apply, **kw)
        if schedule == "diagonal":
            captured = boundary_states_from_capture(layout, captured,
                                                    x.shape[0])
        hidden = ys[:, :, :seg_len] if with_mem else ys
        return hidden, fin, captured
    ys, fin = run(layout, exec_params, state0, x, apply, **kw)
    hidden = ys[:, :, :seg_len] if with_mem else ys
    return hidden, fin


def _head_matmul(params: Dict, cfg: ArchConfig, h: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", h, params["embed"])
    return jnp.einsum("...d,dv->...v", h, params["head"])


def lm_loss(params: Dict, cfg: ArchConfig, tokens: jax.Array,
            labels: jax.Array, *, schedule: str = "diagonal",
            mode: str = "segmented", seg_len: Optional[int] = None,
            loss_mask: Optional[jax.Array] = None,
            enc_frames: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token NLL. Logits are never materialized for the whole
    sequence — CE is computed per segment inside a scan (DESIGN.md §6.1)."""
    hidden, _ = forward_hidden(params, cfg, tokens, schedule=schedule,
                               mode=mode, seg_len=seg_len,
                               enc_frames=enc_frames)
    S, B, T, D = hidden.shape
    labels_seg = labels.reshape(B, S, T).transpose(1, 0, 2)
    if loss_mask is None:
        mask_seg = jnp.ones((S, B, T), jnp.float32)
    else:
        mask_seg = loss_mask.reshape(B, S, T).transpose(1, 0, 2).astype(jnp.float32)

    # chunk tokens inside each segment too: fp32 logits for a [B, T, V]
    # block of e.g. qwen2.5 (T=1024, V=152k) would be ~10 GB — chunked CE
    # keeps the transient at B*chunk*V (DESIGN.md §6.1)
    chunk = 256
    n_chunks = T // chunk if (T % chunk == 0 and T > chunk) else 1
    Tc = T // n_chunks

    def _chunked(a):
        # [S, B, T, ...] -> [S*n, B, T/n, ...]
        a = a.reshape((S, B, n_chunks, Tc) + a.shape[3:])
        return a.swapaxes(1, 2).reshape((S * n_chunks, B, Tc) + a.shape[4:])

    hidden_c = _chunked(hidden)
    labels_c = _chunked(labels_seg)
    mask_c = _chunked(mask_seg)

    def ce_step(acc, inp):
        h, y, m = inp
        hn = norm(cfg.norm, h, params["final_norm"])
        logits = _head_matmul(params, cfg, hn).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * m
        return (acc[0] + nll.sum(), acc[1] + m.sum()), None

    (tot, cnt), _ = jax.lax.scan(ce_step, (jnp.float32(0), jnp.float32(0)),
                                 (hidden_c, labels_c, mask_c))
    return tot / jnp.maximum(cnt, 1.0)


def last_logits(params: Dict, cfg: ArchConfig, hidden: jax.Array) -> jax.Array:
    """Logits of the final position of the final segment. hidden: [S,B,T,D]."""
    h = norm(cfg.norm, hidden[-1, :, -1], params["final_norm"])
    return _head_matmul(params, cfg, h).astype(jnp.float32)


def boundary_logits(params: Dict, cfg: ArchConfig,
                    hidden: jax.Array) -> jax.Array:
    """Logits of the last real-token position of *every* segment:
    hidden [S, B, T, D] -> [S, B, V] fp32. Stored alongside segment-boundary
    snapshots so an exact full-prefix cache hit needs no forward at all."""
    h = norm(cfg.norm, hidden[:, :, -1], params["final_norm"])
    return _head_matmul(params, cfg, h).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Decode / serving
# ---------------------------------------------------------------------------

def decode_state_init(cfg: ArchConfig, batch: int, *, serve_mode: str,
                      max_len: int, dtype, per_slot_pos: bool = False) -> Dict:
    """Per-layer decode state. serve_mode 'cache': full KV cache of max_len.
    serve_mode 'armt': associative memory + current-segment cache.

    per_slot_pos: position as an int32 [batch] vector instead of a scalar —
    each batch row (decode slot) tracks its own in-segment position, so a
    continuous-batching scheduler can pack requests at heterogeneous segment
    phases into one state (serve/scheduler.py)."""
    layout = StackLayout.from_config(cfg)
    hd = cfg.head_dim if cfg.n_heads > 0 else 0
    kv = max(cfg.n_kv_heads, 1)

    def one(t: str) -> Dict:
        st = block_state_init(t, cfg, batch,
                              "segmented" if serve_mode == "armt" else "full",
                              dtype)
        if _is_attn(t) and t != "enc":
            if serve_mode == "armt":
                cache_len = (cfg.armt.segment_len + cfg.armt.num_mem_tokens
                             if cfg.armt else max_len)
            else:
                cache_len = max_len
                st.pop("A", None), st.pop("z", None)
            st["k"] = jnp.zeros((batch, cache_len, kv, hd), dtype)
            st["v"] = jnp.zeros((batch, cache_len, kv, hd), dtype)
        return st

    state = {"prelude": tuple(one(t) for t in layout.prelude)}
    pattern = []
    for t in layout.pattern:
        st = one(t)
        pattern.append(jax.tree_util.tree_map(
            lambda a: jnp.zeros((layout.n_super,) + a.shape, a.dtype), st))
    state["pattern"] = tuple(pattern)
    # position (global or in-segment); [batch] when per-slot
    state["pos"] = jnp.zeros((batch,) if per_slot_pos else (), jnp.int32)
    return state


def _pos_embed_slice(table: jax.Array, pos: jax.Array, T: int) -> jax.Array:
    """Slice T rows of a learned position table starting at ``pos`` (scalar)
    or per-row at ``pos[b]`` (vector) -> [1 or B, T, D]."""
    if getattr(pos, "ndim", 0) == 1:
        return jax.vmap(lambda p: jax.lax.dynamic_slice_in_dim(
            table, p, T, axis=0))(pos)
    return jax.lax.dynamic_slice_in_dim(table, pos, T, axis=0)[None]


def _write_rows(leaf: jax.Array, i, rows: jax.Array, pos: jax.Array,
               keep: Optional[jax.Array]) -> jax.Array:
    """Write ``rows`` [B, Tq, ...] at in-segment ``pos`` (scalar, or int32
    [B] per row) into layer ``i`` of a stacked cache leaf [n_super, B, S,
    ...] (``i`` None: a prelude leaf [B, S, ...]), in place; needs pos +
    Tq <= S. Rows where ``keep`` is False are written their current values
    back, so they stay bit-exactly as they were.

    The write rewrites the whole 128-position block around the Tq
    positions (one in-place ``dynamic_update_slice`` per row, or one for
    all rows at a scalar pos), the other positions with their own values.
    On a TPU the cache lives with its position axis minor, which is how
    decode attention reads it; whole 128-lane blocks keep it there, where
    Tq bare rows make XLA relayout the whole cache around the loop."""
    B, Tq = rows.shape[:2]
    S = leaf.shape[-(rows.ndim - 1)]
    W = min(S, -(-(Tq + 127) // 128) * 128)
    lead = () if i is None else (i,)
    rest, tail = rows.shape[2:], (1,) * (rows.ndim - 2)
    zero = jnp.zeros((B, W) + rest, rows.dtype)
    groups = ([(0, B, pos)] if pos.ndim == 0
              else [(b, 1, pos[b]) for b in range(B)])
    for b, nb, p0 in groups:
        start = jnp.clip(p0 // 128 * 128, 0, S - W)
        at = lead + (b, start) + (0,) * len(tail)
        shape = (1,) * len(lead) + (nb, W) + rest
        # the rows shifted to their offset in the block: a slice of
        # [W zeros, rows, W - Tq zeros] (no gather, no layout of its own)
        src = jnp.concatenate([zero[:nb], rows[b:b + nb],
                               zero[:nb, :W - Tq]], axis=1)
        new = jax.lax.dynamic_slice_in_dim(src, W - (p0 - start), W, axis=1)
        p = start + jnp.arange(W)
        put = ((p >= p0) & (p < p0 + Tq))[None]                 # [1, W]
        if keep is not None:
            put = put & keep[b:b + nb, None]
        blk = jnp.where(put.reshape(put.shape + tail), new.reshape(shape),
                        jax.lax.dynamic_slice(leaf, at, shape))
        leaf = jax.lax.dynamic_update_slice(leaf, blk, at)
    return leaf


def _write_slice(leaf: jax.Array, i, new: jax.Array,
                 keep: Optional[jax.Array]) -> jax.Array:
    """Write a block's rewritten state ``new`` [B, ...] as layer ``i`` of a
    stacked leaf (``i`` None: the whole prelude leaf); rows where ``keep``
    is False keep their current values."""
    old = leaf if i is None else jax.lax.dynamic_index_in_dim(
        leaf, i, 0, keepdims=False)
    if keep is not None:
        new = jnp.where(keep.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)
    if i is None:
        return new
    return jax.lax.dynamic_update_index_in_dim(leaf, new, i, 0)


def _decode_layers(layout: StackLayout, params: Dict, state: Dict,
                   x: jax.Array, apply, pos: jax.Array,
                   keep: Optional[jax.Array]):
    """The decode layer loop: x [B, Tq, D] through every layer against the
    carried state, which stays stacked and is updated in place. A
    ``lax.scan`` over the stacked pattern params carries (x, the stacked
    pattern state, the layer index); each block reads its layer's leaves
    and writes only what it changes:

      * rows: ``put_rows({"k": k, "v": v})`` writes its Tq new K/V rows at
        each row's ``pos`` and returns the layer's cache holding them;
      * whole leaves: what the block returns in ``new`` (Mamba ``h`` /
        ``conv``; the flush's ``A``, ``z`` and reset caches) is written as
        the layer's slice; a leaf it does not return is not written.

    ``keep``: bool [B] rows to write (None: every row); the other rows
    keep their state bit for bit. Prefill's recurrence rewrites the whole
    state every segment and stays on ``run_sequential``; this loop is
    decode's alone. Returns (y, {'prelude', 'pattern'})."""

    def layer(t, p, x, leaves, i):
        leaves = dict(leaves)

        def read(name):
            a = leaves[name]
            return a if i is None else jax.lax.dynamic_index_in_dim(
                a, i, 0, keepdims=False)

        def put_rows(rows):
            for name, r in rows.items():
                leaves[name] = _write_rows(leaves[name], i, r, pos, keep)
            return {name: read(name) for name in rows}

        y, new = apply(t, p, x, {name: read(name) for name in leaves},
                       put_rows)
        for name, v in new.items():
            leaves[name] = _write_slice(leaves[name], i, v, keep)
        return y, leaves

    prelude = []
    for j, t in enumerate(layout.prelude):
        x, st = layer(t, params["prelude"][j], x, state["prelude"][j], None)
        prelude.append(st)

    def superblock(carry, sb_params):
        x, pattern, i = carry
        pattern = list(pattern)
        for p_i, t in enumerate(layout.pattern):
            x, pattern[p_i] = layer(t, sb_params[p_i], x, pattern[p_i], i)
        return (x, tuple(pattern), i + 1), None

    pattern = tuple(state["pattern"])
    if layout.pattern:
        (x, pattern, _), _ = jax.lax.scan(
            superblock, (x, pattern, jnp.int32(0)), params["pattern"])
    return x, {"prelude": tuple(prelude), "pattern": pattern}


def make_decode_apply(cfg: ArchConfig, serve_mode: str, pos):
    """Block apply for ``_decode_layers``: (t, p, x [B, Tq, D], st,
    put_rows) -> (y, new) — ``st`` the layer's leaves, ``new`` the leaves
    the block rewrote whole (attention blocks write only K/V rows)."""
    armt_on = serve_mode == "armt" and cfg.armt is not None

    def apply_ffn(t, h, p):
        if t.endswith("moe"):
            return h + moe_ffn(norm(cfg.norm, h, p["ln2"]), p["moe"],
                               cfg.moe, cfg.act)
        if "ffn" in p:
            return h + ffn(cfg.act, norm(cfg.norm, h, p["ln2"]), p["ffn"])
        return h

    def apply(t, p, x, st, put_rows):
        if _is_attn(t):
            if armt_on:
                x = x + mem_read(p["mem"], st, x, cfg.armt)
            q, k, v = decode_qkv(norm(cfg.norm, x, p["ln1"]), p["attn"], cfg,
                                 pos)
            cache = put_rows({"k": k, "v": v})
            h = x + decode_attend(q, cache["k"], cache["v"], p["attn"], cfg,
                                  pos)
            if t == "dec":
                h = h + cross_attention(norm(cfg.norm, h, p["ln_x"]),
                                        p["xattn"], st["ck"], st["cv"], cfg)
            return apply_ffn(t, h, p), {}
        if t.startswith("mamba"):
            mix, new_ssm = mamba_mixer(norm(cfg.norm, x, p["ln1"]), p["mixer"],
                                       cfg.ssm,
                                       {"h": st["h"], "conv": st["conv"]})
            return apply_ffn(t, x + mix, p), new_ssm
        raise ValueError(t)

    return apply


def decode_step(params: Dict, cfg: ArchConfig, state: Dict,
                tokens: jax.Array, *, serve_mode: str = "armt",
                active: Optional[jax.Array] = None):
    """Decoding step. tokens: [B] (one step) or [B, Tq] (chunked prefill) ->
    (logits of the last position [B, V] fp32, new state).

    The state is updated in place (``_decode_layers``): each layer writes
    its Tq new K/V rows, and nothing else changes in decode. active:
    optional bool [B] slot mask (needs a per-slot ``pos``) — rows where it
    is False keep every leaf and ``pos`` bit for bit; their logits are
    computed but meaningless."""
    assert active is None or state["pos"].ndim == 1, (
        "decode_step(active=...) needs a per-slot pos vector")
    layout = StackLayout.from_config(cfg)
    pos = state["pos"]
    toks = tokens if tokens.ndim == 2 else tokens[:, None]
    Tq = toks.shape[1]
    x = params["embed"][toks]                                    # [B,Tq,D]
    if "pos_embed" in params:
        x = x + _pos_embed_slice(params["pos_embed"], pos, Tq).astype(x.dtype)
    apply = make_decode_apply(cfg, serve_mode, pos)
    y, layers = _decode_layers(layout, params, state, x, apply, pos, active)
    h = norm(cfg.norm, y[:, -1], params["final_norm"])
    logits = _head_matmul(params, cfg, h).astype(jnp.float32)
    new_pos = pos + Tq if active is None else jnp.where(active, pos + Tq, pos)
    return logits, {**layers, "pos": new_pos}


def flush_segment(params: Dict, cfg: ArchConfig, state: Dict,
                  slot_mask: Optional[jax.Array] = None):
    """ARMT segment boundary: run the memory tokens through the stack against
    the current-segment cache, delta-update every layer's (A, z), then reset
    the segment cache and position.

    slot_mask: optional bool [B] — flush only those batch rows (decode
    slots), keeping the other rows' state/cache/pos untouched. The flush is
    computed for every row and written through the same in-place layer loop
    as ``decode_step``, masked per row at each write, so heterogeneous
    slots hitting segment boundaries at different steps stay inside one
    jitted step (no host branching); requires a per-slot ``pos`` vector."""
    assert cfg.armt is not None
    assert slot_mask is None or state["pos"].ndim == 1, (
        "flush_segment(slot_mask=...) needs a per-slot pos vector "
        "(decode_state_init(per_slot_pos=True)); a scalar pos cannot be "
        "reset per-row and would silently re-flush every step")
    layout = StackLayout.from_config(cfg)
    M = cfg.armt.num_mem_tokens
    mem = params["mem_tokens"]
    # infer batch from any cache leaf
    first = jax.tree_util.tree_leaves(state["pattern"])[0]
    batch = first.shape[1]
    x = jnp.broadcast_to(mem[None], (batch, M, mem.shape[-1]))
    if "pos_embed" in params:
        x = x + _pos_embed_slice(params["pos_embed"], state["pos"],
                                 M).astype(x.dtype)

    pos = state["pos"]
    base_apply = make_decode_apply(cfg, "armt", pos)

    def apply(t, p, xx, st, put_rows):
        y, new = base_apply(t, p, xx, st, put_rows)
        if _is_attn(t) and t != "enc" and "A" in st:
            upd = mem_update(p["mem"], {"A": st["A"], "z": st["z"]}, y, cfg.armt)
            # and reset the current-segment cache
            new = {**new, **upd, "k": jnp.zeros_like(st["k"]),
                   "v": jnp.zeros_like(st["v"])}
        return y, new

    _, layers = _decode_layers(layout, params, state, x, apply, pos,
                               slot_mask)
    new_pos = (jnp.zeros_like(pos) if slot_mask is None
               else jnp.where(slot_mask, 0, pos))
    return {**layers, "pos": new_pos}
