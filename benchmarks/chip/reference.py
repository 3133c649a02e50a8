"""The plain reference of an ARMT decoder, in float32, independent of the
program under test.

It reads the weights by name from the program's parameter layout (the tree
``weights.make`` fills) and computes, segment after segment and layer after
layer, what the configuration states:

* a prompt is cut into segments of ``segment_len`` tokens; each full
  segment gets ``num_mem_tokens`` memory tokens appended;
* per layer: x += read(x) (eq. 6), pre-norm causal self-attention with
  rotary positions local to the segment, pre-norm SwiGLU FFN; after a full
  segment the memory is updated from the layer's outputs at the memory
  tokens (eqs. 3-5, gamma clipped to [0, 1]);
* the last, partial segment is causal attention over its own tokens with
  the memory read and no update.

All matmuls run in float32 at ``highest`` precision. ``quant='fp8'`` is the
control: both operands of every weight matmul (projections, memory, head)
are rounded to float8_e4m3fn with absmax scales per row of the activations
and per column of the weights, the precision below the configuration's
bfloat16; attention scores stay in float32.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6          # the memory's denominator guard, as the program states
NORM_EPS = 1e-5     # rms_norm_eps / layernorm_epsilon of both sources
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(a, axis):
    """Round ``a`` to float8_e4m3fn with absmax scales along ``axis``."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(F8).astype(jnp.float32) * s


def _mm(quant):
    def mm(x, w):
        """x [..., K] @ w [K, N] in float32 (or through fp8)."""
        x = x.astype(jnp.float32)
        w = w.astype(jnp.float32)
        if quant == "fp8":
            x, w = _q8(x, -1), _q8(w, 0)
        return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
    return mm


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + NORM_EPS) * w.astype(jnp.float32)


def _rope(x, n, frac, theta):
    """Rotate-half rotary on the first ``frac`` of x's head dims, at
    positions 0..n-1. x: [T, H, hd]."""
    hd = x.shape[-1]
    d = int(hd * frac)
    d -= d % 2
    if d == 0:
        return x
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., :d // 2], x[..., d // 2:d], x[..., d:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c, rest], -1)


def _dpfp(x, nu):
    r = jnp.concatenate([jax.nn.relu(x), jax.nn.relu(-x)], -1)
    return jnp.concatenate([r * jnp.roll(r, j, axis=-1)
                            for j in range(1, nu + 1)], -1)


def _layer(m: Dict, p, x, A, z, mm, n_mem: int):
    """One layer over x [T, D] (memory tokens last when n_mem > 0)."""
    T = x.shape[0]
    nu = m["nu"]
    # associative read (eq. 6), on every position
    pq = _dpfp(mm(x, p["mem"]["wq"]), nu)
    read = mm(pq, A) / (pq @ z + EPS)[:, None]
    x = x + read
    # attention
    a = p["attn"]
    h = _rms(x, p["ln1"]["w"])
    q = mm(h, a["wq"]) + a.get("bq", 0.0)
    k = mm(h, a["wk"]) + a.get("bk", 0.0)
    v = mm(h, a["wv"]) + a.get("bv", 0.0)
    H, Hk, hd = m["n_heads"], m["n_kv_heads"], m["d_head"]
    q = _rope(q.reshape(T, H, hd), T, m["rope_fraction"], m["rope_theta"])
    k = _rope(k.reshape(T, Hk, hd), T, m["rope_fraction"], m["rope_theta"])
    v = v.reshape(T, Hk, hd)
    k = jnp.repeat(k, H // Hk, axis=1)
    v = jnp.repeat(v, H // Hk, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k,
                   precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    t_i = jnp.arange(T)[:, None]
    s_i = jnp.arange(T)[None, :]
    vis = s_i <= t_i
    if m["sliding_window"] > 0:
        vis &= s_i > t_i - m["sliding_window"]
    s = jnp.where(vis[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hts,shd->thd", w, v, precision=jax.lax.Precision.HIGHEST)
    x = x + mm(o.reshape(T, H * hd), a["wo"])
    # SwiGLU FFN
    f = p["ffn"]
    h = _rms(x, p["ln2"]["w"])
    y = x + mm(jax.nn.silu(mm(h, f["wg"])) * mm(h, f["wu"]), f["wd"])
    if n_mem == 0:
        return y, A, z
    # delta-rule update from the memory tokens' outputs (eqs. 3-5)
    mt = y[-n_mem:]
    kk = mm(mt, p["mem"]["wk"])
    vv = mm(mt, p["mem"]["wv"])
    beta = jax.nn.sigmoid(mm(mt, p["mem"]["wb"]))[:, 0]
    pk = _dpfp(kk, nu)
    zk = pk @ z
    vbar = mm(pk, A) / (zk + EPS)[:, None]
    gamma = jnp.clip(1.0 - zk / (jnp.sum(pk * pk, -1) + EPS), 0.0, 1.0)
    A = A + mm((beta[:, None] * pk).T, vv - vbar)
    z = z + gamma @ pk
    return y, A, z


class Reference:
    """Segment steps of the reference for one configuration and weights.

    ``model``: the configuration file's ``model`` section. ``params``: the
    weights in the program's layout (bf16 leaves are widened per layer)."""

    def __init__(self, model: Dict, params, quant: Optional[str] = None):
        self.m = dict(model)
        self.m["nu"] = model["armt"]["nu"]
        self.T = model["armt"]["segment_len"]
        self.M = model["armt"]["num_mem_tokens"]
        self.params = params
        mm = _mm(quant)
        m = self.m
        M = self.M

        # the weights are arguments of the jitted steps, never constants
        # captured into the programs
        def run(layers, x, A, z, n_mem):
            def body(carry, inp):
                p, a, zz = inp
                y, a2, z2 = _layer(m, p, carry, a, zz, mm, n_mem)
                return y, (a2, z2)
            y, (A2, z2) = jax.lax.scan(body, x, (layers, A, z))
            return y, A2, z2

        def full(params, toks, A, z):
            x = params["embed"][toks].astype(jnp.float32)
            x = jnp.concatenate(
                [x, params["mem_tokens"].astype(jnp.float32)], 0)
            y, A2, z2 = run(params["pattern"][0], x, A, z, M)
            return y[:-M], A2, z2

        def partial(params, toks, A, z):
            x = params["embed"][toks].astype(jnp.float32)
            y, _, _ = run(params["pattern"][0], x, A, z, 0)
            return y

        def logits(params, h):
            h = _rms(h, params["final_norm"]["w"])
            if "head" in params:
                return mm(h, params["head"])
            return mm(h, params["embed"].T)

        self._full = jax.jit(full)
        self._partial = jax.jit(partial)
        self._logits = jax.jit(logits)

    def state0(self):
        L = self.m["n_layers"]
        d_val = self.m["armt"]["d_val"] or self.m["d_model"]
        P = 2 * self.m["nu"] * self.m["armt"]["d_mem"]
        return (jnp.zeros((L, P, d_val), jnp.float32),
                jnp.zeros((L, P), jnp.float32))

    def logits_at(self, tokens: np.ndarray,
                  positions: Sequence[int]) -> np.ndarray:
        """Next-token logits [len(positions), V] (float32) at the given
        positions of the token stream ``tokens``."""
        with jax.default_matmul_precision("highest"):
            return self._logits_at(tokens, positions)

    def _logits_at(self, tokens, positions):
        T = self.T
        tokens = np.asarray(tokens, np.int32)
        want = sorted(set(int(p) for p in positions))
        assert want and want[-1] < tokens.shape[0]
        A, z = self.state0()
        rows: Dict[int, np.ndarray] = {}
        n_seg = -(-(want[-1] + 1) // T)
        for s in range(n_seg):
            seg = tokens[s * T:(s + 1) * T]
            here = [p - s * T for p in want if s * T <= p < (s + 1) * T]
            if seg.shape[0] == T:
                # a full segment: the program flushes it (memory tokens,
                # update) once its last token is in
                h, A, z = self._full(self.params, jnp.asarray(seg), A, z)
            else:
                pad = np.zeros(T, np.int32)
                pad[:seg.shape[0]] = seg
                h = self._partial(self.params, jnp.asarray(pad), A, z)
            if here:
                # logits of every row of the segment: one program whatever
                # rows are wanted
                hl = np.asarray(self._logits(self.params, h))
                for p in here:
                    rows[s * T + p] = hl[p]
        return np.stack([np.asarray(rows[int(p)], np.float32)
                         for p in positions])


def served_gaps(ref: Reference, prompt: np.ndarray,
                served: np.ndarray) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at that position (0 where it is the reference's
    argmax)."""
    stream = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    pos = [prompt.shape[0] - 1 + j for j in range(served.shape[0])]
    lg = ref.logits_at(stream, pos)
    return lg.max(-1) - lg[np.arange(len(pos)), served]


def control_gaps(ref: Reference, ctl: Reference, prompt: np.ndarray,
                 served: np.ndarray) -> np.ndarray:
    """The control's gaps: at the same positions of the same stream, the
    reference-logit gap of the token the control puts first."""
    stream = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    pos = [prompt.shape[0] - 1 + j for j in range(served.shape[0])]
    lg = ref.logits_at(stream, pos)
    lc = ctl.logits_at(stream, pos)
    pick = lc.argmax(-1)
    return lg.max(-1) - lg[np.arange(len(pos)), pick]
