"""Pure-jnp oracles for every Pallas kernel (the allclose reference)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.memory import dpfp

EPS = 1e-6


@functools.lru_cache(maxsize=64)
def _attn_bias_cached(T: int, S: int, causal: bool, window: int):
    """Additive fp32 attention bias [1,1,T,S] (0 valid / -1e30 masked),
    computed eagerly once per shape and cached — embeds as one on-device
    constant shared by every compiled diagonal step body instead of being
    re-materialized per step (the [T,S] tensor is past XLA's constant-
    folding size cap, so in-graph construction really runs each step)."""
    with jax.ensure_compile_time_eval():
        qpos = jnp.arange(T)[:, None]
        kpos = jnp.arange(S)[None, :]
        mask = jnp.ones((T, S), bool)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > (qpos - window)
            if not causal:
                mask &= kpos < (qpos + window)
        return jnp.where(mask, 0.0, -1e30).astype(jnp.float32)[None, None]


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """q: [N,Hq,T,hd]; k/v: [N,Hkv,S,hd] -> [N,Hq,T,hd], fp32 softmax."""
    N, Hq, T, hd = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("nhtd,nhsd->nhts", q, k).astype(jnp.float32) * hd ** -0.5
    qpos = jnp.arange(T)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > (qpos - window)
        if not causal:
            # symmetric window: bounded above as well (causal mode is
            # already bounded above by the diagonal)
            mask &= kpos < (qpos + window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nhts,nhsd->nhtd", p, v.astype(jnp.float32)).astype(q.dtype)


def flash_attention_grouped_ref(q, k, v, *, causal: bool = True,
                                window: int = 0,
                                fast_softmax: bool = False,
                                causal_blocks: int = 0):
    """q/k/v: [G, B, T, H, hd] (the grouped-block layout, kept 5-D) ->
    [G, B, T, Hq, hd]. Same math as flash_attention_ref, lowered with
    (g, b, h) batch dims — on CPU XLA schedules this form markedly faster
    than the flattened [N, H, T, S] contraction (BENCH_diagonal).

    The mask enters as an additive fp32 bias (0 / -1e30) fused into the
    score epilogue instead of a `where` select — one fewer full pass over
    the [*, T, S] score tensor, and still bit-identical to the select form
    (adding 0.0 is exact; -1e30 + O(scores) rounds back to -1e30).

    ``fast_softmax`` applies the normalizer to the [*, T, hd] *output* of
    the value matmul instead of the [*, T, S] probability tensor — one
    fewer full pass over the score-sized tensor, exact up to fp
    reassociation (max-subtraction keeps it overflow-safe). It is a
    *dispatched* lowering (the CPU heuristic turns it on, see
    kernels/dispatch.py); the default keeps the `jax.nn.softmax`
    association so the oracle path stays fp32-ulp-equal to the vmap
    executor (tests/test_grouped_blocks.py::test_fused_structure_is_exact).

    ``causal_blocks = n`` (dispatched the same way) splits the query range
    into n bands and, per band, only computes scores against the keys the
    causal mask can reach — the skipped blocks are *fully masked*, and
    their softmax terms would have been exact zeros (exp(-1e30 - m) ==
    0.0, and p * v contributes exact 0.0), so the blocked lowering is
    value-identical while saving (n-1)/(2n) of the score-tensor matmul,
    exp and weighted-sum work. Engaged only for the plain causal square
    case (no window) when n divides T."""
    G, B, T, Hq, hd = q.shape
    S, Hkv = k.shape[2], k.shape[3]
    rep = Hq // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=3)
        v = jnp.repeat(v, rep, axis=3)
    bias = _attn_bias_cached(T, S, causal, window)
    scale = hd ** -0.5
    nb = int(causal_blocks or 0)
    if not (nb > 1 and causal and window == 0 and T == S
            and T % nb == 0 and T // nb >= 8):
        nb = 0

    def attend(q, k, v, bb):                # [B,*,H,hd], bias slice
        s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32)
        s = s * scale + bb
        if fast_softmax:
            m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m)              # unnormalized probabilities
            o = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
            l = jnp.sum(p, axis=-1)         # [B,H,T]
            return o / l.swapaxes(1, 2)[..., None]
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))

    def one(q, k, v):                       # [B, T, H, hd] per group
        if not nb:
            return attend(q, k, v, bias)
        h = T // nb
        outs = [attend(q[:, i * h:(i + 1) * h],
                       k[:, :(i + 1) * h], v[:, :(i + 1) * h],
                       bias[:, :, i * h:(i + 1) * h, :(i + 1) * h])
                for i in range(nb)]
        return jnp.concatenate(outs, axis=1)

    # vmap over the group dim rather than one 5-D einsum: XLA (notably on
    # CPU) schedules the vmapped batched dot measurably faster, and it is
    # the exact dot shape the vmap executor path produces
    return jax.vmap(one)(q, k, v).astype(q.dtype)


def decode_attention_ref(q, k, v, lengths, *, window: int = 0):
    """q: [B,Hq,hd]; k/v: [B,S,Hkv,hd] (cache layout); lengths: [B] ->
    [B,Hq,hd], fp32 softmax over the valid prefix per row."""
    B, Hq, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    kh = jnp.repeat(k.swapaxes(1, 2), rep, axis=1)       # [B,Hq,S,hd]
    vh = jnp.repeat(v.swapaxes(1, 2), rep, axis=1)
    s = jnp.einsum("bhd,bhsd->bhs", q, kh).astype(jnp.float32) * hd ** -0.5
    kpos = jnp.arange(S)[None, None, :]
    lens = lengths.astype(jnp.int32)[:, None, None]
    mask = kpos < lens
    if window > 0:
        mask &= kpos > (lens - 1 - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, vh.astype(jnp.float32)).astype(q.dtype)


def grouped_matmul_ref(x, w, bias=None, *, activation: str | None = None):
    """x: [G,M,K] or [G,B,T,K], w: [G,K,N] (+ bias [G,N]) -> [G,(B,T|M),N]
    (fp32 accumulation, epilogue = bias add + activation in fp32, matching
    the Pallas kernel). The 4-D form keeps the grouped-block row dims
    un-flattened — on CPU XLA the `gbtk,gkn` contraction schedules ~2x
    faster inside composed graphs than the flattened `gmk,gkn` form
    (EXPERIMENTS.md §Kernels), and the two are value-identical."""
    if x.ndim == 4:
        acc = jnp.einsum("gbtk,gkn->gbtn", x.astype(jnp.float32),
                         w.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        if bias is not None:
            acc = acc + bias.astype(jnp.float32)[:, None, None, :]
    else:
        acc = jnp.einsum("gmk,gkn->gmn", x.astype(jnp.float32),
                         w.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        if bias is not None:
            acc = acc + bias.astype(jnp.float32)[:, None, :]
    if activation == "silu":
        acc = acc * jax.nn.sigmoid(acc)
    elif activation == "gelu":
        acc = jax.nn.gelu(acc, approximate=True)
    else:
        assert activation is None, activation
    return acc.astype(x.dtype)


def _proj(x, w):
    """x: [N,T,D] @ w: [D,E] (shared) or [G,D,E] (per-group, N = G*batch)."""
    if w.ndim == 2:
        return jnp.einsum("ntd,de->nte", x, w)
    G = w.shape[0]
    N = x.shape[0]
    xg = x.reshape((G, N // G) + x.shape[1:])
    out = jnp.einsum("gbtd,gde->gbte", xg, w)
    return out.reshape((N,) + out.shape[2:])


def armt_read_ref(x, wq, A, z, *, nu: int = 3):
    """x: [N,T,D]; wq: [D,dm] or [G,D,dm]; A: [N,P,Dv]; z: [N,P] -> [N,T,Dv]."""
    q = _proj(x.astype(jnp.float32), wq.astype(jnp.float32))
    pq = dpfp(q, nu)
    num = jnp.einsum("ntp,npv->ntv", pq, A.astype(jnp.float32))
    den = jnp.einsum("ntp,np->nt", pq, z.astype(jnp.float32)) + EPS
    return (num / den[..., None]).astype(x.dtype)


def armt_update_ref(m, wk, wv, wb, A, z, *, nu: int = 3):
    """m: [N,M,D]; wk/wv/wb: [D,*] (shared) or [G,D,*] (per-group)."""
    m32 = m.astype(jnp.float32)
    k = _proj(m32, wk.astype(jnp.float32))
    v = _proj(m32, wv.astype(jnp.float32))
    beta = jax.nn.sigmoid(_proj(m32, wb.astype(jnp.float32)))[..., 0]
    pk = dpfp(k, nu)
    zk = jnp.einsum("nmp,np->nm", pk, z.astype(jnp.float32))
    vbar = jnp.einsum("nmp,npv->nmv", pk, A.astype(jnp.float32)) \
        / (zk + EPS)[..., None]
    gamma = jnp.clip(1.0 - zk / (jnp.sum(pk * pk, axis=-1) + EPS), 0.0, 1.0)
    A_new = A.astype(jnp.float32) + jnp.einsum("nm,nmv,nmp->npv",
                                               beta, v - vbar, pk)
    z_new = z.astype(jnp.float32) + jnp.einsum("nm,nmp->np", gamma, pk)
    return A_new.astype(A.dtype), z_new.astype(z.dtype)


def grouped_matmul_armt_update_ref(x, w, res, wk, wv, wb, A, z, bias=None, *,
                                   M: int, nu: int = 3):
    """Composition oracle for the fused GEMM + ARMT-update epilogue:
    y = res + x @ w (+ bias), then the delta-rule update fed from the last
    M rows of y per group (cast to the activation dtype first, matching
    both the fused kernel and the unfused two-launch path). x/res may be
    [G,M,K]/[G,M,N] or the un-flattened [G,B,T,K]/[G,B,T,N] grouped-block
    layout (B == 1; same fast-lowering rationale as grouped_matmul_ref)."""
    y32 = grouped_matmul_ref(x.astype(jnp.float32), w, bias) \
        + res.astype(jnp.float32)
    y = y32.astype(res.dtype)
    yf = y.reshape(y.shape[0], -1, y.shape[-1]) if x.ndim == 4 else y
    A2, z2 = armt_update_ref(yf[:, -M:, :], wk, wv, wb, A, z, nu=nu)
    return y, A2, z2


def mamba_scan_ref(x, dt, Bt, Ct, A_log, D, h0):
    """Token-sequential reference (fp32)."""
    A = -jnp.exp(A_log.astype(jnp.float32))

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        da = jnp.exp(dt_t[..., None] * A)
        h = da * h + (dt_t * x_t)[..., None] * B_t[:, None, :]
        y = jnp.einsum("bis,bs->bi", h, C_t) + D * x_t
        return h, y

    xs = (x.swapaxes(0, 1).astype(jnp.float32),
          dt.swapaxes(0, 1).astype(jnp.float32),
          Bt.swapaxes(0, 1).astype(jnp.float32),
          Ct.swapaxes(0, 1).astype(jnp.float32))
    hT, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    return ys.swapaxes(0, 1), hT
