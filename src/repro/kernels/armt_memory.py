"""ARMT associative memory — fused Pallas TPU kernels.

The paper-specific hot spot (eqs. 3-6). Two kernels:

  armt_read:   out = (phi(x Wq) A) / (phi(x Wq) . z + eps), tiled over tokens
               and the value dim; phi (DPFP-nu) is computed in VMEM and never
               materialized in HBM.
  armt_update: delta-rule A' = A + sum_i beta_i (v_i - vbar_i) phi(k_i)^T,
               z' = z + sum_i gamma_i phi(k_i), tiled over the value dim
               (memory tokens M is small — one block).

Layout: x [N, T, D], A [N, P, Dv], z [N, P] with N = group*batch (the diagonal
executor's grouped launch), P = 2*nu*d_mem. Inside the kernels z is the
[1, P] row of an [N, 1, P] view, so every block's last two dims are a legal
TPU tile.

Projection weights may be shared across N (``wq: [D, dm]``) or stacked per
group (``wq: [G, D, dm]`` with N = G*batch) — the grouped-block fast path
stacks per-layer weights on the group dim and the BlockSpec index map picks
row ``n // batch``; the kernel bodies are identical in both cases.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EPS = 1e-6


def _wspec(w, N: int, last_block=None, last_axis=None):
    """BlockSpec for a projection weight: shared ``[D, E]`` or per-group
    ``[G, D, E]`` (row ``n // batch``, batch = N // G). ``last_block`` tiles
    the final weight dim over grid axis ``last_axis``."""
    D = w.shape[-2]
    E = last_block if last_block is not None else w.shape[-1]
    if w.ndim == 2:
        def idx(n, *rest):
            return (0, rest[last_axis] if last_axis is not None else 0)
        return pl.BlockSpec((D, E), idx)
    batch, r = divmod(N, w.shape[0])
    assert r == 0, f"N={N} not divisible by weight groups G={w.shape[0]}"

    def gidx(n, *rest):
        return (n // batch, 0, rest[last_axis] if last_axis is not None else 0)
    return pl.BlockSpec((None, D, E), gidx)


def _dpfp(x, nu: int):
    r = jnp.concatenate([jnp.maximum(x, 0), jnp.maximum(-x, 0)], axis=-1)
    return jnp.concatenate(
        [r * jnp.roll(r, j, axis=-1) for j in range(1, nu + 1)], axis=-1)


def _dot_nt(a, b):
    """``a @ b.T`` for a [m, k] and b [n, k], fp32 (the row-vector form the
    normalizer ``z`` takes in VMEM)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())))


def _read_kernel(x_ref, wq_ref, a_ref, z_ref, o_ref, *, nu: int):
    # x: [bt, D], wq: [D, dm], a: [P, bv], z: [1, P], o: [bt, bv]
    x = x_ref[...].astype(jnp.float32)
    q = x @ wq_ref[...].astype(jnp.float32)
    pq = _dpfp(q, nu)                                       # [bt, P]
    num = pq @ a_ref[...].astype(jnp.float32)               # [bt, bv]
    # a lane reduction, not a matmul: Mosaic folds `dot + EPS` into a
    # non-zero matmul accumulator, which it refuses
    den = jnp.sum(pq * z_ref[...].astype(jnp.float32), axis=-1,
                  keepdims=True)                            # [bt, 1]
    o_ref[...] = (num / (den + EPS)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("nu", "block_t", "block_v", "interpret"))
def armt_read(x, wq, A, z, *, nu: int = 3, block_t: int = 256,
              block_v: int = 512, interpret: bool = False):
    """x: [N,T,D], wq: [D,dm] or [G,D,dm], A: [N,P,Dv], z: [N,P] -> [N,T,Dv]."""
    N, T, D = x.shape
    _, P, Dv = A.shape
    block_t = min(block_t, T)
    block_v = min(block_v, Dv)
    grid = (N, pl.cdiv(T, block_t), pl.cdiv(Dv, block_v))
    # z rides as [N, 1, P]: a (1, P) block is a legal TPU tile, where a
    # (None, P) block of [N, P] squeezes the sublane dim and is refused
    return pl.pallas_call(
        functools.partial(_read_kernel, nu=nu),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_t, D), lambda n, it, iv: (n, it, 0)),
            _wspec(wq, N),
            pl.BlockSpec((None, P, block_v), lambda n, it, iv: (n, 0, iv)),
            pl.BlockSpec((None, 1, P), lambda n, it, iv: (n, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_t, block_v),
                               lambda n, it, iv: (n, it, iv)),
        out_shape=jax.ShapeDtypeStruct((N, T, Dv), x.dtype),
        name="armt_read",
        interpret=interpret,
    )(x, wq, A, z[:, None, :])


def _update_kernel(m_ref, wk_ref, wv_ref, wb_ref, a_ref, z_ref,
                   a_out_ref, z_out_ref, *, nu: int):
    # m: [M, D]; wk: [D, dm]; wv: [D, bv]; wb: [D, 1];
    # a: [P, bv]; z: [1, P]  ->  a_out: [P, bv]; z_out: [1, P]
    m = m_ref[...].astype(jnp.float32)
    k = m @ wk_ref[...].astype(jnp.float32)
    pk = _dpfp(k, nu)                                        # [M, P]
    v = m @ wv_ref[...].astype(jnp.float32)                  # [M, bv]
    beta = jax.nn.sigmoid(m @ wb_ref[...].astype(jnp.float32))  # [M, 1]
    a = a_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)
    zk = _dot_nt(pk, z)                                      # [M, 1]
    vbar = (pk @ a) / (zk + EPS)
    a_out_ref[...] = (a + pk.T @ (beta * (v - vbar))).astype(a_out_ref.dtype)

    @pl.when(pl.program_id(1) == 0)
    def _z():
        gamma = jnp.clip(1.0 - zk[:, 0] / (jnp.sum(pk * pk, axis=-1) + EPS),
                         0.0, 1.0)                                  # [M]
        z_out_ref[...] = (z + gamma[None, :] @ pk).astype(z_out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("nu", "block_v", "interpret"))
def armt_update(m, wk, wv, wb, A, z, *, nu: int = 3, block_v: int = 512,
                interpret: bool = False):
    """m: [N,M,D]; wk/wv/wb: [D,*] or [G,D,*]; A: [N,P,Dv]; z: [N,P] -> (A', z')."""
    N, M, D = m.shape
    _, P, Dv = A.shape
    block_v = min(block_v, Dv)
    grid = (N, pl.cdiv(Dv, block_v))
    A2, z2 = pl.pallas_call(
        functools.partial(_update_kernel, nu=nu),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, M, D), lambda n, iv: (n, 0, 0)),
            _wspec(wk, N),
            _wspec(wv, N, last_block=block_v, last_axis=0),
            _wspec(wb, N),
            pl.BlockSpec((None, P, block_v), lambda n, iv: (n, 0, iv)),
            pl.BlockSpec((None, 1, P), lambda n, iv: (n, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, P, block_v), lambda n, iv: (n, 0, iv)),
            pl.BlockSpec((None, 1, P), lambda n, iv: (n, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(A.shape, A.dtype),
            jax.ShapeDtypeStruct((N, 1, P), z.dtype),
        ],
        name="armt_update",
        interpret=interpret,
    )(m, wk, wv, wb, A, z[:, None, :])
    return A2, z2[:, 0, :]
