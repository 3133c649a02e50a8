"""Grouped (batched) matmul — Pallas TPU kernel.

TPU analogue of the paper's CUTLASS GroupedGEMM (§3.3): per-layer weights are
stacked on a leading group dim, so the grouped GEMM is a batched GEMM the MXU
executes at peak. Explicit VMEM tiling: [bm, bk] x [bk, bn] tiles with fp32
accumulation over the K grid dimension (output block revisited, initialized
at k==0 — the canonical Pallas accumulation pattern).

Fused epilogue: an optional per-group bias [G, N] and an optional activation
("silu" | "gelu") are applied to the fp32 accumulator in VMEM before the
output store — the QKV bias add and the FFN up-proj + activation never round
trip through HBM (the grouped-block fast path relies on this).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ACTIVATIONS = (None, "silu", "gelu")

# The fused ARMT epilogue holds one layer's whole wv [D, Dv] and A [P, Dv]
# in VMEM next to the GEMM tiles: ~37 MB double-buffered at llama-1b-armt
# widths, past the 16 MB default scoped limit. A TPU v5e core has 128 MiB.
_FUSED_VMEM_LIMIT = 100 * 2**20


def _epilogue(acc, activation: str | None):
    if activation == "silu":
        return acc * jax.nn.sigmoid(acc)
    if activation == "gelu":
        return jax.nn.gelu(acc, approximate=True)
    return acc


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int,
                activation: str | None):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        o_ref[...] = _epilogue(acc_ref[...], activation).astype(o_ref.dtype)


def _gmm_bias_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, n_k: int,
                     activation: str | None):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        acc = acc_ref[...] + b_ref[...].astype(jnp.float32)    # [1, bn]
        o_ref[...] = _epilogue(acc, activation).astype(o_ref.dtype)


def _gmm_armt_kernel(x_ref, w_ref, res_ref, b_ref, wk_ref, wv_ref, wb_ref,
                     a_ref, z_ref, y_ref, a_out_ref, z_out_ref, acc_ref, *,
                     n_m: int, n_k: int, mem_off: int, M: int, nu: int):
    from repro.kernels.armt_memory import EPS, _dot_nt, _dpfp
    im, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        out = (acc_ref[...] + b_ref[...].astype(jnp.float32)   # [1, D]
               + res_ref[...].astype(jnp.float32))
        y = out.astype(y_ref.dtype)
        y_ref[...] = y

        # ARMT delta-rule epilogue on the tile holding the memory tokens:
        # identical math to armt_memory._update_kernel, fed from the y tile
        # already resident in VMEM (cast to the activation dtype first, so
        # fused == unfused bit-for-bit — the unfused path reads y from HBM).
        @pl.when(im == n_m - 1)
        def _armt():
            m = y[mem_off:mem_off + M, :].astype(jnp.float32)
            k = m @ wk_ref[...].astype(jnp.float32)
            pk = _dpfp(k, nu)                                    # [M, P]
            v = m @ wv_ref[...].astype(jnp.float32)              # [M, Dv]
            beta = jax.nn.sigmoid(m @ wb_ref[...].astype(jnp.float32))
            a = a_ref[...].astype(jnp.float32)
            z = z_ref[...].astype(jnp.float32)                   # [1, P]
            zk = _dot_nt(pk, z)                                  # [M, 1]
            vbar = (pk @ a) / (zk + EPS)
            a_out_ref[...] = (
                a + pk.T @ (beta * (v - vbar))).astype(a_out_ref.dtype)
            gamma = jnp.clip(
                1.0 - zk[:, 0] / (jnp.sum(pk * pk, axis=-1) + EPS), 0.0, 1.0)
            z_out_ref[...] = (
                z + gamma[None, :] @ pk).astype(z_out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("M", "nu", "block_m", "block_k", "interpret"))
def grouped_matmul_armt_update(x, w, res, wk, wv, wb, A, z, bias=None, *,
                               M: int, nu: int = 3, block_m: int = 256,
                               block_k: int = 512, interpret: bool = False):
    """Grouped GEMM with residual + fused ARMT memory-update epilogue.

    ``y = res + x @ w (+ bias)`` and, in the same launch, the delta-rule
    update of ``(A, z)`` from the last ``M`` rows of each group's ``y``
    (the memory tokens) — the two separate per-anti-diagonal-cell launches
    of the grouped-block fast path collapsed into one, so the memory
    tokens never round trip through HBM between the down-projection and
    the associative update.

    x: [G, R, K]; w: [G, K, D]; res: [G, R, D]; bias: optional [G, D];
    wk: [G, D, dm] | [D, dm]; wv: [G, D, Dv]; wb: [G, D, 1];
    A: [G, P, Dv]; z: [G, P]  ->  (y [G, R, D], A' [G, P, Dv], z' [G, P]).

    Tiling constraints (checked; ops.py falls back to separate launches
    when unmet): N is full-width (the epilogue needs complete memory-token
    rows) and the last M rows must sit inside the final m-tile.
    """
    from repro.kernels.armt_memory import _wspec
    G, R, K = x.shape
    _, _, D = w.shape
    _, P, Dv = A.shape
    block_m = min(block_m, R)
    block_k = min(block_k, K)
    n_m = pl.cdiv(R, block_m)
    n_k = pl.cdiv(K, block_k)
    rows_last = R - (n_m - 1) * block_m
    assert rows_last >= M, (
        f"mem rows (M={M}) straddle the last m-tile "
        f"(rows_last={rows_last}); use separate launches")
    mem_off = rows_last - M
    if bias is None:
        bias = jnp.zeros((G, D), x.dtype)

    # zero-pad ragged R/K up to block multiples (padded K columns are
    # exact zeros in the accumulator; padded rows sit past the memory
    # tokens in the last m-tile and are sliced off below)
    Rp, Kp = n_m * block_m, n_k * block_k
    if (Rp, Kp) != (R, K):
        x = jnp.pad(x, ((0, 0), (0, Rp - R), (0, Kp - K)))
        w = jnp.pad(w, ((0, 0), (0, Kp - K), (0, 0)))
        res = jnp.pad(res, ((0, 0), (0, Rp - R), (0, 0)))

    grid = (G, n_m, n_k)
    kernel = functools.partial(_gmm_armt_kernel, n_m=n_m, n_k=n_k,
                               mem_off=mem_off, M=M, nu=nu)
    y, A2, z2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_m, block_k),
                         lambda g, im, ik: (g, im, ik)),
            pl.BlockSpec((None, block_k, D),
                         lambda g, im, ik: (g, ik, 0)),
            pl.BlockSpec((None, block_m, D),
                         lambda g, im, ik: (g, im, 0)),
            pl.BlockSpec((None, 1, D), lambda g, im, ik: (g, 0, 0)),
            _wspec(wk, G),
            _wspec(wv, G),
            _wspec(wb, G),
            pl.BlockSpec((None, P, Dv), lambda g, im, ik: (g, 0, 0)),
            pl.BlockSpec((None, 1, P), lambda g, im, ik: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_m, D), lambda g, im, ik: (g, im, 0)),
            pl.BlockSpec((None, P, Dv), lambda g, im, ik: (g, 0, 0)),
            pl.BlockSpec((None, 1, P), lambda g, im, ik: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, Rp, D), x.dtype),
            jax.ShapeDtypeStruct(A.shape, A.dtype),
            jax.ShapeDtypeStruct((G, 1, P), z.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_m, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_FUSED_VMEM_LIMIT),
        name="grouped_matmul_armt_update",
        interpret=interpret,
        # bias and z ride as [G, 1, N] rows (legal (1, N) TPU tiles)
    )(x, w, res, bias[:, None, :], wk, wv, wb, A, z[:, None, :])
    return (y[:, :R, :] if Rp != R else y), A2, z2[:, 0, :]


@functools.partial(
    jax.jit, static_argnames=("activation", "block_m", "block_n", "block_k",
                              "interpret"))
def grouped_matmul(x, w, bias=None, *, activation: str | None = None,
                   block_m: int = 128, block_n: int = 128,
                   block_k: int = 512, interpret: bool = False):
    """x: [G, M, K], w: [G, K, N] (+ bias [G, N]) -> [G, M, N].

    ``activation`` is applied to the fp32 accumulator (after the bias add)
    inside the kernel epilogue: None | "silu" | "gelu" (tanh approximation,
    matching ``jax.nn.gelu(approximate=True)`` in models/layers.py).
    """
    assert activation in ACTIVATIONS, activation
    G, M, K = x.shape
    _, _, N = w.shape
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)

    # zero-pad ragged dims up to block multiples: padded K columns
    # contribute exactly zero to the fp32 accumulator, padded M rows /
    # N columns are sliced off after the call
    Mp, Np, Kp = (pl.cdiv(d, b) * b for d, b in
                  ((M, block_m), (N, block_n), (K, block_k)))
    if (Mp, Kp) != (M, K):
        x = jnp.pad(x, ((0, 0), (0, Mp - M), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        w = jnp.pad(w, ((0, 0), (0, Kp - K), (0, Np - N)))
    if bias is not None and Np != N:
        bias = jnp.pad(bias, ((0, 0), (0, Np - N)))

    n_k = Kp // block_k
    grid = (G, Mp // block_m, Np // block_n, n_k)
    in_specs = [
        pl.BlockSpec((None, block_m, block_k),
                     lambda g, im, jn, ik: (g, im, ik)),
        pl.BlockSpec((None, block_k, block_n),
                     lambda g, im, jn, ik: (g, ik, jn)),
    ]
    if bias is None:
        kernel = functools.partial(_gmm_kernel, n_k=n_k, activation=activation)
        operands = (x, w)
    else:
        assert bias.shape == (G, Np), (bias.shape, (G, Np))
        # [G, 1, N] with a (1, block_n) block: a legal TPU tile, where a
        # (None, block_n) block of [G, N] squeezes the sublane dim
        in_specs.append(pl.BlockSpec((None, 1, block_n),
                                     lambda g, im, jn, ik: (g, 0, jn)))
        kernel = functools.partial(_gmm_bias_kernel, n_k=n_k,
                                   activation=activation)
        operands = (x, w, bias[:, None, :])
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, block_m, block_n),
                               lambda g, im, jn, ik: (g, im, jn)),
        out_shape=jax.ShapeDtypeStruct((G, Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        name="grouped_matmul",
        interpret=interpret,
    )(*operands)
    return out[:, :M, :N] if (Mp, Np) != (M, N) else out
