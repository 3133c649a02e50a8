"""Jit'd op entry points, routed through the backend dispatch resolver
(kernels/dispatch.py, DESIGN.md §14): every call resolves one KernelConfig
— pallas-kernel vs. XLA-native, block sizes, interpret lowering — from the
per-call override, the autotune cache, or the per-backend heuristic table,
in that order. MXU-alignment padding stays here (the resolver is
shape-bucketed; padding is an op-local concern)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import dispatch, ref
from repro.kernels.dispatch import KernelConfig
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grouped_matmul import (grouped_matmul,
                                          grouped_matmul_armt_update)
from repro.kernels.armt_memory import armt_read, armt_update
from repro.kernels.decode_attention import decode_attention as \
    decode_attention_kernel
from repro.kernels.mamba_scan import mamba_scan
from repro.utils import round_up


def _pad_axis(x, axis: int, to: int):
    pad = round_up(x.shape[axis], to) - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _resolve(op, shapes, dtype, use_kernel, interpret, config):
    if config is not None:
        return config
    return dispatch.resolve(op, shapes, dtype, use_kernel=use_kernel,
                            interpret=interpret)


def segment_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      use_kernel: bool | None = None,
                      interpret: bool | None = None,
                      config: KernelConfig | None = None):
    """Grouped attention with automatic 128-lane head-dim padding.
    q: [N,Hq,T,hd]; k/v: [N,Hkv,S,hd] — or the 5-D grouped-block layout
    q: [G,B,T,Hq,hd]; k/v: [G,B,S,Hkv,hd], which the XLA branch keeps
    un-flattened (the (g,b,h)-batched dot is what CPU XLA schedules
    fastest and what the vmap path lowers to; see DESIGN.md §14) and the
    pallas branch transposes at the boundary."""
    cfg = _resolve("flash_attention", (q.shape, k.shape), q.dtype,
                   use_kernel, interpret, config)
    if q.ndim == 5:
        if cfg.impl == "xla":
            return ref.flash_attention_grouped_ref(
                q, k, v, causal=causal, window=window,
                fast_softmax=cfg.fast_softmax,
                causal_blocks=cfg.causal_blocks)
        G, B, T, Hq, hd = q.shape
        flat = lambda a: a.reshape((G * B,) + a.shape[2:]).swapaxes(1, 2)
        out = segment_attention(flat(q), flat(k), flat(v), causal=causal,
                                window=window, config=cfg)
        return out.swapaxes(1, 2).reshape(G, B, T, Hq, hd)
    if cfg.impl == "xla":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    hd = q.shape[-1]
    hd_p = round_up(hd, 128)
    if hd_p != hd:
        # zero-pad head dim; scale is computed from the true hd inside ref,
        # so rescale q to keep softmax temperature identical
        scale_fix = (hd_p / hd) ** 0.5
        q = _pad_axis(q * scale_fix, -1, 128)
        k = _pad_axis(k, -1, 128)
        v = _pad_axis(v, -1, 128)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=cfg.interpret,
                          **cfg.blocks("block_q", "block_k"))
    return out[..., :hd]


def decode_attention(q, k, v, lengths, *, window: int = 0,
                     use_kernel: bool | None = None,
                     interpret: bool | None = None,
                     config: KernelConfig | None = None):
    """Single-token decode attention against the serve KV-cache layout.
    q: [B,Hq,hd]; k/v: [B,S,Hkv,hd]; lengths: [B]."""
    cfg = _resolve("decode_attention", (q.shape, k.shape), q.dtype,
                   use_kernel, interpret, config)
    if cfg.impl == "xla":
        return ref.decode_attention_ref(q, k, v, lengths, window=window)
    hd = q.shape[-1]
    hd_p = round_up(hd, 128)
    if hd_p != hd:
        scale_fix = (hd_p / hd) ** 0.5
        q = _pad_axis(q * scale_fix, -1, 128)
        k = _pad_axis(k, -1, 128)
        v = _pad_axis(v, -1, 128)
    out = decode_attention_kernel(q, k, v, lengths, window=window,
                                  interpret=cfg.interpret,
                                  **cfg.blocks("block_k"))
    return out[..., :hd]


def grouped_gemm(x, w, bias=None, *, activation: str | None = None,
                 use_kernel: bool | None = None,
                 interpret: bool | None = None,
                 config: KernelConfig | None = None):
    """Grouped GEMM with a fused bias + activation epilogue.
    x: [G,M,K] or the un-flattened grouped-block layout [G,B,T,K];
    w: [G,K,N]; bias: optional [G,N]; activation: None|silu|gelu.
    The XLA branch keeps the 4-D form (the fast CPU lowering — see
    grouped_matmul_ref); the pallas branch flattens rows at the kernel
    boundary."""
    cfg = _resolve("grouped_matmul", (x.shape, w.shape), x.dtype,
                   use_kernel, interpret, config)
    if cfg.impl == "xla":
        return ref.grouped_matmul_ref(x, w, bias, activation=activation)
    shape4 = x.shape if x.ndim == 4 else None
    if shape4 is not None:
        x = x.reshape(shape4[0], shape4[1] * shape4[2], shape4[3])
    out = grouped_matmul(x, w, bias, activation=activation,
                         interpret=cfg.interpret,
                         **cfg.blocks("block_m", "block_n", "block_k"))
    if shape4 is not None:
        out = out.reshape(shape4[:3] + (out.shape[-1],))
    return out


def grouped_gemm_armt_update(x, w, res, wk, wv, wb, A, z, bias=None, *,
                             M: int, nu: int = 3,
                             use_kernel: bool | None = None,
                             interpret: bool | None = None,
                             config: KernelConfig | None = None):
    """Grouped GEMM + residual with the ARMT delta-rule update fused into
    the epilogue (one launch instead of two per anti-diagonal cell).
    x/res: [G,R,K]/[G,R,N] or the un-flattened [G,B,T,K]/[G,B,T,N]
    grouped-block layout (B == 1). Falls back to the composition when the
    fused kernel's tiling constraints don't hold (mem rows straddling the
    last m-tile)."""
    cfg = _resolve("grouped_matmul_armt_update", (x.shape, w.shape, A.shape),
                   x.dtype, use_kernel, interpret, config)
    shape4 = x.shape if x.ndim == 4 else None
    if shape4 is not None and cfg.impl != "xla":
        x = x.reshape(shape4[0], shape4[1] * shape4[2], shape4[3])
        res = res.reshape(shape4[0], shape4[1] * shape4[2], res.shape[-1])
    R = x.shape[1] if x.ndim == 3 else x.shape[1] * x.shape[2]
    bm = min(cfg.block_m or 256, R)
    n_m = -(-R // bm)
    rows_last = R - (n_m - 1) * bm
    fusable = cfg.fuse_epilogue and rows_last >= M
    if cfg.impl == "xla":
        return ref.grouped_matmul_armt_update_ref(x, w, res, wk, wv, wb,
                                                  A, z, bias, M=M, nu=nu)
    # trace-time count of which form ran, like kernel_dispatch_total
    dispatch._registry().inc("armt_update_epilogue_total",
                             form="fused" if fusable else "two_launch")
    if not fusable:
        y = res + grouped_matmul(x, w, bias, interpret=cfg.interpret,
                                 **cfg.blocks("block_m", "block_k"))
        A2, z2 = armt_update(y[:, -M:, :], wk, wv, wb, A, z, nu=nu,
                             interpret=cfg.interpret)
    else:
        y, A2, z2 = grouped_matmul_armt_update(
            x, w, res, wk, wv, wb, A, z, bias, M=M, nu=nu,
            interpret=cfg.interpret, **cfg.blocks("block_m", "block_k"))
    if shape4 is not None:
        y = y.reshape(shape4[:3] + (y.shape[-1],))
    return y, A2, z2


def assoc_read(x, wq, A, z, *, nu: int = 3, use_kernel: bool | None = None,
               interpret: bool | None = None,
               config: KernelConfig | None = None):
    cfg = _resolve("armt_read", (x.shape, A.shape), x.dtype,
                   use_kernel, interpret, config)
    if cfg.impl == "xla":
        return ref.armt_read_ref(x, wq, A, z, nu=nu)
    return armt_read(x, wq, A, z, nu=nu, interpret=cfg.interpret,
                     **cfg.blocks("block_t", "block_v"))


def assoc_update(m, wk, wv, wb, A, z, *, nu: int = 3,
                 use_kernel: bool | None = None,
                 interpret: bool | None = None,
                 config: KernelConfig | None = None):
    cfg = _resolve("armt_update", (m.shape, A.shape), m.dtype,
                   use_kernel, interpret, config)
    if cfg.impl == "xla":
        return ref.armt_update_ref(m, wk, wv, wb, A, z, nu=nu)
    return armt_update(m, wk, wv, wb, A, z, nu=nu, interpret=cfg.interpret,
                       **cfg.blocks("block_v"))


def selective_scan_fused(x, dt, Bt, Ct, A_log, D, h0, *,
                         use_kernel: bool | None = None,
                         interpret: bool | None = None,
                         config: KernelConfig | None = None):
    cfg = _resolve("mamba_scan", (x.shape, Bt.shape), x.dtype,
                   use_kernel, interpret, config)
    if cfg.impl == "xla":
        return ref.mamba_scan_ref(x, dt, Bt, Ct, A_log, D, h0)
    return mamba_scan(x, dt, Bt, Ct, A_log, D, h0, interpret=cfg.interpret,
                      **cfg.blocks("block_i"))
