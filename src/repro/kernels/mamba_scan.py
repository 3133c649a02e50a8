"""Mamba-1 selective scan — fused Pallas TPU kernel.

The faithful mamba-1 recurrence is sequential in time; the CUDA kernel's win
is keeping h resident in SRAM. TPU analogue: grid over (batch, d_inner
blocks); per program the state h [block_i, dS] lives in VMEM scratch and the
time loop streams x/dt/B/C tiles — h never touches HBM.

h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t ;  y_t = h_t . C_t + D*x_t
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, alog_ref, d_ref, h0_ref,
                 y_ref, hout_ref, h_scr, *, T: int):
    # x/dt: [T, bi]; b/c: [T, dS]; alog: [bi, dS]; d: [bi]; h0: [bi, dS]
    A = -jnp.exp(alog_ref[...].astype(jnp.float32))          # [bi, dS]
    D = d_ref[...].astype(jnp.float32)
    h_scr[...] = h0_ref[...].astype(jnp.float32)

    def step(t, _):
        x_t = x_ref[t, :].astype(jnp.float32)                # [bi]
        dt_t = dt_ref[t, :].astype(jnp.float32)              # [bi]
        B_t = b_ref[t, :].astype(jnp.float32)                # [dS]
        C_t = c_ref[t, :].astype(jnp.float32)                # [dS]
        da = jnp.exp(dt_t[:, None] * A)                      # [bi, dS]
        h = da * h_scr[...] + (dt_t * x_t)[:, None] * B_t[None, :]
        h_scr[...] = h
        y = h @ C_t + D * x_t                                # [bi]
        y_ref[t, :] = y.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, T, step, 0)
    hout_ref[...] = h_scr[...].astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_i", "interpret"))
def mamba_scan(x, dt, Bt, Ct, A_log, D, h0, *, block_i: int = 512,
               interpret: bool = False):
    """x/dt: [B,T,dI]; Bt/Ct: [B,T,dS]; A_log: [dI,dS]; D: [dI];
    h0: [B,dI,dS] -> (y [B,T,dI] fp32, hT [B,dI,dS] fp32)."""
    B, T, dI = x.shape
    dS = Bt.shape[-1]
    block_i = min(block_i, dI)
    grid = (B, pl.cdiv(dI, block_i))
    y, hT = pl.pallas_call(
        functools.partial(_scan_kernel, T=T),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, T, block_i), lambda b, ii: (b, 0, ii)),
            pl.BlockSpec((None, T, block_i), lambda b, ii: (b, 0, ii)),
            pl.BlockSpec((None, T, dS), lambda b, ii: (b, 0, 0)),
            pl.BlockSpec((None, T, dS), lambda b, ii: (b, 0, 0)),
            pl.BlockSpec((block_i, dS), lambda b, ii: (ii, 0)),
            pl.BlockSpec((block_i,), lambda b, ii: (ii,)),
            pl.BlockSpec((None, block_i, dS), lambda b, ii: (b, ii, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, T, block_i), lambda b, ii: (b, 0, ii)),
            pl.BlockSpec((None, block_i, dS), lambda b, ii: (b, ii, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, dI), jnp.float32),
            jax.ShapeDtypeStruct((B, dI, dS), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_i, dS), jnp.float32)],
        name="mamba_scan",
        interpret=interpret,
    )(x, dt, Bt, Ct, A_log, D, h0)
    return y, hT
