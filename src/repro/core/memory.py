"""ARMT associative memory (paper eqs. 3-6).

Per layer l the memory is an associative matrix A^l in R^{d_phi x d_val} and a
normalizer z^l in R^{d_phi}, with d_phi = 2*nu*d_mem (DPFP-nu feature map,
nu=3 in the paper -> 6*d_mem). Once per segment:

  read (eq 6):    AssociativeLayer(x) = A phi(W_Q x) / (z^T phi(W_Q x))
  update (3-5):   k,v = W_K m, W_V m;  beta = sigmoid(W_beta m)
                  vbar  = A phi(k) / (z^T phi(k))
                  gamma = clip(1 - z^T phi(k) / ||phi(k)||^2, 0, 1)
                  A <- A + sum_i beta_i (v_i - vbar_i) (x) phi(k_i)
                  z <- z + sum_i gamma_i phi(k_i)

gamma is clipped to [0, 1], which eq. 5 as printed does not do: unclipped,
gamma goes negative, z follows, and the denominators z^T phi(.) + eps pass
through zero — with seeded random weights at llama-1b-armt's widths the
hidden state is non-finite within 16 segments (DESIGN.md §7). With the clip
z stays non-negative (phi is), so every denominator is at least eps.

The read is applied residually to every position of the segment input; the
update uses the transformer-layer *outputs* at the memory-token positions.
State is kept in float32 regardless of model dtype (cheap: d_phi*d_val per
layer) — numerics note in DESIGN.md §7.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs import ARMTConfig

EPS = 1e-6

# The per-layer *recurrent* state leaves: ARMT associative memory (A, z) and
# SSM carry (h, conv). This — plus an in-segment position — is everything a
# segment-boundary snapshot needs: KV caches are segment-local (reset at every
# flush) and so are empty at a boundary by construction. The serving state
# store (serve/state_store.py) and the decode-state transplant both key off
# this list, so it lives here next to the memory math.
RECURRENT_KEYS = ("A", "z", "h", "conv")


def recurrent_state(state: Dict) -> Dict:
    """Project an executor/decode state tree onto its recurrent leaves.

    state: {'prelude': tuple of per-layer dicts, 'pattern': tuple of stacked
    dicts} (extra keys like 'pos' or caches are ignored). Returns the same
    structure with only RECURRENT_KEYS kept per layer — the constant-size
    summary of the whole prefix that makes segment-granular prefix caching
    kilobytes instead of a KV-cache's gigabytes."""
    def keep(d: Dict) -> Dict:
        return {k: d[k] for k in RECURRENT_KEYS if k in d}
    return {"prelude": tuple(keep(d) for d in state["prelude"]),
            "pattern": tuple(keep(d) for d in state["pattern"])}


def dpfp(x: jax.Array, nu: int = 3) -> jax.Array:
    """Deterministic Parameter-Free Projection (Schlag et al. 2021).

    x: [..., d]  ->  [..., 2*nu*d], elementwise non-negative.
    """
    r = jnp.concatenate([jax.nn.relu(x), jax.nn.relu(-x)], axis=-1)  # [..., 2d]
    parts = [r * jnp.roll(r, shift=j, axis=-1) for j in range(1, nu + 1)]
    return jnp.concatenate(parts, axis=-1)


def d_phi(acfg: ARMTConfig) -> int:
    return 2 * acfg.nu * acfg.d_mem


def mem_param_init(key: jax.Array, d_model: int, acfg: ARMTConfig,
                   dtype=jnp.float32) -> Dict[str, jax.Array]:
    d_val = acfg.d_val or d_model
    kq, kk, kv, kb = jax.random.split(key, 4)
    s = d_model ** -0.5
    return {
        "wq": (jax.random.normal(kq, (d_model, acfg.d_mem)) * s).astype(dtype),
        "wk": (jax.random.normal(kk, (d_model, acfg.d_mem)) * s).astype(dtype),
        "wv": (jax.random.normal(kv, (d_model, d_val)) * s).astype(dtype),
        "wb": (jax.random.normal(kb, (d_model, 1)) * s).astype(dtype),
    }


def state_dtype(x_dtype) -> jnp.dtype:
    """Memory state is kept at >= fp32 (fp64 under x64 for exactness tests)."""
    return jnp.result_type(x_dtype, jnp.float32)


def mem_state_init(batch: int, d_model: int, acfg: ARMTConfig,
                   dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Zero state (eq 3: A_0 = 0, z_0 = 0)."""
    d_val = acfg.d_val or d_model
    dt = state_dtype(dtype)
    return {
        "A": jnp.zeros((batch, d_phi(acfg), d_val), dt),
        "z": jnp.zeros((batch, d_phi(acfg)), dt),
    }


def mem_read(params: Dict[str, jax.Array], state: Dict[str, jax.Array],
             x: jax.Array, acfg: ARMTConfig) -> jax.Array:
    """Associative read (eq 6). x: [B, T, D] -> [B, T, d_val] (fp32+ math)."""
    dt = state_dtype(x.dtype)
    q = jnp.einsum("btd,dm->btm", x.astype(dt), params["wq"].astype(dt))
    pq = dpfp(q, acfg.nu)                                        # [B,T,P]
    num = jnp.einsum("btp,bpv->btv", pq, state["A"])
    den = jnp.einsum("btp,bp->bt", pq, state["z"]) + EPS
    return (num / den[..., None]).astype(x.dtype)


def mem_update(params: Dict[str, jax.Array], state: Dict[str, jax.Array],
               m: jax.Array, acfg: ARMTConfig) -> Dict[str, jax.Array]:
    """Delta-rule update (eqs 3-5). m: [B, M, D] memory-token layer outputs."""
    dt = state_dtype(m.dtype)
    m32 = m.astype(dt)
    k = jnp.einsum("bmd,de->bme", m32, params["wk"].astype(dt))
    v = jnp.einsum("bmd,dv->bmv", m32, params["wv"].astype(dt))
    beta = jax.nn.sigmoid(
        jnp.einsum("bmd,do->bmo", m32, params["wb"].astype(dt)))[..., 0]
    pk = dpfp(k, acfg.nu)                                        # [B,M,P]
    zk = jnp.einsum("bmp,bp->bm", pk, state["z"])                # z^T phi(k)
    vbar = jnp.einsum("bmp,bpv->bmv", pk, state["A"]) / (zk + EPS)[..., None]
    gamma = jnp.clip(1.0 - zk / (jnp.sum(pk * pk, axis=-1) + EPS), 0.0, 1.0)
    A_new = state["A"] + jnp.einsum("bm,bmv,bmp->bpv", beta, v - vbar, pk)
    z_new = state["z"] + jnp.einsum("bm,bmp->bp", gamma, pk)
    return {"A": A_new, "z": z_new}
