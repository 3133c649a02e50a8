"""Seeded weights for a benchmark configuration, made on the device in one
jitted call.

The tree has the program's parameter layout (taken from
``jax.eval_shape`` of its ``init_params``: shapes and dtypes only); every
value comes from here. The scales are the configuration file's
``weights`` section:

* projections: normal with std fan_in**-0.5 (fan_in = the leaf's second
  to last dim), so every projection keeps its input's scale;
* ``embed`` and ``mem_tokens``: normal with the given std;
* biases: normal with std ``bias`` (nonzero, so a dropped bias shows);
* norm weights: ones;
* the memory's value projection ``mem.wv``: fan_in**-0.5 times
  ``mem_wv_gain``. The memory feeds every layer's residual stream and its
  own next write, so the recurrence across segments amplifies what a
  rounding changes, the more the more memory tokens write. On one TPU v5e
  at h2o-danube-1.8b's widths (segments of 1024 + 128 memory tokens), the
  served tokens' gap to the float32 reference after 16 segments is 1.02 at
  gain 0.005 and at most 0.04 at gain 0.001 up to 64 segments, where the
  memory still moves the logits by 15-19% of their norm (PERF.md,
  Findings).

The same seed gives the same weights: the reference regenerates them
instead of taking the program's arrays.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    k = jax.random.key(stream)
    seed = int(seed)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        k = jax.random.fold_in(k, np.uint32(word & 0xFFFFFFFF))
    return k


def _names(path) -> Tuple[str, ...]:
    out = []
    for p in path:
        out.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return tuple(out)


def plan(shapes, spec: Dict) -> List[Tuple[str, float]]:
    """(kind, scale) per leaf of ``shapes`` in flattening order; kind is
    'normal' or 'ones'."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = _names(path)
        name = names[-1]
        parent = names[-2] if len(names) > 1 else ""
        if name in ("embed", "mem_tokens"):
            out.append(("normal", float(spec[name])))
        elif name == "w" and parent.startswith(("ln", "final_norm", "qn",
                                                "kn")):
            out.append(("ones", 1.0))
        elif name.startswith("b") and name != "wb" and leaf.ndim <= 2:
            out.append(("normal", float(spec["bias"])))
        elif leaf.ndim >= 2:
            s = float(leaf.shape[-2]) ** -0.5
            if parent == "mem" and name == "wv":
                s *= float(spec["mem_wv_gain"])
            out.append(("normal", s))
        else:
            raise ValueError(f"no weight rule for leaf {'/'.join(names)} "
                             f"of shape {leaf.shape}")
    return out


def make(shapes, spec: Dict, seed: int):
    """The weights for ``shapes`` (a tree of ShapeDtypeStruct) from
    ``seed``, in the leaves' own dtypes, made by one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    rules = plan(shapes, spec)

    def gen(key):
        out = []
        for i, (leaf, (kind, s)) in enumerate(zip(leaves, rules)):
            if kind == "ones":
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            else:
                z = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                      jnp.float32)
                out.append((z * s).astype(leaf.dtype))
        return out

    out = jax.jit(gen)(seed_key(seed, 1))
    return jax.tree_util.tree_unflatten(treedef, out)
