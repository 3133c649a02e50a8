"""Backend dispatch for every kernel entry point (DESIGN.md §14).

One resolver decides, per op call, *which implementation* runs (Pallas
kernel vs. XLA-native jnp oracle) and *with which tuning config* (block
sizes, interpret-mode lowering, epilogue fusion) — so ``forward_hidden``,
the fused grouped-block path, and the serving scheduler's pooled launches
all make the same decision through one table instead of scattered
backend checks.

Resolution order (``resolve``):

1. explicit per-call override (``use_kernel`` / ``interpret`` kwargs — the
   historical ops.py convention, kept verbatim so tests can force the
   kernel bodies in interpret mode on CPU);
2. the autotune cache — winners measured offline by
   :mod:`repro.kernels.autotune`, keyed ``(backend, op, shape-bucket,
   dtype)`` and persisted to disk (``set_cache_path`` /
   ``REPRO_KERNEL_CACHE``);
3. the static per-backend heuristic table (``HEURISTICS``) — the
   cold-start default: CPU dispatches to XLA-native (the jnp oracle beats
   pallas-interpret by orders of magnitude there), TPU/GPU dispatch to the
   Pallas kernels with MXU/SM-sized blocks.

``resolve`` is called at *trace time* (the ops wrappers run inside jit),
so it must stay pure-static: a dict lookup, no timing, no device work.
Sweeps happen strictly offline in autotune.py. Each resolution increments
``kernel_dispatch_total{op,impl,backend,source}`` in the serve-stack
metrics registry — once per compiled specialization, which is exactly the
cardinality a dispatch counter should have. ``impl`` is ``xla``,
``pallas`` (compiled Mosaic kernel) or ``pallas_interpret``.

Shape bucketing: every dim is rounded up to a power of two, so one tuned
config serves e.g. all of M in (65..128] — the diagonal executor's grouped
shapes span three orders of magnitude (1-token decode cells to 1M-token
prefill), and exact-shape keys would never hit.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, Iterable, Optional, Tuple

import jax

OPS = (
    "grouped_matmul",
    "grouped_matmul_armt_update",
    "flash_attention",
    "decode_attention",
    "armt_read",
    "armt_update",
    "mamba_scan",
)

BACKENDS = ("cpu", "gpu", "tpu", "interpret")


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One dispatch decision: implementation + tuning knobs.

    ``impl`` picks the lowering: ``"xla"`` = the jnp oracle in
    kernels/ref.py (XLA fuses it natively — the CPU fast path and the
    autodiff path), ``"pallas"`` = the hand-tiled kernel.

    Block fields are 0 when unused by the op or "kernel default"; each
    ops.py wrapper forwards only the fields its kernel accepts
    (``blocks()``). ``fuse_epilogue`` gates the ARMT-update-into-GEMM
    fusion (grouped_matmul_armt_update) — tunable because the fused
    kernel constrains tiling (full-width N) and can lose on some shapes.
    """
    impl: str = "xla"            # xla | pallas
    interpret: bool = False      # pallas: interpret-mode (CPU validation)
    block_m: int = 0
    block_n: int = 0
    block_k: int = 0
    block_q: int = 0
    block_t: int = 0
    block_v: int = 0
    block_i: int = 0
    fuse_epilogue: bool = True
    # flash_attention, xla impl only: unnormalized-softmax lowering (divide
    # the value-matmul output instead of the score-sized probability
    # tensor). Reassociates the normalizer, so it is never part of the
    # exactness-oracle config (use_kernel=False) — only heuristics and
    # autotuned winners may switch it on; bit-validation against the
    # oracle is tests/test_kernel_dispatch.py.
    fast_softmax: bool = False
    # flash_attention, xla impl only: split the causal square into query
    # halves and skip the fully-masked upper-right score quadrant (exact —
    # the skipped softmax terms are hard zeros). 0 = off.
    causal_blocks: int = 0

    def blocks(self, *names: str) -> Dict[str, int]:
        """The requested block fields that are set (nonzero)."""
        out = {}
        for n in names:
            v = getattr(self, n)
            if v:
                out[n] = v
        return out

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "KernelConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


XLA = KernelConfig(impl="xla")
PALLAS = KernelConfig(impl="pallas")
PALLAS_INTERPRET = KernelConfig(impl="pallas", interpret=True)


def backend() -> str:
    """The active JAX backend as a heuristic-table key. A backend the table
    does not know is an error: mapping it to another key would dispatch
    lowerings chosen for a different device."""
    b = jax.default_backend()
    if b not in ("cpu", "gpu", "tpu"):
        raise RuntimeError(f"kernel dispatch has no table for JAX backend "
                           f"{b!r} (known: cpu, gpu, tpu)")
    return b


# ---------------------------------------------------------------------------
# Cold-start heuristic table
# ---------------------------------------------------------------------------
# (backend, op) -> KernelConfig. Unlisted (backend, op) pairs fall back to
# the backend default: cpu -> XLA, tpu/gpu -> PALLAS with kernel defaults,
# interpret -> PALLAS_INTERPRET. Kernel-default block sizes live in the
# kernel signatures (grouped_matmul.py etc.); entries here override them
# where the generic default is known-bad for a backend.

_BACKEND_DEFAULT = {
    "cpu": XLA,
    "gpu": PALLAS,
    "tpu": PALLAS,
    "interpret": PALLAS_INTERPRET,
}

HEURISTICS: Dict[Tuple[str, str], KernelConfig] = {
    # TPU: MXU-native 128 lanes; deep K accumulation amortizes the revisit.
    ("tpu", "grouped_matmul"): KernelConfig(
        impl="pallas", block_m=128, block_n=128, block_k=512),
    ("tpu", "grouped_matmul_armt_update"): KernelConfig(
        impl="pallas", block_m=256, block_k=512),
    ("tpu", "flash_attention"): KernelConfig(
        impl="pallas", block_q=128, block_k=128),
    ("tpu", "decode_attention"): KernelConfig(impl="pallas", block_k=128),
    ("tpu", "armt_read"): KernelConfig(
        impl="pallas", block_t=256, block_v=512),
    ("tpu", "armt_update"): KernelConfig(impl="pallas", block_v=512),
    ("tpu", "mamba_scan"): KernelConfig(impl="pallas", block_i=512),
    # GPU: smaller K tiles (SMEM pressure), everything else kernel-default.
    ("gpu", "grouped_matmul"): KernelConfig(
        impl="pallas", block_m=64, block_n=128, block_k=64),
    ("gpu", "flash_attention"): KernelConfig(
        impl="pallas", block_q=64, block_k=64),
    ("gpu", "decode_attention"): KernelConfig(impl="pallas", block_k=128),
    # CPU: XLA-native everywhere — pallas-interpret is a validation tool,
    # not an execution engine (orders of magnitude slower than fused XLA).
    # Attention additionally takes the unnormalized-softmax lowering (one
    # fewer pass over the score-sized tensor) and the causal quadrant skip
    # — measurably faster on the memory-bound CPU backend
    # (EXPERIMENTS.md §Kernels).
    ("cpu", "flash_attention"): KernelConfig(
        impl="xla", fast_softmax=True, causal_blocks=4),
}


def heuristic(op: str, bk: Optional[str] = None) -> KernelConfig:
    bk = backend() if bk is None else bk
    return HEURISTICS.get((bk, op), _BACKEND_DEFAULT[bk])


# ---------------------------------------------------------------------------
# Autotune cache (disk-backed, loaded lazily, written by autotune.py)
# ---------------------------------------------------------------------------

_cache: Optional[Dict[str, KernelConfig]] = None
_cache_path: Optional[str] = None


def default_cache_path() -> str:
    """``REPRO_KERNEL_CACHE`` if set, else ``.kernel_cache.json`` at the
    checkout root (gitignored) — never a file outside the checkout, so a
    dispatch decision depends only on what the checkout holds."""
    from repro.utils import CHECKOUT_ROOT
    return os.environ.get("REPRO_KERNEL_CACHE",
                          str(CHECKOUT_ROOT / ".kernel_cache.json"))


def set_cache_path(path: Optional[str]) -> None:
    """Point the dispatch layer at a cache file (None -> default path).
    Drops the in-memory table so the next resolve reloads."""
    global _cache_path, _cache
    _cache_path = path
    _cache = None


def _load_cache() -> Dict[str, KernelConfig]:
    global _cache
    if _cache is None:
        path = _cache_path or default_cache_path()
        _cache = {}
        if os.path.exists(path):
            # a file that does not parse is an error, not an empty cache:
            # silently dropping it would change every dispatch decision
            with open(path) as f:
                try:
                    raw = json.load(f)
                    _cache = {k: KernelConfig.from_json(v)
                              for k, v in raw["configs"].items()}
                except (ValueError, TypeError, KeyError, AttributeError) as e:
                    raise ValueError(
                        f"corrupt kernel autotune cache {path}: {e!r}; "
                        "delete it or re-run the autotuner") from e
    return _cache


def store_config(key: str, cfg: KernelConfig, persist: bool = True) -> None:
    """Install an autotuned winner (autotune.py's write path)."""
    cache = _load_cache()
    cache[key] = cfg
    if persist:
        path = _cache_path or default_cache_path()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump({"configs": {k: v.to_json()
                                   for k, v in cache.items()}}, f, indent=1)


def cached_config(key: str) -> Optional[KernelConfig]:
    return _load_cache().get(key)


# ---------------------------------------------------------------------------
# Shape bucketing + cache keys
# ---------------------------------------------------------------------------

def _pow2(n: int) -> int:
    return 1 << (int(n) - 1).bit_length() if n > 1 else 1


def shape_bucket(shapes: Iterable[Tuple[int, ...]]) -> Tuple[Tuple[int, ...],
                                                             ...]:
    """Pow2-round every dim of every operand shape."""
    return tuple(tuple(_pow2(d) for d in s) for s in shapes)


def cache_key(bk: str, op: str, shapes: Iterable[Tuple[int, ...]],
              dtype) -> str:
    bucket = shape_bucket(shapes)
    bs = "x".join("_".join(map(str, s)) for s in bucket)
    return f"{bk}/{op}/{bs}/{jax.numpy.dtype(dtype).name}"


# ---------------------------------------------------------------------------
# The resolver
# ---------------------------------------------------------------------------

def _registry():
    # lazy: kernels must not import the serve stack at module load
    from repro.serve.telemetry import default_registry
    return default_registry()


def resolve(op: str, shapes: Iterable[Tuple[int, ...]], dtype, *,
            use_kernel: Optional[bool] = None,
            interpret: Optional[bool] = None,
            kernel_backend: Optional[str] = None) -> KernelConfig:
    """Pick the KernelConfig for one op call. Pure static — safe at trace
    time. ``use_kernel``/``interpret`` are the historical per-call
    overrides; ``kernel_backend`` is the config-level knob
    (ArchConfig.kernel_backend): 'auto' | 'xla' | 'pallas' |
    'pallas_interpret'."""
    assert op in OPS, op
    shapes = tuple(tuple(s) for s in shapes)
    if use_kernel is None and kernel_backend and kernel_backend != "auto":
        use_kernel = kernel_backend != "xla"
        if interpret is None and kernel_backend == "pallas_interpret":
            interpret = True
    if use_kernel is not None:
        if not use_kernel:
            cfg, source = XLA, "override"
        else:
            base = heuristic(op, "tpu" if backend() == "cpu" else backend())
            cfg = dataclasses.replace(base, impl="pallas",
                                      interpret=bool(interpret))
            source = "override"
    else:
        bk = backend()
        key = cache_key(bk, op, shapes, dtype)
        hit = cached_config(key)
        if hit is not None:
            cfg, source = hit, "cache"
        else:
            cfg, source = heuristic(op, bk), "heuristic"
    impl = ("pallas_interpret" if cfg.impl == "pallas" and cfg.interpret
            else cfg.impl)
    _registry().inc("kernel_dispatch_total", op=op, impl=impl,
                    backend=backend(), source=source)
    return cfg
