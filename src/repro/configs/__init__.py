"""Architecture configs and input-shape registry.

Every assigned architecture has its own module ``<id>.py`` exporting ``CONFIG``.
``get_config(arch_id)`` resolves ids like ``"qwen2.5-32b"``; ``get_smoke_config``
returns a reduced same-family config for CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Config dataclasses
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int              # per-expert FFN hidden size
    d_shared: int = 0          # shared-expert FFN hidden size (0 = no shared expert)
    capacity_factor: float = 1.25
    router_dtype: str = "float32"
    # 'global': one argsort over all tokens (exact capacity, but the sort
    # gathers across data shards under SPMD); 'per_row': dispatch per batch
    # row — fully local under batch sharding (GSPMD-MoE 'groups' semantics)
    dispatch: str = "global"


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0           # 0 -> ceil(d_model / 16)


@dataclass(frozen=True)
class ARMTConfig:
    """Associative Recurrent Memory Transformer (paper eqs. 3-6)."""
    segment_len: int = 1024    # tokens per segment (paper's main config)
    num_mem_tokens: int = 128  # memory tokens appended per segment
    d_mem: int = 64            # key dim before DPFP (phi maps to 2*nu*d_mem)
    d_val: int = 0             # value dim of A; 0 -> d_model
    nu: int = 3                # DPFP order (DPFP-3 in the paper)


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for enc-dec archs (whisper). Frontend is a stub: the
    input spec provides precomputed frame embeddings (B, n_frames, d_model)."""
    n_layers: int
    n_frames: int = 1500


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                # dense | moe | hybrid | ssm | audio | vlm
    n_layers: int              # total decoder/backbone layers
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int                  # dense FFN hidden (0 for attn-free archs)
    vocab: int
    d_head: int = 0            # 0 -> d_model // n_heads
    # Layer-stack structure: n_prelude 'prelude' layers of type prelude_type,
    # then block_pattern repeated n_superblocks times.
    block_pattern: Tuple[str, ...] = ("attn",)
    prelude: Tuple[str, ...] = ()       # e.g. kimi's single leading dense layer
    norm: str = "rmsnorm"      # rmsnorm | layernorm
    act: str = "silu"          # silu | gelu
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0  # chatglm applies rotary to half the head dims
    use_rope: bool = True       # whisper decoder uses learned positions instead
    sliding_window: int = 0     # 0 = full causal attention
    tie_embeddings: bool = False
    prelude_d_ff: int = 0       # dense FFN size for prelude layers (kimi)
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    armt: Optional[ARMTConfig] = None   # None -> pure full attention
    encoder: Optional[EncoderConfig] = None
    max_position: int = 131072
    dtype: str = "bfloat16"
    remat: str = "full"        # none | dots | full
    # dense | chunked (flash-style online softmax) | pallas (the flash and
    # decode kernels; interpret mode only with kernel_backend=
    # 'pallas_interpret')
    attn_impl: str = "dense"
    # Diagonal-executor grouped-block implementation: 'vmap' applies the
    # scalar block per slot via jax.vmap (exactness oracle, autodiff-safe);
    # 'fused' launches the grouped Pallas kernels over the whole group
    # (models/grouped_blocks.py; forward/inference fast path).
    grouped_impl: str = "vmap"  # vmap | fused
    # Kernel lowering for the fused path's op calls (kernels/dispatch.py):
    # 'auto' lets the resolver pick per backend (autotune cache, then the
    # heuristic table — XLA-native on CPU, Pallas on TPU/GPU); 'xla' and
    # 'pallas' force the implementation; 'pallas_interpret' forces the
    # kernel bodies under interpret-mode lowering (CPU validation).
    kernel_backend: str = "auto"  # auto | xla | pallas | pallas_interpret
    # Blockwise segment cells (DESIGN.md §15): query-block size for the
    # intra-cell FFN so per-cell activation peaks are O(cell_block·d_ff)
    # instead of O(T·d_ff) (BPT-style; attention already blocks via
    # attn_impl='chunked' / the dispatch resolver's causal_blocks). 0 (the
    # default) keeps the unblocked path — blocked accumulation can differ
    # in ulps, so the bit-exactness oracles stay on 0.
    cell_block: int = 0
    source: str = ""           # provenance note

    @property
    def head_dim(self) -> int:
        return self.d_head or (self.d_model // self.n_heads)

    @property
    def n_superblocks(self) -> int:
        body = self.n_layers - len(self.prelude)
        assert body % len(self.block_pattern) == 0, (
            f"{self.name}: {body} layers do not tile by pattern {self.block_pattern}")
        return body // len(self.block_pattern)

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """Flat per-layer type list (prelude + pattern * n_superblocks)."""
        return tuple(self.prelude) + tuple(self.block_pattern) * self.n_superblocks

    @property
    def is_recurrent(self) -> bool:
        """True if every layer carries layer-local recurrent state (PRMT family)."""
        return self.armt is not None or all(
            t.startswith("mamba") for t in self.layer_types)

    def validate(self) -> None:
        assert self.d_model > 0 and self.n_layers > 0 and self.vocab > 0
        assert self.grouped_impl in ("vmap", "fused"), self.grouped_impl
        assert self.kernel_backend in (
            "auto", "xla", "pallas", "pallas_interpret"), self.kernel_backend
        assert self.cell_block >= 0, self.cell_block
        if any(t.startswith("attn") or t.startswith("dec") or t.startswith("enc")
               for t in self.layer_types):
            assert self.n_heads > 0 and self.n_kv_heads > 0
            assert self.n_heads % self.n_kv_heads == 0
        if any(t.endswith("moe") for t in self.layer_types):
            assert self.moe is not None
        if any(t.startswith("mamba") for t in self.layer_types):
            assert self.ssm is not None
        _ = self.n_superblocks  # asserts pattern tiling


# ---------------------------------------------------------------------------
# Input shapes (assigned; identical set for every LM arch)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_ARCH_MODULES = {
    "h2o-danube-1.8b": "h2o_danube_1_8b",
    "qwen2.5-32b": "qwen2_5_32b",
    "minitron-8b": "minitron_8b",
    "chatglm3-6b": "chatglm3_6b",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "whisper-medium": "whisper_medium",
    "chameleon-34b": "chameleon_34b",
    # The paper's own model family (Llama-3 + ARMT)
    "llama-160m-armt": "llama_armt",
    "llama-1b-armt": "llama_armt",
    "llama-3b-armt": "llama_armt",
    "llama-8b-armt": "llama_armt",
}

ASSIGNED_ARCHS = [
    "h2o-danube-1.8b", "qwen2.5-32b", "minitron-8b", "chatglm3-6b",
    "kimi-k2-1t-a32b", "qwen2-moe-a2.7b", "jamba-1.5-large-398b",
    "falcon-mamba-7b", "whisper-medium", "chameleon-34b",
]


def get_config(arch_id: str) -> ArchConfig:
    mod_name = _ARCH_MODULES.get(arch_id)
    if mod_name is None:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    if hasattr(mod, "CONFIGS"):
        cfg = mod.CONFIGS[arch_id]
    else:
        cfg = mod.CONFIG
    cfg.validate()
    return cfg


def list_archs():
    return list(_ARCH_MODULES)


# ---------------------------------------------------------------------------
# Smoke-test reduction: same family, tiny dims, runnable on 1 CPU core.
# ---------------------------------------------------------------------------

def get_smoke_config(arch_id: str, *, seq_len: int = 64) -> ArchConfig:
    cfg = get_config(arch_id)
    n_pattern = len(cfg.block_pattern)
    # two superblocks (cross-superblock recurrence coverage) unless the
    # pattern itself is long (jamba: 8 heterogeneous layers) — one repeat of
    # a long pattern already exercises every block type, and doubling it
    # used to make that single arch dominate the tier-1 wall-clock
    n_sb = 1 if n_pattern >= 4 else 2
    n_layers = len(cfg.prelude) + n_sb * n_pattern
    armt = None
    if cfg.armt is not None:
        armt = replace(cfg.armt, segment_len=max(8, seq_len // 4),
                       num_mem_tokens=4, d_mem=8, d_val=0)
    moe = None
    if cfg.moe is not None:
        moe = replace(cfg.moe, n_experts=4, top_k=min(2, cfg.moe.top_k),
                      d_expert=32, d_shared=(32 if cfg.moe.d_shared else 0))
    ssm = None
    if cfg.ssm is not None:
        ssm = replace(cfg.ssm, d_state=4, d_conv=4, expand=2)
    enc = None
    if cfg.encoder is not None:
        enc = replace(cfg.encoder, n_layers=2, n_frames=16)
    return replace(
        cfg,
        n_layers=n_layers,
        d_model=32,
        n_heads=4,
        n_kv_heads=max(1, min(cfg.n_kv_heads, 2)),
        d_head=8,
        d_ff=(64 if cfg.d_ff else 0),
        prelude_d_ff=(64 if cfg.prelude_d_ff else 0),
        vocab=256,
        armt=armt, moe=moe, ssm=ssm, encoder=enc,
        max_position=max(2048, seq_len),
        dtype="float32",
        remat="none",
    )
