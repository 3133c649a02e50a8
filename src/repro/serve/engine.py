"""Batched long-context serving engine.

Prefill uses the *diagonal* schedule over full segments (the paper's win:
one long request keeps the GPU/TPU busy without cross-request batching),
then transplants the executor's per-layer memory states into the decode
state; the prompt tail and new tokens run through `decode_step`, with ARMT
segment flushes at segment boundaries (constant memory in context length).

Decode runs entirely on device: a `jax.lax.scan` over steps with the state
donated to the jitted loop, segment flushes folded in as a `lax.cond`, and
greedy/temperature/top-k sampling applied to the logits on device — the
host sees tokens once per `generate` call (zero per-token device->host
transfers), not once per token.

Prompt shapes are *bucketed* (on by default): full segments run in
descending power-of-two groups with the executor state threaded through,
and sub-segment tails feed `decode_step` in descending power-of-two
chunks — so the engine compiles O(log) distinct prefill shapes instead of
one per prompt length the scheduler ever sees. Bucketing is pure
re-chunking of the exact same tokens (never padding), so it is
token-identical to the unbucketed path by construction (tested).

The engine optionally carries a serving state store (serve/state_store.py):
a segment-granular `PrefixCache` (longest-prefix match at admission, so
only uncached tail segments are prefilled) and a `SessionStore` (multi-turn
resume via `generate(..., session_id=...)` — O(new turn) instead of
re-prefilling the conversation).

The whole stack is *mesh-native* (DESIGN.md §10): `ServeEngine(mesh=...)`
derives placement from `parallel/sharding.py` rules — params over 'model'
(TP, stacked pattern optionally over 'stage'), the diagonal prefill's slot
buffer pipeline-sharded via `slot_buf_spec`, decode state with batch/slots
over the DP axes and heads/d_model over 'model' — and every jitted graph
(`decode_step`, `flush_segment`, the whole-decode `lax.scan`, the
scheduler's packed chunk/admission/extract) stays a single program with
GSPMD inserting the collectives. State-store blobs cross the mesh boundary
host-portable (gather-on-capture in the store, `_place_state`
scatter-on-restore here), so snapshots resume across different mesh shapes.

Prefill is also available as a *resumable pipeline* (DESIGN.md §11):
`start_prefill` returns a `PrefillPipeline` whose `advance()` runs one
bounded unit — `prefill_groups_per_chunk` anti-diagonal groups via the
jitted `prefill_step` stepper (carry donated), or one tail `decode_step`
piece — so the continuous-batching scheduler interleaves a new request's
admission with decode chunks instead of blocking every slot for the whole
prompt. The pipeline shares the one-shot executor's step body bit for bit
and `_prefill`'s stage/piece decomposition, so it is token-identical
(greedy) to the blocking path by construction.

Multi-request continuous batching lives in `serve/scheduler.py`; the
`ServeEngine.serve(requests)` iterator is the streaming front door.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Dict, Iterable, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig
from repro.core import diagonal as diag
from repro.core.memory import RECURRENT_KEYS
from repro.core.schedule import StackLayout, n_diagonal_groups
from repro.models import (boundary_logits, decode_state_init,
                          decode_state_sharding, decode_step, embed_segments,
                          flush_segment, forward_hidden, init_state,
                          last_logits)
from repro.parallel import sharding as shd
from repro.serve.telemetry import Telemetry


def _transplant(fin: Dict, dstate: Dict) -> Dict:
    """Copy recurrent state (RECURRENT_KEYS: A/z/h/conv) from an executor
    final state or boundary snapshot into the decode state (which
    additionally holds kv caches and pos)."""
    def merge_one(src: Dict, dst: Dict) -> Dict:
        out = dict(dst)
        for k in RECURRENT_KEYS:
            if k in src:
                out[k] = src[k].astype(dst[k].dtype) if hasattr(dst.get(k), "dtype") else src[k]
        return out

    new_prelude = tuple(merge_one(s, d) for s, d in
                        zip(fin["prelude"], dstate["prelude"]))
    new_pattern = tuple(merge_one(s, d) for s, d in
                        zip(fin["pattern"], dstate["pattern"]))
    return {"prelude": new_prelude, "pattern": new_pattern,
            "pos": dstate["pos"]}


def _pow2_chunks(n: int) -> List[int]:
    """Descending power-of-two decomposition of n (13 -> [8, 4, 1]) — the
    length buckets that keep prefill compile counts logarithmic."""
    out = []
    while n > 0:
        p = 1 << (n.bit_length() - 1)
        out.append(p)
        n -= p
    return out


@dataclass
class GenerationResult:
    tokens: np.ndarray          # [B, max_new]
    prefill_segments: int
    schedule: str
    # serving metrics (host-clock; decode is one device call, so TTFT is the
    # prefill/admission wall time — the quantity prefix caching attacks)
    ttft_s: float = 0.0
    tok_s: float = 0.0          # decode throughput after first token
    cached_segments: int = 0    # segments transplanted from the prefix cache
    session_id: Optional[str] = None
    resumed: bool = False       # True when restored from the session store
    # queue-wait breakdown, mirroring StreamEvent (DESIGN.md §12): direct
    # generate() calls never queue, so these stay at their idle defaults —
    # they exist so result records from both front doors aggregate uniformly
    queue_wait_s: float = 0.0
    concurrent_admissions: int = 1
    # telemetry snapshot at result time (DESIGN.md §13): the engine's
    # metrics registry — compile counts, store stats, serving histograms —
    # as a JSON-able dict; None when the engine's telemetry is disabled
    metrics: Optional[Dict] = None


class ServeEngine:
    """Compile-once engine for a fixed (batch, prompt_len, max_new) shape.

    serve_mode 'armt': constant-memory decode (paper Fig. 1); 'cache':
    standard full-KV decoding for the baseline comparison.

    prefix_cache / session_store: optional serving state stores
    (serve/state_store.py). The prefix cache needs serve_mode='armt' (its
    snapshots are the constant-size recurrent memory; a 'cache'-mode prefix
    would be the full KV tensor — exactly what the RMT lets us avoid).

    mesh: optional `jax.sharding.Mesh` — the engine becomes mesh-native
    (DESIGN.md §10): params are device_put to `parallel/sharding.py` specs
    (TP over 'model', stacked pattern over 'stage' when present), decode
    states to `decode_state_sharding` (batch/slots over DP axes), and the
    diagonal prefill runs with `slot_buf_spec` pipeline sharding. All
    decode/serve math is unchanged — GSPMD derives the collectives from the
    argument placements, so sharded serving is token-identical (greedy) to
    the single-device engine (tests/test_serve_sharded.py).
    """

    def __init__(self, params, cfg: ArchConfig, *, serve_mode: str = "armt",
                 schedule: str = "diagonal", max_len: int = 8192,
                 grouped_impl: Optional[str] = None,
                 prefix_cache=None, session_store=None,
                 bucket_prompts: bool = True, mesh=None,
                 telemetry: Optional[Telemetry] = None):
        if serve_mode not in ("armt", "cache"):
            raise ValueError(f"unknown serve_mode {serve_mode!r}")
        if serve_mode == "armt" and cfg.armt is None and not cfg.is_recurrent:
            # used to silently fall back to seg_len=1024: attention layers
            # then never flush and segments of the prefill are disconnected
            # contexts — constant-memory serving is simply undefined here
            raise ValueError(
                f"serve_mode='armt' needs recurrent layer state, but "
                f"{cfg.name} has cfg.armt=None and non-SSM layers — pass "
                "serve_mode='cache' for full-KV decoding or add an "
                "ARMTConfig to the arch")
        self.cfg = cfg
        self.serve_mode = serve_mode
        self.schedule = schedule
        self.max_len = max_len
        # 'fused' routes diagonal prefill through the grouped Pallas kernels
        # (models/grouped_blocks.py); None defers to cfg.grouped_impl.
        self.grouped_impl = grouped_impl
        # pure-SSM archs have no segment boundaries: state carries across
        # arbitrary chunk sizes, so 'one chunk' (max_len) replaces the old
        # silent seg_len=1024 fallback
        self.seg_len = cfg.armt.segment_len if cfg.armt else max_len
        if prefix_cache is not None:
            if serve_mode != "armt":
                raise ValueError(
                    "prefix_cache needs serve_mode='armt' — its snapshots "
                    "are the constant-size recurrent memory at segment "
                    "boundaries, which full-KV 'cache' mode does not have")
            if prefix_cache.seg_len != self.seg_len:
                raise ValueError(
                    f"prefix_cache.seg_len {prefix_cache.seg_len} != engine "
                    f"segment length {self.seg_len}: boundary hashes would "
                    "never match this engine's prefill boundaries")
        self.prefix_cache = prefix_cache
        self.session_store = session_store
        self.bucket_prompts = bucket_prompts
        self.mesh = mesh
        self.stacked_axis = (
            "stage" if mesh is not None and "stage" in mesh.axis_names
            else None)
        if mesh is not None:
            # params committed to their TP/stage shardings once, up front —
            # every jitted graph below then inherits the placement and GSPMD
            # inserts the collectives (no per-call resharding)
            pspecs = shd.param_specs(params, mesh,
                                     stacked_axis=self.stacked_axis)
            params = jax.device_put(params, pspecs)
        self.params = params
        self._n_layers = StackLayout.from_config(cfg).n_layers
        def tail_step(p, s, t):
            # the admissions' tail pieces (and a session resume's turn)
            with jax.named_scope("serve.tail_step"):
                return decode_step(p, cfg, s, t, serve_mode=serve_mode)

        self._step = jax.jit(tail_step)
        self._flush = jax.jit(
            lambda p, s: flush_segment(p, cfg, s)) if cfg.armt else None
        self._loops: Dict = {}    # (max_new, greedy, top_k) -> jitted loop
        self._sched_fns: Dict = {}   # chunk -> jitted scheduler fns (shared
        #                              across serve() calls / slot counts)
        self._pipe_steps: Dict = {}  # (S, B, capture, k) -> jitted
        #                              prefill_step (resumable pipeline §11)
        self._fused_fns: Dict = {}   # (chunk, S, capture, k) -> fused
        #                              decode-chunk + prefill-step program
        #                              (and pooled variants, §12 — keyed
        #                              ('pool', chunk, bucket-signatures))
        self._pool_steps: Dict = {}  # (S, B, capture, k, n_pool) -> jitted
        #                              pooled stepper (admission pool §12)
        self._mem_stats: Dict = {}   # (S, B, capture, stream, k) -> compiled
        #                              prefill memory_analysis (§15 budget)
        # observability (DESIGN.md §13): metrics into the process default
        # registry unless told otherwise; spans only when a recorder was
        # asked for. Host-side only — never adds a device sync.
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        reg = self.telemetry.registry
        if reg is not None:
            # probes are sampled at snapshot time, so compile counts and
            # store stats are always current with zero per-chunk bookkeeping
            reg.register_probe("engine_compile_counts", self.compile_counts)
            if prefix_cache is not None:
                reg.register_probe(
                    "prefix_cache", lambda: self.prefix_cache.stats.as_dict())
            if session_store is not None:
                reg.register_probe(
                    "session_store",
                    lambda: self.session_store.stats.as_dict())

    # ------------------------------------------------------------------
    # Observability (DESIGN.md §13)
    # ------------------------------------------------------------------

    def compile_counts(self) -> Dict[str, int]:
        """Compiled-program counts per jit-cache kind, from the jitted
        functions' own trace caches — the per-signature ground truth behind
        the pow2-bucketing "O(log) compiles" claim (the registry's
        ``xla_backend_compiles_total`` counter cross-checks it at the XLA
        layer)."""
        def sz(fn):
            return fn._cache_size() if hasattr(fn, "_cache_size") else 0

        counts = {
            "decode_step": sz(self._step),
            "flush": sz(self._flush) if self._flush is not None else 0,
            "decode_loops": sum(sz(f) for f in self._loops.values()),
            "scheduler_fns": sum(sz(f) for fns in self._sched_fns.values()
                                 for f in fns),
            "prefill_steps": sum(sz(f) for f in self._pipe_steps.values()),
            "fused": sum(sz(f) for f in self._fused_fns.values()),
            "pool_steps": sum(sz(f) for f in self._pool_steps.values()),
        }
        counts["total"] = sum(counts.values())
        return counts

    def metrics_snapshot(self) -> Dict:
        """Registry snapshot plus the engine's own probes flattened in —
        what ``launch/serve.py --metrics`` dumps and ``bench_serve.py``
        embeds into BENCH_serve.json."""
        snap = self.telemetry.snapshot() or {}
        snap["compile_counts"] = self.compile_counts()
        if self.prefix_cache is not None:
            snap["prefix_cache"] = self.prefix_cache.stats.as_dict()
        if self.session_store is not None:
            snap["session_store"] = self.session_store.stats.as_dict()
        return snap

    # ------------------------------------------------------------------
    # Mesh placement (DESIGN.md §10) — no-ops on a mesh-less engine
    # ------------------------------------------------------------------

    def state_sharding(self, batch: int, *, per_slot_pos: bool = False):
        """Decode-state NamedSharding tree for this engine's placement
        rules; None without a mesh."""
        if self.mesh is None:
            return None
        return decode_state_sharding(
            self.cfg, self.mesh, batch, serve_mode=self.serve_mode,
            max_len=self.max_len, dtype=self.params["embed"].dtype,
            per_slot_pos=per_slot_pos,
            stacked_axis=self.stacked_axis)

    def _place_state(self, tree, batch: int):
        """Scatter-on-restore: commit a decode/recurrent state tree (possibly
        host numpy out of a mesh-agnostic store blob) to this engine's
        shardings. The tree may be a sub-tree of a full decode state (e.g. a
        boundary snapshot without pos/kv) — specs are derived from the tree
        itself, so any {'prelude','pattern'} layout works.

        Always a *fresh* buffer, never the store's own arrays — load-bearing:
        on an exact full-prefix hit with no tail the transplanted leaves
        reach the decode loop unmodified, and that loop donates its state;
        without a fresh buffer, donation would delete the cache entry's
        arrays out from under the store and the next hit on the same prefix
        would transplant deleted arrays (GPU/TPU only; donation is skipped
        on CPU, so CPU tests can't catch it)."""
        if self.mesh is None:
            return jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True),
                                          tree)
        specs = shd.decode_state_specs(tree, self.mesh, batch,
                                       stacked_axis=self.stacked_axis)

        def one(a, s):
            # device_put can alias the input's buffers when the placement
            # already matches (including the zero-copy commit of an
            # uncommitted array) — copy device arrays first so the result
            # never shares storage with the store. Host numpy leaves skip
            # the copy: device_put from host always allocates fresh device
            # buffers.
            if isinstance(a, jax.Array):
                a = jnp.array(a, copy=True)
            return jax.device_put(a, s)

        return jax.tree_util.tree_map(one, tree, specs)

    def _slot_spec(self, batch: int):
        """Diagonal slot-buffer PartitionSpec for prefill on this mesh."""
        if self.mesh is None or self.schedule != "diagonal":
            return None
        return shd.slot_buf_spec(self.mesh, self._n_layers, batch)

    def prefill(self, prompts: jax.Array, enc_frames=None):
        """prompts: [B, P]. Returns (next_token_logits, decode_state)."""
        logits, dstate, _, _ = self._prefill(prompts, enc_frames=enc_frames)
        return logits, dstate

    # ------------------------------------------------------------------
    # Prefill: diagonal full segments (+ prefix cache) then bucketed tail
    # ------------------------------------------------------------------

    def _mesh_ctx(self):
        """Ambient-mesh context: the diagonal executor (and the pipeline
        stepper) constrain buffers with raw PartitionSpecs, which resolve
        against the ambient mesh — enter it around any prefill trace."""
        return self.mesh if self.mesh is not None else contextlib.nullcontext()

    def _forward(self, toks, exec_state, enc_frames, capture: bool):
        with self._mesh_ctx():
            return forward_hidden(
                self.params, self.cfg, toks, schedule=self.schedule,
                enc_frames=enc_frames, grouped_impl=self.grouped_impl,
                slot_spec=self._slot_spec(toks.shape[0]),
                init_state=exec_state, capture_states=capture)

    def _prefill(self, prompts: jax.Array, enc_frames=None):
        """prompts: [B, P]. Returns (next_token_logits, decode_state,
        in-segment pos, cached_segments)."""
        B, P = prompts.shape
        dtype = self.params["embed"].dtype
        dstate = decode_state_init(self.cfg, B, serve_mode=self.serve_mode,
                                   max_len=self.max_len, dtype=dtype)
        if self.mesh is not None:
            dstate = jax.device_put(dstate, self.state_sharding(B))
        n_full = P // self.seg_len if self.serve_mode == "armt" else 0
        logits = None
        cached = 0
        exec_state = None
        prompt_np = None
        # prefix caching is per-request (B=1 — the scheduler's admission
        # shape) and needs token-addressable segments, which encoder archs'
        # frame inputs are not
        use_cache = (self.prefix_cache is not None and B == 1
                     and enc_frames is None and n_full > 0)
        chain = None
        if use_cache:
            from repro.serve.state_store import prefix_hash_chain
            prompt_np = np.asarray(prompts[0], np.int32)
            chain = prefix_hash_chain(prompt_np, self.seg_len)
            with self.telemetry.span("prefix_probe", "cache",
                                     n_segments=n_full):
                cached, snap = self.prefix_cache.match(prompt_np, chain=chain)
            self.telemetry.inc("prefix_probe_total",
                               result="hit" if cached else "miss")
            if cached:
                exec_state = self._place_state(snap.state, B)
                dstate = _transplant(exec_state, dstate)
                logits = (jax.device_put(snap.logits, shd.replicated(self.mesh))
                          if self.mesh is not None
                          else jnp.asarray(snap.logits))
        rem = n_full - cached
        if rem > 0:
            groups = _pow2_chunks(rem) if self.bucket_prompts else [rem]
            off = cached
            fin = None
            for g in groups:
                toks_g = prompts[:, off * self.seg_len:(off + g) * self.seg_len]
                if use_cache:
                    hidden, fin, bstates = self._forward(
                        toks_g, exec_state, enc_frames, capture=True)
                    blogits = boundary_logits(self.params, self.cfg, hidden)
                    for c in range(g):
                        end = (off + c + 1) * self.seg_len
                        self.prefix_cache.insert(
                            prompt_np[:end],
                            jax.tree_util.tree_map(lambda a, _c=c: a[_c],
                                                   bstates),
                            blogits[c], key=chain[off + c])
                else:
                    hidden, fin = self._forward(toks_g, exec_state,
                                                enc_frames, capture=False)
                logits = last_logits(self.params, self.cfg, hidden)
                exec_state = fin
                off += g
            dstate = _transplant(fin, dstate)
        tail = prompts[:, n_full * self.seg_len:]
        pos = 0                       # host-side segment position (no sync)
        if tail.shape[1] > 0:
            logits, dstate, pos = self._chunk(dstate, tail, pos)
        assert logits is not None, "empty prompt"
        return logits, dstate, pos, cached

    def _chunk(self, dstate, toks, pos: int):
        """Feed a multi-token chunk, flushing at ARMT segment boundaries.
        With bucket_prompts, each piece is the largest power of two that
        fits before the next boundary — O(log seg_len) compiled shapes.
        Implemented as a loop over ``_tail_pieces`` — the same
        decomposition the resumable PrefillPipeline runs one piece per
        ``advance()``, so the blocking and interleaved tail paths cannot
        drift."""
        logits = None
        pieces, end_pos = _tail_pieces(self, toks.shape[1], pos)
        for (t, take, flush) in pieces:
            logits, dstate = self._step(self.params, dstate,
                                        toks[:, t:t + take])
            if flush:
                with self.telemetry.span("flush_segment", "flush",
                                         take=take):
                    dstate = self._flush(self.params, dstate)
        return logits, dstate, end_pos

    # ------------------------------------------------------------------
    # Resumable prefill pipeline (interleaved admission, DESIGN.md §11)
    # ------------------------------------------------------------------

    def exec_apply(self):
        """The serving executor's block application pair
        ``(apply_block, grouped_apply)`` — the ONE source of truth for the
        numerics-critical (mode='segmented', ssm_method='assoc',
        grouped_impl) combination that the blocking prefill inherits
        through ``forward_hidden``'s defaults. ``prefill_step`` and the
        scheduler's ``fused_fns`` both build their diagonal stages from
        this, so the interleaved==blocking bit-identity cannot be broken
        by one copy drifting.

        Kernel lowering rides the same single source of truth: the fused
        grouped_apply's op calls resolve their implementation + tuning
        config through ``kernels/dispatch.py`` (honoring
        ``cfg.kernel_backend``, the autotune cache, and the per-backend
        heuristic table), so the scheduler's pooled launches and
        ``forward_hidden`` dispatch through one resolver — the
        ``kernel_dispatch_total{op,impl,backend,source}`` counters land
        in this engine's metrics registry (it defaults to the process
        registry the resolver writes to)."""
        from repro.models.blocks import make_apply_block
        from repro.models.grouped_blocks import resolve_grouped_apply
        apply = make_apply_block(self.cfg, mode="segmented",
                                 ssm_method="assoc")
        gapply = resolve_grouped_apply(self.cfg, self.grouped_impl,
                                       mode="segmented", ssm_method="assoc",
                                       remat=self.cfg.remat != "none")
        return apply, gapply

    def prefill_step(self, n_segments: int, batch: int, capture: bool,
                     n_groups: int):
        """The jitted resumable-prefill stepper for a diagonal stage of
        ``n_segments`` segments: ``step(params, xs, carry) -> carry``
        advancing ``n_groups`` anti-diagonal groups per call. Bucketed like
        ``_prefill`` (stages are power-of-two segment groups, so the cache
        holds O(log) programs per group budget), capture-aware (the carry's
        ``cap`` buffers feed the prefix cache exactly like the blocking
        path), and mesh-aware (slot-buffer/state constraints identical to
        ``_forward``'s diagonal run).

        The carry argument is DONATED on backends that honor donation —
        callers must never pass arrays a store still owns (see
        PrefillPipeline's fresh-buffer note)."""
        key = (n_segments, batch, capture, n_groups)
        if key in self._pipe_steps:
            return self._pipe_steps[key]
        layout = StackLayout.from_config(self.cfg)
        apply, gapply = self.exec_apply()
        buf_spec = self._slot_spec(batch)

        def step(params, xs, carry):
            exec_params = {"prelude": params["prelude"],
                           "pattern": params["pattern"]}
            # named_scope: XLA profiles show these ops under a stable label
            # that matches the scheduler's host spans (DESIGN.md §13)
            with jax.named_scope("serve.diag_stage"):
                return diag.pipeline_step(layout, exec_params, xs, carry,
                                          apply, n_groups=n_groups,
                                          buf_spec=buf_spec,
                                          grouped_apply=gapply,
                                          remat=self.cfg.remat != "none",
                                          retain_pos=self.seg_len - 1)

        donate = (2,) if jax.default_backend() != "cpu" else ()
        self._pipe_steps[key] = jax.jit(step, donate_argnums=donate)
        return self._pipe_steps[key]

    def _pool_step_body(self, n_segments: int, batch: int, capture: bool,
                        n_groups: int, n_pool: int):
        """The pooled-stepper body as a pure (un-jitted) function
        ``(params, xs_tuple, carry_tuple) -> carry_tuple`` over ``n_pool``
        same-signature admission carries — the single source of truth
        shared by the standalone jitted stepper (``pool_prefill_step``)
        and the fused global-grid launch (scheduler.fused_pool_fns).

        Stacking/unstacking happens INSIDE the traced body (tuples in,
        tuples out): one dispatch per round, and each member's output is
        its own buffer — unstacked members never alias each other, so
        handing them back to their pipelines is donation-safe."""
        layout = StackLayout.from_config(self.cfg)
        apply, gapply = self.exec_apply()
        mesh, stacked_axis = self.mesh, self.stacked_axis
        del capture                       # implied by the carry structure

        def body(params, xs_tup, carry_tup):
            with jax.named_scope("serve.pooled_diag_round"):
                exec_params = {"prelude": params["prelude"],
                               "pattern": params["pattern"]}
                xs_pool = jax.tree_util.tree_map(
                    lambda *ls: jnp.stack(ls), *xs_tup)
                carry_pool = jax.tree_util.tree_map(
                    lambda *ls: jnp.stack(ls), *carry_tup)
                pool_spec = None
                if mesh is not None:
                    pool_spec = shd.pool_carry_specs(
                        carry_pool, mesh, layout.n_layers, batch,
                        stacked_axis=stacked_axis)
                carry_pool = diag.pipeline_step_pool(
                    layout, exec_params, xs_pool, carry_pool, apply,
                    n_groups=n_groups, grouped_apply=gapply,
                    pool_spec=pool_spec,
                    remat=self.cfg.remat != "none",
                    retain_pos=self.seg_len - 1)
                return tuple(
                    jax.tree_util.tree_map(lambda a, _i=i: a[_i], carry_pool)
                    for i in range(n_pool))

        return body

    def pool_prefill_step(self, n_segments: int, batch: int, capture: bool,
                          n_groups: int, n_pool: int):
        """The jitted pooled stepper (DESIGN.md §12): one launch advances
        ``n_pool`` same-signature admission carries by ``n_groups`` groups
        each. Pool sizes are pow2-bucketed by the caller (``pool_pack``),
        so the cache holds O(log N) programs per (S, capture, k) on top of
        the single-carry stepper's O(log) stage buckets.

        The carry tuple is DONATED on backends that honor donation — every
        entry (including pad members) must be fresh-buffered and pairwise
        non-aliased (see diag.pipeline_pool_pad)."""
        key = (n_segments, batch, capture, n_groups, n_pool)
        if key in self._pool_steps:
            return self._pool_steps[key]
        body = self._pool_step_body(n_segments, batch, capture, n_groups,
                                    n_pool)
        donate = (2,) if jax.default_backend() != "cpu" else ()
        self._pool_steps[key] = jax.jit(body, donate_argnums=donate)
        return self._pool_steps[key]

    def pool_pack(self, n_segments: int, group):
        """Pad a same-signature admission group — ``[(pipe, xs, carry),
        ...]`` — up to its pow2 pool bucket: returns ``(n_pool, xs_tuple,
        carry_tuple)`` with fresh zero no-op pad members (cursor parked
        past the grid, diag.pipeline_pool_pad)."""
        n = len(group)
        n_pool = 1 << (n - 1).bit_length() if n > 1 else 1
        xs_t = tuple(x for _, x, _ in group)
        carry_t = tuple(c for _, _, c in group)
        n_steps = n_diagonal_groups(n_segments, self._n_layers)
        for _ in range(n_pool - n):
            px, pc = diag.pipeline_pool_pad(xs_t[0], carry_t[0], n_steps)
            xs_t += (px,)
            carry_t += (pc,)
        return n_pool, xs_t, carry_t

    def pool_prefill_step_run(self, n_segments: int, capture: bool,
                              n_groups: int, group):
        """Advance every member of ``group`` (same (S, capture, k)
        signature, B=1 admissions) by ``n_groups`` diagonal groups in ONE
        jitted launch; returns the new carries in member order. The input
        carries are donated — callers must treat them as consumed and keep
        only the returned ones (AdmissionPool does)."""
        n_pool, xs_t, carry_t = self.pool_pack(n_segments, group)
        step = self.pool_prefill_step(n_segments, 1, capture, n_groups,
                                      n_pool)
        with self._mesh_ctx():
            out = step(self.params, xs_t, carry_t)
        return list(out[:len(group)])

    # ------------------------------------------------------------------
    # Admission memory accounting (DESIGN.md §15)
    # ------------------------------------------------------------------

    def prefill_activation_bytes(self, n_segments: int, batch: int = 1, *,
                                 stream: bool = True) -> int:
        """Host-side analytic estimate of the device buffers one diagonal
        admission of ``n_segments`` segments holds while suspended: the
        read-only drain-padded ``xs [S+L-1]``, the slot buffer ``[L]``, and
        the output carry — the rolling ``win [min(L,S)]`` + ``brow`` pair
        in stream mode, the full ``ys [S]`` otherwise (all in units of one
        ``[B, T, D]`` segment). Pure arithmetic (no compile, no sync): the
        scheduler's byte-budget admission check runs this per request. The
        compiled-program ground truth is ``prefill_memory_stats``."""
        cfg = self.cfg
        M = cfg.armt.num_mem_tokens if cfg.armt is not None else 0
        T = self.seg_len + M
        item = jnp.dtype(self.params["embed"].dtype).itemsize
        seg = batch * T * cfg.d_model * item
        L = self._n_layers
        S = n_segments
        total = (S + L - 1) * seg + L * seg                  # xs + buf
        if stream:
            total += min(L, S) * seg + S * batch * cfg.d_model * item
        else:
            total += S * seg                                 # full ys
        return total

    def prefill_memory_stats(self, n_segments: int, batch: int = 1, *,
                             capture: bool = False, stream: bool = False,
                             n_groups: int = 4) -> Dict:
        """AOT-compile the resumable prefill stepper for this signature
        (abstract inputs — nothing runs) and return its
        ``compiled.memory_analysis()`` byte counts:
        ``{argument,output,temp,peak}_bytes`` (peak falls back to
        argument+output+temp where the backend reports no peak — the
        launch/dryrun.py pattern). Cached per signature; publishes
        ``memory.temp_bytes`` / ``memory.peak_bytes`` gauges to the
        engine's metrics registry so the serve stack's memory trajectory
        is scraped like any other metric (DESIGN.md §15)."""
        key = (n_segments, batch, capture, stream, n_groups)
        if key in self._mem_stats:
            return self._mem_stats[key]
        cfg = self.cfg
        layout = StackLayout.from_config(cfg)
        dtype = self.params["embed"].dtype
        M = cfg.armt.num_mem_tokens if cfg.armt is not None else 0
        T = self.seg_len + M
        state0 = init_state(cfg, batch, "segmented", dtype)
        x_abs = jax.ShapeDtypeStruct((n_segments, batch, T, cfg.d_model),
                                     dtype)
        xs_abs, carry_abs = jax.eval_shape(
            lambda x: diag.pipeline_init(layout, state0, x,
                                         capture_states=capture,
                                         stream_ys=stream), x_abs)
        step = self.prefill_step(n_segments, batch, capture, n_groups)
        with self._mesh_ctx():
            compiled = step.lower(self.params, xs_abs, carry_abs).compile()
        stats = {"argument_bytes": None, "output_bytes": None,
                 "temp_bytes": None, "peak_bytes": None}
        try:
            ma = compiled.memory_analysis()
            arg = getattr(ma, "argument_size_in_bytes", None)
            out = getattr(ma, "output_size_in_bytes", None)
            temp = getattr(ma, "temp_size_in_bytes", None)
            peak = getattr(ma, "peak_memory_in_bytes", None)
            if peak is None and None not in (arg, out, temp):
                peak = arg + out + temp
            stats = {"argument_bytes": arg, "output_bytes": out,
                     "temp_bytes": temp, "peak_bytes": peak}
        except Exception:       # backend without memory_analysis support
            pass
        reg = self.telemetry.registry
        if reg is not None:
            labels = dict(n_segments=str(n_segments),
                          stream="on" if stream else "off")
            if stats["temp_bytes"] is not None:
                reg.set_gauge("memory.temp_bytes", stats["temp_bytes"],
                              **labels)
            if stats["peak_bytes"] is not None:
                reg.set_gauge("memory.peak_bytes", stats["peak_bytes"],
                              **labels)
        self._mem_stats[key] = stats
        return stats

    def start_prefill(self, prompts: jax.Array, *,
                      groups_per_call: Optional[int] = 4,
                      session_entry=None,
                      stream: bool = False,
                      max_stage_segments: Optional[int] = None
                      ) -> "PrefillPipeline":
        """Begin a *resumable* admission (DESIGN.md §11): returns a
        PrefillPipeline equivalent to ``_prefill(prompts)`` (or, with
        ``session_entry``, to the session-resume chunk feed) whose
        ``advance()`` runs one bounded unit of work — ``groups_per_call``
        anti-diagonal groups of the active diagonal stage, or one tail
        chunk piece — so a scheduler can interleave decode chunks between
        calls instead of blocking on the whole prefill.

        ``stream``: bounded-memory admission (DESIGN.md §15) — the diagonal
        stages carry the rolling ``win``/``brow`` pair instead of the full
        ``ys [S, B, T, D]``, so the per-admission activation footprint is
        flat in prompt length. Identical results (last-position logits,
        boundary states, final recurrent state) — the prefix-cache hidden
        states come from the same capture buffers either way.

        ``max_stage_segments``: cap each diagonal stage at this many
        segments — oversized prompts then chunk through multiple resumable
        stages (the recurrent state chains across stages exactly like the
        blocking path's pow2 groups), bounding even the read-only ``xs``
        buffer per stage. The scheduler's byte-budget admission sets both
        knobs together (overflow-aware admission, DESIGN.md §15)."""
        return PrefillPipeline(self, prompts,
                               groups_per_call=groups_per_call,
                               session_entry=session_entry,
                               stream=stream,
                               max_stage_segments=max_stage_segments)

    # ------------------------------------------------------------------
    # On-device decode loop
    # ------------------------------------------------------------------

    def _decode_loop(self, max_new: int, greedy: bool, top_k: int):
        """Build (and cache) the jitted whole-decode loop: a lax.scan over
        steps that samples, steps, and flushes at segment boundaries via
        lax.cond — no host branching, no per-token device->host transfer.
        The decode state is donated to the loop (freely overwritten in
        place on backends that support donation) and the final carry comes
        back out, so a session store can persist it without re-running
        anything. Note the last sampled token is never fed through the
        model — the returned state has consumed max_new - 1 of the emitted
        tokens; the last one is the session's `pending` token."""
        key_ = (max_new, greedy, top_k)
        if key_ in self._loops:
            return self._loops[key_]
        cfg, serve_mode, seg_len = self.cfg, self.serve_mode, self.seg_len
        armt_on = serve_mode == "armt" and cfg.armt is not None

        def loop(params, dstate, logits0, temp, rng):
            def sample(logits, k):
                # `temp` stays a traced scalar so changing the temperature
                # value never recompiles; greedy vs sampling is a different
                # graph (keyed in self._loops)
                if greedy:
                    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
                scaled = logits / temp
                if top_k > 0:
                    kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
                    scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
                return jax.random.categorical(k, scaled, -1).astype(jnp.int32)

            def body(carry, key_t):
                state, tok = carry
                logits, state = decode_step(params, cfg, state, tok,
                                            serve_mode=serve_mode)
                if armt_on:
                    state = jax.lax.cond(
                        state["pos"] >= seg_len,
                        lambda s: flush_segment(params, cfg, s),
                        lambda s: s, state)
                nxt = sample(logits, key_t)
                return (state, nxt), nxt

            # token 0 comes from the prefill logits; the scan emits the
            # max_new-1 stepped samples, so the last emitted token is never
            # fed through a wasted forward
            with jax.named_scope("serve.decode_loop"):
                keys = jax.random.split(rng, max_new)
                tok0 = sample(logits0, keys[0])
                (fstate, _), toks = jax.lax.scan(body, (dstate, tok0),
                                                 keys[1:])
                toks = jnp.concatenate([tok0[None], toks],
                                       axis=0).T  # [B, max_new]
                return toks, fstate

        # donation is a no-op (with a warning) on CPU — only request it where
        # the backend honors it
        donate = (1,) if jax.default_backend() != "cpu" else ()
        self._loops[key_] = jax.jit(loop, donate_argnums=donate)
        return self._loops[key_]

    def generate(self, prompts: jax.Array, max_new: int, *,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 enc_frames=None,
                 session_id: Optional[str] = None) -> GenerationResult:
        """Prefill + decode max_new tokens. temperature<=0: greedy (the
        default, deterministic); otherwise temperature/top-k sampling with
        an on-device PRNG. One device->host transfer for the whole call.

        session_id: persist the end-of-generation state in the engine's
        session store and, when a state for this id already exists, resume
        from it — the prompt is then *this turn's new tokens only* and the
        conversation history is never recomputed."""
        B, P = prompts.shape
        entry = None
        if session_id is not None:
            if self.session_store is None:
                raise ValueError("session_id given but the engine has no "
                                 "session_store")
            if B != 1:
                raise ValueError("sessions are per-conversation: B must be 1")
            entry = self.session_store.get(session_id)   # None on first turn
        if self.serve_mode == "cache":
            base = (entry.pos + len(entry.pending)) if entry is not None else 0
            if base + P + max_new > self.max_len:
                # the KV write offset would clamp at the cache end and
                # silently corrupt logits — refuse instead
                raise ValueError(
                    f"prompt_len {P} + max_new {max_new} (+{base} session "
                    f"tokens) exceeds max_len {self.max_len} of the KV cache")
        t0 = time.perf_counter()
        cached = 0
        tel = self.telemetry
        if entry is not None:
            # scatter-on-restore: session blobs are mesh-shape-agnostic
            # (gathered to host by the store when sharded) — commit them to
            # *this* engine's shardings, whatever mesh the blob came from
            with tel.span("session_restore", "session", session=session_id):
                restored = self._place_state(
                    {"prelude": entry.state["prelude"],
                     "pattern": entry.state["pattern"]}, 1)
                dstate = {**restored,
                          "pos": jnp.asarray(entry.pos, jnp.int32)}
                toks_in = np.concatenate(
                    [entry.pending, np.asarray(prompts[0], np.int32)])
                logits, dstate, _pos = self._chunk(
                    dstate, jnp.asarray(toks_in[None]), entry.pos)
        else:
            with tel.span("prefill", "prefill", prompt_len=P, batch=B):
                logits, dstate, _pos, cached = self._prefill(
                    prompts, enc_frames=enc_frames)
        jax.block_until_ready(logits)
        t_first = time.perf_counter()
        with tel.span("decode", "decode", max_new=max_new):
            loop = self._decode_loop(max_new, temperature <= 0.0, top_k)
            toks, fstate = loop(self.params, dstate, logits,
                                jnp.float32(max(temperature, 1e-6)),
                                jax.random.PRNGKey(seed))
            toks = np.asarray(toks)
        t_end = time.perf_counter()
        tel.observe("generate_ttft_s", t_first - t0)
        tel.observe("generate_decode_tok_s",
                    max_new / max(t_end - t_first, 1e-9))
        if session_id is not None:
            # the loop never feeds the last sampled token — it becomes the
            # resume's `pending` prefix (see _decode_loop)
            history = np.concatenate([
                entry.tokens if entry is not None else np.empty(0, np.int32),
                np.asarray(prompts[0], np.int32), toks[0]]).astype(np.int32)
            self.session_store.put(
                session_id,
                state={"prelude": fstate["prelude"],
                       "pattern": fstate["pattern"]},
                pos=int(np.asarray(fstate["pos"]).reshape(-1)[0]),
                pending=toks[0, -1:], tokens=history)
        return GenerationResult(
            toks, P // self.seg_len, self.schedule,
            ttft_s=t_first - t0,
            tok_s=max_new / max(t_end - t_first, 1e-9),
            cached_segments=cached, session_id=session_id,
            resumed=entry is not None,
            metrics=(self.metrics_snapshot()
                     if tel.registry is not None else None))

    # ------------------------------------------------------------------
    # Continuous batching
    # ------------------------------------------------------------------

    def serve(self, requests: Iterable, *, n_slots: int = 4,
              chunk: int = 8, max_queue: Optional[int] = None,
              prefill_groups_per_chunk: int = 4,
              fused_admission: bool = False,
              max_concurrent_admissions: Optional[int] = None,
              admission_fairness: str = "round_robin",
              admission_byte_budget: Optional[int] = None) -> Iterator:
        """Continuous-batching streaming front door: admit `Request`s into a
        fixed pool of decode slots and yield `StreamEvent`s as tokens are
        produced. Rejections (queue-full, invalid request, evicted session)
        come back as structured `RequestError` events on the same stream —
        the iterator never raises mid-serve for a bad request (see
        serve/scheduler.py for the slot-state invariants).

        prefill_groups_per_chunk: admission fairness knob (DESIGN.md §11) —
        the new request's prefill advances this many diagonal groups per
        decode chunk instead of blocking every slot for its whole prompt;
        0 restores the legacy blocking admission. fused_admission: run the
        admission's diagonal groups inside the same jitted launch as the
        decode chunk (one dispatch per interval).

        max_concurrent_admissions: cap on interleaved admissions in flight
        at once (DESIGN.md §12); None (default) bounds the pool only by
        free slots, 1 restores the PR 5 single-admission behavior.
        admission_fairness: 'round_robin' (default — every in-flight
        admission advances k groups per round, same-signature carries
        pooled into one launch) or 'oldest_first' (head-of-line).

        admission_byte_budget: overflow-aware admission (DESIGN.md §15) —
        prompts whose full-``ys`` prefill would hold more than this many
        activation bytes are admitted through the streaming carry with
        byte-bounded stages instead of being rejected or ballooning
        memory; None (default) disables the check."""
        from repro.serve.scheduler import ContinuousScheduler
        sched = ContinuousScheduler(
            self, n_slots=n_slots, chunk=chunk, max_queue=max_queue,
            prefill_groups_per_chunk=prefill_groups_per_chunk,
            fused_admission=fused_admission,
            max_concurrent_admissions=max_concurrent_admissions,
            admission_fairness=admission_fairness,
            admission_byte_budget=admission_byte_budget)
        return sched.run(requests)


def _tail_pieces(engine: ServeEngine, total: int, pos: int):
    """Host-side decomposition of a token-chunk feed into bounded
    ``decode_step`` pieces: [(start, take, flush_after), ...] plus the
    final in-segment position — ``pos`` is tracked host-side because
    decode_step advances the device-side ``dstate['pos']`` by exactly the
    tokens fed, so the two never diverge and no per-piece device->host
    readback exists. The single source of truth for tail bucketing:
    ``ServeEngine._chunk`` runs all pieces blocking, the resumable
    PrefillPipeline runs one per ``advance()`` — token-identical by
    construction because both consume this same decomposition."""
    pieces = []
    t = 0
    while t < total:
        room = (engine.seg_len - pos if engine.serve_mode == "armt"
                else total - t)
        take = min(room, total - t)
        if engine.bucket_prompts:
            take = 1 << (take.bit_length() - 1)
        pos += take
        flush = (engine.serve_mode == "armt" and engine.cfg.armt is not None
                 and pos >= engine.seg_len)
        pieces.append((t, take, flush))
        if flush:
            pos = 0
        t += take
    return pieces, pos


class PrefillPipeline:
    """A suspended/resumable admission (DESIGN.md §11).

    ``ServeEngine._prefill`` decomposed into bounded work units the host
    drives one ``advance()`` at a time:

      * one *diagonal stage* per power-of-two segment group (the same
        bucketing as ``_prefill``) — each ``advance()`` runs one jitted
        ``engine.prefill_step`` dispatch of ``groups_per_call``
        anti-diagonal groups on the stage's carry;
      * one *tail piece* per bounded ``decode_step`` chunk (the same
        decomposition as ``_chunk`` — also the whole pipeline for a
        session resume, which replays pending + new-turn tokens).

    Token-identical (greedy) to the blocking path by construction: the
    diagonal stages share the one-shot executor's step body bit for bit
    (core/diagonal.py), tail pieces reuse the engine's jitted ``_step`` /
    ``_flush``, prefix-cache matching/insertion and the boundary-logits
    math run the exact same host code on the same arrays.

    Carry freshness: the jitted stepper *donates* its carry, so every
    restored leaf entering it (prefix-cache snapshot, session blob) is
    routed through ``engine._place_state`` — the same fresh-buffer
    guarantee the decode loop got for store blobs. Without the copy, the
    first ``advance()`` after a cache hit would delete the store's own
    arrays out from under it (donation-aliasing; regression-tested in
    tests/test_serve_interleave.py). For the same reason the carry never
    aliases the scheduler's pool: a decode chunk donating the pool between
    ``advance()`` calls cannot invalidate a suspended carry.
    """

    def __init__(self, engine: ServeEngine, prompts, *,
                 groups_per_call: Optional[int] = 4, session_entry=None,
                 stream: bool = False,
                 max_stage_segments: Optional[int] = None):
        self.engine = engine
        self._stream = bool(stream)
        if max_stage_segments is not None and max_stage_segments < 1:
            raise ValueError(
                f"max_stage_segments must be >= 1, got {max_stage_segments}")
        self._max_stage = max_stage_segments
        # None: each advance() runs its whole diagonal stage in one jitted
        # call (blocking semantics through the resumable machinery — the
        # fair baseline the bench compares against, free of the legacy
        # path's per-admission retracing)
        if groups_per_call is not None and groups_per_call < 1:
            raise ValueError(
                f"groups_per_call must be >= 1 or None (whole stage per "
                f"advance), got {groups_per_call}; the scheduler's "
                "'0 = legacy blocking' knob never constructs a pipeline")
        self.groups_per_call = (None if groups_per_call is None
                                else int(groups_per_call))
        prompts = jnp.asarray(prompts)
        assert prompts.ndim == 2, prompts.shape
        self.prompts = prompts
        B, P = prompts.shape
        self.B = B
        cfg = engine.cfg
        dtype = engine.params["embed"].dtype
        self.cached = 0
        self._logits = None
        self._pos = 0
        self._done = False
        self._stage = 0
        self._stages = []            # ("diag", off, g) | ("tail", t, take, fl)
        self._carry = None
        self._xs = None
        self._exec_state = None
        self._use_cache = False
        self._prompt_np = None
        self._chain = None

        if session_entry is not None:
            # O(new turn) resume: the restored blob goes through
            # _place_state (fresh buffers — see the class docstring) and is
            # then consumed piecewise by tail chunks only
            if B != 1:
                raise ValueError("sessions are per-conversation: B must be 1")
            with engine.telemetry.span("session_restore", "session"):
                restored = engine._place_state(
                    {"prelude": session_entry.state["prelude"],
                     "pattern": session_entry.state["pattern"]}, B)
            self._dstate = {**restored,
                            "pos": jnp.asarray(session_entry.pos, jnp.int32)}
            toks_in = np.concatenate(
                [session_entry.pending, np.asarray(prompts[0], np.int32)])
            self._tail = jnp.asarray(toks_in[None])
            self._pos = int(session_entry.pos)
            pieces, _ = _tail_pieces(engine, int(toks_in.shape[0]), self._pos)
            self._stages = [("tail",) + p for p in pieces]
            return

        # --- full-prefill path: mirror _prefill's host prologue ----------
        dstate = decode_state_init(cfg, B, serve_mode=engine.serve_mode,
                                   max_len=engine.max_len, dtype=dtype)
        if engine.mesh is not None:
            dstate = jax.device_put(dstate, engine.state_sharding(B))
        self._dstate = dstate
        n_full = P // engine.seg_len if engine.serve_mode == "armt" else 0
        use_cache = (engine.prefix_cache is not None and B == 1
                     and n_full > 0)
        if use_cache:
            from repro.serve.state_store import prefix_hash_chain
            self._prompt_np = np.asarray(prompts[0], np.int32)
            self._chain = prefix_hash_chain(self._prompt_np, engine.seg_len)
            with engine.telemetry.span("prefix_probe", "cache",
                                       n_segments=n_full):
                self.cached, snap = engine.prefix_cache.match(
                    self._prompt_np, chain=self._chain)
            engine.telemetry.inc("prefix_probe_total",
                                 result="hit" if self.cached else "miss")
            if self.cached:
                # fresh buffers (the stepper donates this into its carry)
                self._exec_state = engine._place_state(snap.state, B)
                self._logits = (
                    jax.device_put(snap.logits, shd.replicated(engine.mesh))
                    if engine.mesh is not None else jnp.asarray(snap.logits))
                if self.cached == n_full:
                    # exact full-segment hit: nothing left for the executor —
                    # transplant straight into the decode state for the tail
                    self._dstate = _transplant(self._exec_state, self._dstate)
        self._use_cache = use_cache
        rem = n_full - self.cached
        if self._max_stage is not None and rem > self._max_stage:
            # byte-budget chunking (DESIGN.md §15): as many largest-pow2-
            # under-cap stages as fit, then the pow2 decomposition of the
            # remainder — every stage size stays a power of two (bounded
            # compile count) and <= the cap (bounded xs/carry bytes); the
            # recurrent state chains across stages like any staged prefill
            cap = 1 << (self._max_stage.bit_length() - 1)
            groups = [cap] * (rem // cap) + _pow2_chunks(rem % cap)
        else:
            groups = (_pow2_chunks(rem) if engine.bucket_prompts
                      else ([rem] if rem else []))
        off = self.cached
        for g in groups:
            if engine.schedule != "diagonal":
                raise ValueError(
                    "start_prefill needs the diagonal schedule for segment "
                    f"stages (engine.schedule={engine.schedule!r}); use the "
                    "blocking _prefill instead")
            self._stages.append(("diag", off, g))
            off += g
        tail = prompts[:, n_full * engine.seg_len:]
        if tail.shape[1] > 0:
            self._tail = tail
            pieces, _ = _tail_pieces(engine, int(tail.shape[1]), 0)
            self._stages += [("tail",) + p for p in pieces]
        if not self._stages:
            assert self._logits is not None, "empty prompt"
            self._done = True

    # -- progress ----------------------------------------------------------

    @property
    def done(self) -> bool:
        return self._done

    def result(self):
        """(next_token_logits, decode_state, in-segment pos, cached) — the
        blocking ``_prefill`` quadruple; valid once ``done``."""
        assert self._done, "pipeline not finished — keep calling advance()"
        return self._logits, self._dstate, self._pos, self.cached

    # -- diagonal stages ---------------------------------------------------

    def _begin_diag(self, off: int, g: int) -> None:
        eng = self.engine
        cfg = eng.cfg
        seg = eng.seg_len
        toks_g = self.prompts[:, off * seg:(off + g) * seg]
        with_mem = cfg.armt is not None and cfg.armt.num_mem_tokens > 0
        x = embed_segments(eng.params, cfg, toks_g, seg, with_mem)
        layout = StackLayout.from_config(cfg)
        state0 = self._exec_state
        if state0 is None:
            state0 = init_state(cfg, self.B, "segmented",
                                eng.params["embed"].dtype)
        xs, carry = diag.pipeline_init(layout, state0, x,
                                       capture_states=self._use_cache,
                                       stream_ys=self._stream)
        if eng.mesh is not None:
            specs = shd.pipeline_carry_specs(
                carry, eng.mesh, layout.n_layers, self.B,
                stacked_axis=eng.stacked_axis)
            xs = jax.device_put(xs, specs["xs"])
            carry = jax.device_put(carry, {k: specs[k] for k in carry})
        self._xs, self._carry = xs, carry
        self._groups_done = 0
        self._n_steps = n_diagonal_groups(g, layout.n_layers)
        self._exec_state = None      # consumed into the (donated) carry

    def _finish_diag(self, off: int, g: int) -> None:
        eng = self.engine
        cfg = eng.cfg
        layout = StackLayout.from_config(cfg)
        ys, fin, capd = diag.pipeline_finalize(layout, self._carry)
        with_mem = cfg.armt is not None and cfg.armt.num_mem_tokens > 0
        if self._stream:
            # Streaming carry (DESIGN.md §15): `brow [S, B, D]` holds
            # exactly the retained row the consumers below read —
            # boundary_logits and last_logits both slice position
            # ``[:, :, -1]`` of the seg_len-trimmed hidden, which is the
            # ``retain_pos = seg_len - 1`` row the stepper kept. Lifting
            # brow to [S, B, 1, D] makes both functions read it unchanged,
            # so the logits math is the same host code on the same values.
            hidden = ys["brow"][:, :, None, :]
        else:
            hidden = ys[:, :, :eng.seg_len] if with_mem else ys
        if self._use_cache:
            blogits = boundary_logits(eng.params, cfg, hidden)
            for c in range(g):
                end = (off + c + 1) * eng.seg_len
                eng.prefix_cache.insert(
                    self._prompt_np[:end],
                    jax.tree_util.tree_map(lambda a, _c=c: a[_c], capd),
                    blogits[c], key=self._chain[off + c])
        self._logits = last_logits(eng.params, cfg, hidden)
        self._exec_state = fin
        self._carry = self._xs = None
        self._stage += 1
        if not any(s[0] == "diag" for s in self._stages[self._stage:]):
            self._dstate = _transplant(fin, self._dstate)

    def active_diag(self):
        """(n_segments, capture, xs, carry) of the in-flight diagonal stage
        (beginning it if needed), or None when the next unit is a tail
        piece / the pipeline is done — the scheduler's fused admission mode
        feeds these through its combined decode+prefill launch."""
        if self._done or self._stage >= len(self._stages):
            return None
        st = self._stages[self._stage]
        if st[0] != "diag":
            return None
        if self._carry is None:
            self._begin_diag(st[1], st[2])
        return st[2], self._use_cache, self._xs, self._carry

    def _groups_per_advance(self) -> int:
        return self.groups_per_call or self._n_steps

    def _advance_diag(self, new_carry=None) -> None:
        st = self._stages[self._stage]
        _, off, g = st
        if self._carry is None:
            self._begin_diag(off, g)
        k = self._groups_per_advance()
        if new_carry is None:
            step = self.engine.prefill_step(g, self.B, self._use_cache, k)
            with self.engine._mesh_ctx():
                self._carry = step(self.engine.params, self._xs, self._carry)
        else:
            self._carry = new_carry
        self._groups_done += k
        if self._groups_done >= self._n_steps:
            self._finish_diag(off, g)

    def apply_diag_result(self, carry) -> bool:
        """Accept the carry advanced ``groups_per_call`` groups by a fused
        scheduler launch; returns ``done`` like ``advance()``."""
        self._advance_diag(new_carry=carry)
        if self._stage >= len(self._stages):
            self._finish()
        return self._done

    # -- tail pieces -------------------------------------------------------

    def _run_tail_piece(self, stage) -> None:
        _, t, take, flush = stage
        eng = self.engine
        self._logits, self._dstate = eng._step(eng.params, self._dstate,
                                               self._tail[:, t:t + take])
        self._pos += take
        if flush:
            with eng.telemetry.span("flush_segment", "flush", take=take):
                self._dstate = eng._flush(eng.params, self._dstate)
            self._pos = 0
        self._stage += 1

    # -- driver ------------------------------------------------------------

    def _finish(self) -> None:
        assert self._logits is not None, "empty prompt"
        self._done = True

    def advance(self) -> bool:
        """Run one bounded unit (k diagonal groups or one tail piece);
        returns True when the admission is complete (``result()`` ready)."""
        if self._done:
            return True
        st = self._stages[self._stage]
        if st[0] == "diag":
            self._advance_diag()
        else:
            self._run_tail_piece(st)
        if self._stage >= len(self._stages):
            self._finish()
        return self._done


class AdmissionPool:
    """N concurrent resumable admissions advanced together (DESIGN.md §12).

    Generalizes §11's single suspended PrefillPipeline to a FIFO pool:
    each fairness round every member advances one bounded unit — its
    ``groups_per_call`` anti-diagonal groups or one tail piece — and the
    members whose active unit is a diagonal stage of the SAME
    (n_segments, capture, k) signature ride ONE pooled jitted launch
    (``ServeEngine.pool_prefill_step``): their carries stack on a leading
    pool axis, per-carry cursors keep heterogeneous progress exact (masked
    overshoot), and the pool size pads to a power of two so the compile
    count stays O(log N) per signature. Per-member host state — prefix
    cache match/insert, session resume, tail bucketing, boundary logits —
    lives in each PrefillPipeline unchanged: pooling batches DEVICE work
    only, so every member is token-identical to its own one-at-a-time
    pipeline by construction.

    Donation safety: the pooled stepper donates the carry tuple, so member
    carries are consumed by ``advance_round`` and replaced via
    ``apply_diag_result`` — nothing else may hold the old arrays (the same
    contract the single-carry stepper already imposes; pads are fresh
    zeros, never aliases)."""

    def __init__(self, engine: ServeEngine):
        self.engine = engine
        self.members: List[PrefillPipeline] = []      # FIFO admission order

    def __len__(self) -> int:
        return len(self.members)

    def add(self, pipe: PrefillPipeline) -> None:
        self.members.append(pipe)

    def grid_cells_remaining(self) -> int:
        """Unexecuted (segment, layer) cells across every member's
        remaining diagonal stages — the pool's share of the global grid.
        Host-side cursors only (never syncs a device carry)."""
        from repro.core.schedule import pool_cells_remaining
        L = self.engine._n_layers
        total = 0
        for pipe in self.members:
            steps, segs = [], []
            for idx, st in enumerate(pipe._stages[pipe._stage:]):
                if st[0] != "diag":
                    continue
                segs.append(st[2])
                steps.append(pipe._groups_done
                             if idx == 0 and pipe._carry is not None else 0)
            total += pool_cells_remaining(steps, segs, L)
        return total

    def diag_buckets(self):
        """Group members whose next unit is a diagonal stage by pooled-
        launch signature: ``{(n_segments, capture, stream, k): [(pipe, xs,
        carry), ...]}`` in member (FIFO) order. ``stream`` keeps
        bounded-memory (win/brow) carries out of full-``ys`` pools — the
        carry structures differ, so they cannot stack. Members at a tail
        piece (or done) are absent — they advance individually."""
        buckets: Dict = {}
        for pipe in self.members:
            ad = pipe.active_diag()
            if ad is None:
                continue
            g, capture, xs, carry = ad
            sig = (g, capture, pipe._stream, pipe._groups_per_advance())
            buckets.setdefault(sig, []).append((pipe, xs, carry))
        return buckets

    def advance_round(self, *, already_advanced=()):
        """One fairness round: every member advances one bounded unit.
        Same-signature diagonal groups of >= 2 members ride one pooled
        launch; singletons and tail pieces advance individually (the PR 5
        single-carry programs). ``already_advanced``: ids of pipes a fused
        scheduler launch advanced this round — they are skipped here.
        Returns the members that completed, FIFO, removed from the pool."""
        advanced = set(already_advanced)
        for sig, group in self.diag_buckets().items():
            group = [g for g in group if id(g[0]) not in advanced]
            if len(group) < 2:
                continue
            g_segs, capture, _stream, k = sig
            carries = self.engine.pool_prefill_step_run(
                g_segs, capture, k, group)
            for (pipe, _, _), c in zip(group, carries):
                pipe.apply_diag_result(c)
                advanced.add(id(pipe))
        done = []
        for pipe in list(self.members):
            if id(pipe) in advanced:
                if pipe.done:
                    done.append(pipe)
            elif pipe.advance():
                done.append(pipe)
        for pipe in done:
            self.members.remove(pipe)
        return done

    def advance_oldest(self):
        """Head-of-line fairness (``admission_fairness='oldest_first'``):
        only the oldest member advances this round — the reference policy
        the round-robin default is contrasted against in tests/bench."""
        pipe = self.members[0]
        if pipe.advance():
            self.members.remove(pipe)
            return [pipe]
        return []


