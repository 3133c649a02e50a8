"""Continuous-batching scheduler: a fixed pool of decode slots fed from a
request queue (DESIGN.md §8).

Each slot is one batch row of a pooled decode state and owns the full
per-request serving state: the ARMT recurrent memory (A, z) / SSM state of
every layer, the current-segment KV cache, and an *in-segment position* —
``state['pos']`` is an int32 [n_slots] vector (``per_slot_pos``), so
requests sit at heterogeneous segment phases inside one jitted step.

The decode loop is a packed ``decode_step`` over all slots followed by a
masked ``flush_segment`` for exactly the slots that crossed a segment
boundary this step — one compiled graph, no host branching, no per-token
device->host transfer. Both write the pooled state in place, masked per
row at each write (models/model.py ``_decode_layers``). Tokens cross to the host once per
``chunk`` steps (a single transfer of the chunk's token block), which is
when finished slots are freed and queued requests admitted.

Admission is *interleaved* by default (DESIGN.md §11): the new request's
prefill runs as a resumable diagonal pipeline (``ServeEngine.start_prefill``)
that advances ``prefill_groups_per_chunk`` anti-diagonal groups between
decode chunks, so a 128k-token admission no longer freezes every decoding
slot for its whole prompt — the last head-of-line block the diagonal
schedule left in the serving stack. ``prefill_groups_per_chunk=0`` restores
the legacy blocking admission (one ``ServeEngine._prefill`` call); with
``fused_admission=True`` the admitting requests' segment-cells ride the
same jitted launch as the decode cells (one combined program per chunk
interval, ``fused_fns`` / ``fused_pool_fns``). Either way the finished B=1
state is transplanted into a free slot of the pool with ``.at[slot].set``
— other slots keep decoding across admissions (their rows are untouched),
and the admission itself is token-identical (greedy) to the blocking path
(tests/test_serve_interleave.py). With a prefix cache on the engine,
admission prefills only the uncached tail segments; with a session store,
a request carrying a known ``session_id`` transplants the stored
conversation state and feeds only the new turn (O(new turn) admission).

Up to ``max_concurrent_admissions`` admissions are in flight at once
(DESIGN.md §12; default None = bounded only by free slots): each holds a
reserved slot and a suspended carry, and every scheduler round is one
*global* (request, segment, layer) work set — k ready diagonal groups from
EACH in-flight admission plus the packed decode chunk. Same-signature
carries batch into one pooled stepper launch (engine.AdmissionPool), and
with ``fused_admission`` the whole round — decode chunk plus every pooled
bucket — is ONE jitted program. Fairness is round-robin by default (every
admission advances k groups per round; slots assigned FIFO at start, so no
admission starves); ``admission_fairness='oldest_first'`` is the
head-of-line reference policy. Queue wait (``t_admit - t_submit``) and the
concurrent-admission count are recorded per request on its StreamEvents.
When no decode slot is active, pending admissions drain in a tight loop
(no per-round scheduling-pass overhead) until a transplant reactivates
decode or a new request could start.

Requests are pulled from the ``requests`` iterable *lazily between
chunks* — a live/streaming source is served as it arrives instead of being
drained before the decode loop starts, and each request's ``t_submit`` is
taken at pull time. With ``max_queue=None`` (the default, the pull model)
backpressure is simply not pulling: nothing is read from the source until
the scheduler can start it. A live source may ``yield None`` to say "no
request ready yet" — the scheduler keeps decoding and polls again at the
next chunk boundary rather than blocking in ``next()``. Setting ``max_queue`` selects the push model:
the source is drained into a bounded backlog and overflow is rejected with
a structured ``queue_full`` event (slots count as capacity, as before).

Rejections are *structured*: invalid requests, a full queue, and evicted
sessions yield ``RequestError`` events on the stream — ``run`` never raises
mid-serve for a bad request, so one malformed request cannot kill the other
slots' in-flight generations.

On a mesh-native engine (``ServeEngine(mesh=...)``, DESIGN.md §10) the pool
and its per-slot control vectors are committed to the engine's decode-state
shardings at construction — slot rows over the 'data' axes, heads/d_model
over 'model' — and the packed chunk / admission / extract jits run as GSPMD
programs over the mesh; the host driver below is unchanged.

Slot-state invariants (DESIGN.md §8):
  * a slot row is meaningful iff its host-side `_Slot.active` is True; an
    inactive slot's row is garbage and is fully overwritten at admission
    (every leaf row, pos, and pending token) — nothing is read from it;
  * inactive slots still flow through the packed step (fixed shapes), but
    every leaf of their state is frozen at the write (``decode_step``'s
    ``active`` mask: their K/V rows are written their own values back,
    whole leaves and pos keep theirs) and the flush mask excludes them — so
    a finished request's row is bit-exactly its end-of-generation state at
    the chunk boundary, which is what the session store persists (§9);
  * per-slot independence of the math itself: all decode ops are
    batch-row-local. The one exception is MoE with `dispatch='global'` and
    a tight capacity factor (capacity drops depend on co-batched rows) —
    serve MoE archs with `dispatch='per_row'` or a dropless capacity if
    exact single-request equivalence matters;
  * host mirrors (remaining/active) are advanced from the chunk's emit
    masks only, so host and device views never need a reconciling sync.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Iterable, Iterator, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decode_step, flush_segment


@dataclass
class Request:
    """One generation request. prompt: int32 [P] token ids (P >= 1).

    session_id: resume/persist the conversation in the engine's session
    store — the prompt is then this turn's new tokens only."""
    req_id: Union[int, str]
    prompt: np.ndarray
    max_new: int
    session_id: Optional[str] = None


@dataclass
class StreamEvent:
    """One generated token, streamed as soon as its chunk reaches the host."""
    req_id: Union[int, str]
    token: int
    index: int                  # 0-based position within the request's output
    done: bool                  # True on the request's final token
    # host-clock serving metrics, chunk-granular by design: set on the
    # request's first event (ttft_s) and final event (ttft_s + tok_s).
    # ttft_s counts from submission (pull time — queue wait included, which
    # is the latency a caller feels); tok_s counts from *admission* (queue
    # wait excluded, prefill included), so it measures this request's
    # service rate, not the queue depth. GenerationResult.tok_s is
    # decode-only. t_emit is the host clock at the chunk boundary that
    # surfaced this token — inter-token-latency and admission-stall
    # aggregation (benchmarks/bench_serve.py) reads it off the stream.
    ttft_s: Optional[float] = None
    tok_s: Optional[float] = None
    t_emit: Optional[float] = None
    # queue-wait breakdown (DESIGN.md §12), set on first and final events:
    # t_admit - t_submit is the time the request sat queued before its
    # admission started (the component concurrent admissions attack —
    # ttft_s = queue_wait_s + service time), and concurrent_admissions is
    # how many admissions were in flight when this one started (its own
    # included; 1 = it had the admission machinery to itself).
    queue_wait_s: Optional[float] = None
    concurrent_admissions: Optional[int] = None


@dataclass
class RequestError:
    """Structured rejection streamed in-band instead of raising out of the
    serve iterator mid-flight. code: 'invalid_request' | 'queue_full' |
    'session_evicted'."""
    req_id: Union[int, str]
    code: str
    message: str


@dataclass
class _Slot:
    req_id: Optional[Union[int, str]] = None
    remaining: int = 0
    index: int = 0
    active: bool = False
    tokens: List[int] = field(default_factory=list)
    session_id: Optional[str] = None
    prompt: Optional[np.ndarray] = None
    history: Optional[np.ndarray] = None    # prior session turns (consumed)
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: Optional[float] = None
    n_concurrent: int = 1        # admissions in flight when this one started
    # host mirror of the device-side in-segment position (DESIGN.md §13):
    # seeded with the admission's pos, advanced one per emitted token, reset
    # at seg_len — exactly the arithmetic decode_step/flush_segment run on
    # device, so in-graph segment flushes are counted and put on the trace
    # timeline without any device readback (_chunk_counts)
    pos: int = 0


@dataclass
class _Admission:
    """Host record of one in-flight interleaved admission: the suspended
    prefill pipeline plus the slot it has reserved and the metadata the
    transplant needs on completion. The scheduler keeps a FIFO list of
    these (up to ``max_concurrent_admissions``), mirrored by the engine's
    AdmissionPool which batches their device work (DESIGN.md §12)."""
    req: Request
    slot: int
    pipe: object                 # serve.engine.PrefillPipeline
    entry: object                # SessionEntry or None
    prompt: np.ndarray
    t_submit: float
    t_admit: float
    n_concurrent: int = 1


class ContinuousScheduler:
    """Drives a ServeEngine over many requests with continuous batching."""

    def __init__(self, engine, *, n_slots: int = 4, chunk: int = 8,
                 max_queue: Optional[int] = None,
                 prefill_groups_per_chunk: int = 4,
                 fused_admission: bool = False,
                 max_concurrent_admissions: Optional[int] = None,
                 admission_fairness: str = "round_robin",
                 admission_byte_budget: Optional[int] = None):
        from repro.models import decode_state_init
        from repro.serve.engine import AdmissionPool
        assert n_slots >= 1 and chunk >= 1
        assert prefill_groups_per_chunk >= -1
        assert (max_concurrent_admissions is None
                or max_concurrent_admissions >= 1), max_concurrent_admissions
        assert admission_fairness in ("round_robin", "oldest_first"), \
            admission_fairness
        self.engine = engine
        self.n_slots = n_slots
        self.chunk = chunk
        self.max_queue = max_queue
        # interleaved admission (DESIGN.md §11): diagonal groups each
        # admitting request's pipeline advances per decode chunk; 0 =
        # legacy blocking admission (one eager _prefill call); -1 = one
        # whole diagonal stage per chunk (blocking semantics for
        # single-stage prompts, but through the jitted stepper — the
        # bench's fair blocking baseline)
        self.prefill_groups_per_chunk = prefill_groups_per_chunk
        self.fused_admission = fused_admission
        # pooled concurrent admissions (DESIGN.md §12): up to this many
        # interleaved admissions in flight at once, each holding a reserved
        # slot; None bounds the pool only by free slots, 1 restores the
        # PR 5 single-admission behavior (and its exact compiled programs)
        self.max_concurrent_admissions = max_concurrent_admissions
        self.admission_fairness = admission_fairness
        # overflow-aware admission (DESIGN.md §15): prompts whose full-ys
        # prefill would exceed this many activation bytes go through the
        # streaming carry with byte-bounded stages; None disables the check
        assert admission_byte_budget is None or admission_byte_budget > 0, \
            admission_byte_budget
        self.admission_byte_budget = admission_byte_budget
        self._adms: List[_Admission] = []            # FIFO
        self._pool_adm = AdmissionPool(engine)
        # idle-drain observability: rounds run inside the tight loop that
        # drains pending admissions while no decode slot is active
        self.idle_drain_rounds = 0
        # (t_start, t_end) of every completed admission — the bench reads
        # these to compute admission_stall (max decode gap overlapping an
        # admission window)
        self.admission_windows: List[tuple] = []
        cfg = engine.cfg
        dtype = engine.params["embed"].dtype
        self.pool = decode_state_init(
            cfg, n_slots, serve_mode=engine.serve_mode,
            max_len=engine.max_len, dtype=dtype, per_slot_pos=True)
        self.tok = jnp.zeros((n_slots,), jnp.int32)      # pending next input
        self.active = jnp.zeros((n_slots,), bool)
        self.remaining = jnp.zeros((n_slots,), jnp.int32)
        if engine.mesh is not None:
            # mesh-native pool (DESIGN.md §10): slot rows shard over the DP
            # axes, heads/d_model over 'model'; the per-slot control vectors
            # (pending token / active / remaining) shard with the slots, so
            # the packed chunk step is one GSPMD program over the mesh
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.parallel import sharding as shd
            self.pool = jax.device_put(
                self.pool, engine.state_sharding(n_slots, per_slot_pos=True))
            vec = NamedSharding(
                engine.mesh,
                P(shd.batch_axes(engine.mesh, n_slots, leaf="slot_vec")))
            self.tok = jax.device_put(self.tok, vec)
            self.active = jax.device_put(self.active, vec)
            self.remaining = jax.device_put(self.remaining, vec)
        self.slots = [_Slot() for _ in range(n_slots)]
        self._armt_flush = (engine.serve_mode == "armt"
                            and engine.cfg.armt is not None)
        self.free: deque = deque(range(n_slots))
        # the jitted step/admit/extract functions are cached on the engine
        # (keyed by chunk) so repeated serve() calls — and schedulers with
        # different slot counts, which only differ in traced shapes — reuse
        # compiles
        self._chunk_fn, self._admit_fn, self._extract_fn = \
            scheduler_fns(engine, chunk)

    @property
    def tel(self):
        """The engine's telemetry bundle (DESIGN.md §13) — resolved
        dynamically so a caller swapping ``engine.telemetry`` between
        serve() calls (the bench does) is picked up without rebuilding the
        scheduler."""
        return self.engine.telemetry

    # ------------------------------------------------------------------
    # Host-side driver
    # ------------------------------------------------------------------

    def _validate(self, req: Request) -> Optional[RequestError]:
        prompt = np.asarray(req.prompt)
        if req.max_new < 1:
            return RequestError(req.req_id, "invalid_request",
                                f"max_new must be >= 1, got {req.max_new}")
        if prompt.ndim != 1 or prompt.shape[0] < 1:
            return RequestError(req.req_id, "invalid_request",
                                f"prompt must be a [P>=1] id vector, got "
                                f"shape {prompt.shape}")
        if (self.engine.serve_mode == "cache"
                and prompt.shape[0] + req.max_new > self.engine.max_len):
            return RequestError(
                req.req_id, "invalid_request",
                f"prompt+max_new exceeds max_len {self.engine.max_len} of "
                "the KV cache")
        if (req.session_id is not None
                and self.engine.session_store is None):
            return RequestError(req.req_id, "invalid_request",
                                "request carries a session_id but the "
                                "engine has no session_store")
        return None

    def _admission_plan(self, prompt_len: int):
        """Byte-budget admission decision (DESIGN.md §15): returns
        ``(stream, max_stage_segments)`` for a prompt of ``prompt_len``
        tokens. Prompts whose full-``ys`` prefill fits the budget keep the
        default path bit for bit; oversized prompts stream (rolling
        win/brow carry) with stages capped so even the per-stage ``xs``
        fits, and the decision is counted + the compiled stepper's
        temp/peak bytes published as gauges. Host arithmetic only — no
        device sync on the admit path."""
        budget = self.admission_byte_budget
        if budget is None:
            return False, None
        S = prompt_len // self.engine.seg_len
        if S < 2 or self.engine.prefill_activation_bytes(
                S, stream=False) <= budget:
            return False, None
        max_g = S
        while max_g > 1 and self.engine.prefill_activation_bytes(
                max_g, stream=True) > budget:
            max_g //= 2
        self.tel.inc("overflow_admissions_total")
        self.tel.set_gauge("admission_stage_cap_segments", max_g)
        k = self.prefill_groups_per_chunk
        self.engine.prefill_memory_stats(
            min(max_g, S), stream=True,
            n_groups=(k if k and k > 0 else 4))
        return True, (max_g if max_g < S else None)

    def _admit(self, req: Request, t_submit: float) -> Optional[RequestError]:
        """Prefill (or session-resume) the request alone and transplant it
        into a free slot; other slots keep decoding across this call.
        Returns a RequestError instead of admitting when rejected."""
        err = self._validate(req)
        if err is not None:
            return err
        t_admit = time.perf_counter()
        prompt = np.asarray(req.prompt, np.int32)
        entry = None
        if req.session_id is not None:
            from repro.serve.state_store import SessionEvicted
            try:
                entry = self.engine.session_store.get(req.session_id)
            except SessionEvicted as e:
                return RequestError(req.req_id, "session_evicted", str(e))
        slot = self.free.popleft()
        if entry is not None:
            # O(new turn) resume: transplant the stored conversation state
            # and feed only pending + this turn's tokens. _place_state is
            # the scatter-on-restore boundary: blobs are mesh-shape-agnostic
            # host arrays when they were captured sharded — commit them to
            # this engine's shardings (a device_put, not a host round-trip,
            # when they are already device-resident)
            with self.tel.span("session_restore", "session",
                               lane=str(req.req_id), session=req.session_id):
                restored = self.engine._place_state(
                    {"prelude": entry.state["prelude"],
                     "pattern": entry.state["pattern"]}, 1)
                dstate = {**restored,
                          "pos": jnp.asarray(entry.pos, jnp.int32)}
                toks_in = np.concatenate([entry.pending, prompt])
                logits, one_state, pos = self.engine._chunk(
                    dstate, jnp.asarray(toks_in[None]), entry.pos)
        else:
            stream, max_g = self._admission_plan(prompt.shape[0])
            if stream:
                # oversized prompt under the byte budget: drain a streaming
                # resumable pipeline synchronously — blocking semantics,
                # bounded memory (the full-ys _prefill would hold the whole
                # O(S) activation set at once)
                pipe = self.engine.start_prefill(
                    prompt[None], groups_per_call=None, stream=True,
                    max_stage_segments=max_g)
                while not pipe.advance():
                    pass
                logits, one_state, pos, _cached = pipe.result()
            else:
                # diagonal prefill of the new request alone (longest-prefix
                # cache hit inside _prefill when the engine carries one)
                logits, one_state, pos, _cached = self.engine._prefill(
                    prompt[None])
        self._install(slot, req, entry, prompt, logits, one_state, pos,
                      t_submit, t_admit, n_concurrent=1)
        return None

    def _install(self, slot: int, req: Request, entry, prompt: np.ndarray,
                 logits, one_state, pos: int, t_submit: float,
                 t_admit: float, n_concurrent: int = 1) -> None:
        """Transplant a finished admission into its slot — the single
        completion path shared by blocking (_admit) and interleaved
        (_finish_admission) admission, so the two modes cannot drift
        field-for-field (the token-identity invariant depends on it)."""
        first_tok = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)
        with self.tel.span("transplant", "transplant",
                           lane=str(req.req_id), slot=slot):
            self.pool, self.tok, self.active, self.remaining = self._admit_fn(
                self.pool, self.tok, self.active, self.remaining,
                jnp.int32(slot), one_state, first_tok,
                jnp.int32(pos), jnp.int32(req.max_new))
        s = self.slots[slot]
        s.req_id, s.remaining, s.index, s.active, s.tokens = (
            req.req_id, req.max_new, 0, True, [])
        s.session_id, s.prompt = req.session_id, prompt
        s.history = (entry.tokens if entry is not None
                     else np.empty(0, np.int32))
        s.t_submit, s.t_admit, s.t_first = t_submit, t_admit, None
        s.n_concurrent = n_concurrent
        s.pos = int(pos)
        t_end = time.perf_counter()
        self.admission_windows.append((t_admit, t_end))
        # retroactive span covering the whole admission window (start ->
        # transplant landed), on the request's own lane — the trace-side
        # twin of the admission_windows record the bench reads
        self.tel.add_span("admission", "admission", t_admit, t_end,
                          lane=str(req.req_id), slot=slot,
                          queue_wait_s=t_admit - t_submit,
                          concurrent=n_concurrent)
        self.tel.inc("admissions_total")
        self.tel.observe("queue_wait_s", t_admit - t_submit)
        self.tel.observe("admission_window_s", t_end - t_admit)

    def _interleave(self) -> bool:
        """Interleaved admission needs the resumable pipeline's diagonal
        stepper for segment stages; tail-only admissions ('cache' mode) are
        schedule-agnostic. Everything else falls back to blocking."""
        if self.prefill_groups_per_chunk == 0:
            return False
        eng = self.engine
        return eng.schedule == "diagonal" or eng.serve_mode != "armt"

    def _can_admit(self) -> bool:
        """Room for another admission to START (a free slot is checked by
        the caller). Blocking admissions are synchronous, so ``_adms`` is
        empty and they are never capped; interleaved admissions respect
        ``max_concurrent_admissions``."""
        return (self.max_concurrent_admissions is None
                or len(self._adms) < self.max_concurrent_admissions)

    def _start(self, req: Request, t_submit: float) -> Optional[RequestError]:
        """Begin serving ``req``: the full blocking admission when
        interleaving is off/unavailable, else reserve a slot and suspendably
        prefill via the engine's pipeline — the new member joins the
        admission pool and advances every fairness round (``run``).
        Returns a RequestError instead of starting when rejected."""
        if not self._interleave():
            return self._admit(req, t_submit)
        err = self._validate(req)
        if err is not None:
            return err
        t_admit = time.perf_counter()
        prompt = np.asarray(req.prompt, np.int32)
        entry = None
        if req.session_id is not None:
            from repro.serve.state_store import SessionEvicted
            try:
                entry = self.engine.session_store.get(req.session_id)
            except SessionEvicted as e:
                return RequestError(req.req_id, "session_evicted", str(e))
        slot = self.free.popleft()
        k = self.prefill_groups_per_chunk
        stream, max_g = (self._admission_plan(prompt.shape[0])
                         if entry is None else (False, None))
        pipe = self.engine.start_prefill(
            prompt[None], groups_per_call=(None if k < 0 else k),
            session_entry=entry, stream=stream, max_stage_segments=max_g)
        self._adms.append(_Admission(
            req=req, slot=slot, pipe=pipe, entry=entry, prompt=prompt,
            t_submit=t_submit, t_admit=t_admit,
            n_concurrent=len(self._adms) + 1))
        self._pool_adm.add(pipe)
        return None

    def _finish_admissions(self, done_pipes) -> None:
        """Pipelines that completed this round: transplant each B=1 state
        into its reserved slot, FIFO (identical to blocking admission from
        here)."""
        for pipe in done_pipes:
            adm = next(a for a in self._adms if a.pipe is pipe)
            logits, one_state, pos, _cached = pipe.result()
            self._install(adm.slot, adm.req, adm.entry, adm.prompt, logits,
                          one_state, pos, adm.t_submit, adm.t_admit,
                          n_concurrent=adm.n_concurrent)
            self._adms.remove(adm)

    def _fused_round(self):
        """The global-grid launch (DESIGN.md §12): ONE jitted program runs
        the packed decode chunk over every slot plus k diagonal groups from
        every pooled admission bucket. Returns ``(toks, masks, advanced)``
        — the chunk's outputs and the ids of pipes the launch advanced
        (tail-piece members are not in any bucket and advance individually
        afterwards)."""
        buckets = self._pool_adm.diag_buckets()
        if not buckets:
            return None, None, frozenset()
        order = sorted(buckets.keys())        # deterministic compile key
        sigs, xs_b, carry_b, groups = [], [], [], []
        for sig in order:
            g_segs, capture, stream, k = sig
            group = buckets[sig]
            n_pool, xs_t, carry_t = self.engine.pool_pack(g_segs, group)
            sigs.append((g_segs, capture, stream, k, n_pool))
            xs_b.append(xs_t)
            carry_b.append(carry_t)
            groups.append(group)
        ffn = fused_pool_fns(self.engine, self.chunk, tuple(sigs))
        with self.engine._mesh_ctx():
            (self.pool, self.tok, self.active, self.remaining, toks, masks,
             out_b) = ffn(self.engine.params, self.pool, self.tok,
                          self.active, self.remaining, tuple(xs_b),
                          tuple(carry_b))
        advanced = set()
        for group, outs in zip(groups, out_b):
            for (pipe, _, _), c in zip(group, outs):
                pipe.apply_diag_result(c)
                advanced.add(id(pipe))
        return toks, masks, frozenset(advanced)

    def _advance_admissions(self):
        """Span-wrapped fairness round — every pooled admission round
        (interleaved AND idle-drain) shows up on the trace timeline with
        its pool size and launch mode."""
        with self.tel.span("admission_round", "admission",
                           n_adms=len(self._adms),
                           fused=self.fused_admission):
            return self._advance_admissions_inner()

    def _advance_admissions_inner(self):
        """One fairness round over the in-flight admissions: every member
        advances one bounded unit — its k diagonal groups (same-signature
        members batched into one pooled launch) or one tail piece. With
        ``fused_admission`` and active decode slots, the decode chunk and
        every bucket's pooled groups run as ONE jitted program
        (``fused_pool_fns``); the single-admission case keeps PR 5's
        ``fused_fns`` path (same compiled programs). Completed admissions
        transplant FIFO into their reserved slots. Returns ``(toks,
        masks)`` when the fused launch ran the decode chunk, else
        ``(None, None)``."""
        toks = masks = None
        run_fused = self.fused_admission and any(s.active for s in self.slots)
        if self.admission_fairness == "oldest_first" and len(self._adms) > 1:
            done_pipes = self._pool_adm.advance_oldest()
        elif len(self._adms) == 1:
            # PR 5 single-carry path bit for bit (and its compiled programs)
            pipe = self._adms[0].pipe
            fused = pipe.active_diag() if run_fused else None
            if fused is not None:
                g, capture, xs, carry = fused
                ffn = fused_fns(self.engine, self.chunk, g, capture,
                                pipe._groups_per_advance())
                with self.engine._mesh_ctx():
                    (self.pool, self.tok, self.active, self.remaining,
                     toks, masks, carry) = ffn(
                        self.engine.params, self.pool, self.tok,
                        self.active, self.remaining, xs, carry)
                done = pipe.apply_diag_result(carry)
            else:
                done = pipe.advance()
            done_pipes = [pipe] if done else []
            if done:
                self._pool_adm.members.remove(pipe)
        else:
            advanced = frozenset()
            if run_fused:
                toks, masks, advanced = self._fused_round()
            done_pipes = self._pool_adm.advance_round(
                already_advanced=advanced)
        self._finish_admissions(done_pipes)
        return toks, masks

    def _persist_session(self, b: int) -> None:
        """End of generation for slot b: lift its row out of the pool
        (device-side gather at the chunk boundary — the packed chunk froze
        the row bit-exactly at its end-of-generation state) and persist it.
        The scheduler's step consumes every emitted token (unlike
        generate's loop), so nothing is pending on resume."""
        s = self.slots[b]
        with self.tel.span("session_persist", "session",
                           lane=str(s.req_id), session=s.session_id):
            row, pos, _pend = self._extract_fn(self.pool, self.tok,
                                               jnp.int32(b))
            history = np.concatenate(
                [s.history, s.prompt,
                 np.asarray(s.tokens, np.int32)]).astype(np.int32)
            self.engine.session_store.put(
                s.session_id, state=row, pos=int(np.asarray(pos)),
                pending=np.empty(0, np.int32), tokens=history)

    def _chunk_counts(self, masks_np: np.ndarray):
        """What one chunk computed, from the host copy of its emit masks
        and the slots' ``pos`` mirrors (advanced here). Returns the
        counters and the [chunk, n_slots] mask of segment crossings (None
        without ARMT flushes). The counters: ``steps``;
        ``slot_steps_active``, the decode rows that emitted a token;
        ``flush_steps``, the steps whose flush branch ran (some row
        crossed a segment boundary); ``flush_rows_needed``, the rows that
        crossed; ``flush_rows_computed``, the rows the masked flush ran,
        every slot at each flush step. The device's own arithmetic: a row
        that emits advances ``pos`` by one, and reaching ``seg_len`` flushes
        it and resets ``pos`` to 0."""
        counts = {"steps": self.chunk,
                  "slot_steps_active": int(masks_np.sum())}
        if not self._armt_flush:
            return counts, None
        seg = self.engine.seg_len
        pos0 = np.array([s.pos for s in self.slots], np.int64)
        # in-segment position after each step, before any reset
        after = pos0 + np.cumsum(masks_np, axis=0, dtype=np.int64)
        cross = masks_np & (after % seg == 0)
        for b, s in enumerate(self.slots):
            s.pos = int(after[-1, b] % seg)
        flush_steps = int(cross.any(axis=1).sum())
        counts.update(flush_steps=flush_steps,
                      flush_rows_needed=int(cross.sum()),
                      flush_rows_computed=self.n_slots * flush_steps)
        return counts, cross

    def _drain_chunk(self, toks, masks,
                     queue_depth: int) -> Iterator[StreamEvent]:
        """Cross one chunk's token block to the host and stream its events
        (the single device->host transfer for these ``chunk`` steps).

        This is the telemetry piggyback point (DESIGN.md §13): the
        ``decode_chunk`` span brackets exactly the two ``np.asarray``
        transfers that already existed (so its duration is the
        device-sync + copy wall time); the ``sched.drain`` span holds the
        rest, the consumer's handling of every yielded event included, and
        carries the chunk's counters (``_chunk_counts``) as arguments.
        Emit stamps, flush instants, counters and gauges come from the
        host copies, and nothing else touches the device — the
        one-transfer-per-chunk invariant is regression-tested with
        telemetry enabled."""
        tel = self.tel
        with tel.span("decode_chunk", "decode", steps=self.chunk):
            toks_np = np.asarray(toks)
            masks_np = np.asarray(masks)
        with tel.span("sched.drain", "decode") as drain:
            now = time.perf_counter()
            counts, cross = self._chunk_counts(masks_np)
            drain.set(**counts)
            if counts.get("flush_steps"):
                tel.inc("decode_flushes_total", counts["flush_rows_needed"])
                tel.inc("decode_flush_steps_total", counts["flush_steps"])
                tel.inc("decode_flush_rows_computed_total",
                        counts["flush_rows_computed"])
            if tel.trace is not None:
                for b, s in enumerate(self.slots):
                    if s.active:
                        n = int(masks_np[:, b].sum())
                        if n:
                            tel.emit(s.req_id, now, n)
                if cross is not None:
                    for b in np.nonzero(cross)[1]:
                        tel.instant("segment_flush", "flush", t=now,
                                    lane=str(self.slots[b].req_id))
            tel.observe("chunk_queue_depth", queue_depth)
            tel.set_gauge("pool_occupancy", self.n_slots - len(self.free))
            for t in range(self.chunk):
                for b, s in enumerate(self.slots):
                    if not masks_np[t, b] or not s.active:
                        continue
                    s.remaining -= 1
                    done = s.remaining == 0
                    tok = int(toks_np[t, b])
                    s.tokens.append(tok)
                    first = s.t_first is None
                    if first:
                        s.t_first = now
                    ev = StreamEvent(s.req_id, tok, s.index, done, t_emit=now)
                    if first or done:
                        ev.queue_wait_s = s.t_admit - s.t_submit
                        ev.concurrent_admissions = s.n_concurrent
                    if first:
                        ev.ttft_s = now - s.t_submit
                    if done:
                        ev.ttft_s = s.t_first - s.t_submit
                        ev.tok_s = (s.index + 1) / max(now - s.t_admit,
                                                       1e-9)
                    yield ev
                    s.index += 1
                    if done:
                        s.active = False
                        if (s.session_id is not None
                                and self.engine.session_store is not None):
                            self._persist_session(b)
                        self.free.append(b)

    def run(self, requests: Iterable[Request]) -> Iterator[
            Union[StreamEvent, RequestError]]:
        """Generator: pulls requests lazily, admits as slots free up
        (interleaving the admitting prefill with decode chunks unless
        ``prefill_groups_per_chunk=0``), and yields one StreamEvent per
        generated token (chunk-granular latency) plus RequestError events
        for rejected requests.

        Live sources: the iterator is only pulled when the scheduler can
        start the request, but ``next()`` on a plain iterator is a
        *blocking* call — a source with nothing ready would stall the
        active streams. A live source should therefore ``yield None`` when
        no request is ready yet: the scheduler stops pulling for that
        round, keeps decoding, and polls again at the next chunk boundary
        (finite lists/generators that always have a request ready are
        unaffected)."""
        it = iter(requests)
        exhausted = False

        def pull() -> Optional[Request]:
            # returns None when the source is exhausted OR yielded None
            # ("nothing ready yet") — either way the caller stops pulling
            # this round; `exhausted` tells the two cases apart at
            # termination time
            nonlocal exhausted
            if exhausted:
                return None
            try:
                return next(it)
            except StopIteration:
                exhausted = True
                return None

        queue: deque = deque()           # (request, t_submit-at-pull)
        while True:
            # one top-level phase span at every instant (SCHED_PHASES)
            # ---- start work: backlog first, then pull from the source ----
            with self.tel.span("sched.admit", "admission"):
                while self.free and queue and self._can_admit():
                    req, t_sub = queue.popleft()
                    err = self._start(req, t_sub)
                    if err is not None:
                        yield err
                while not exhausted:
                    can_start = (bool(self.free) and not queue
                                 and self._can_admit())
                    if not can_start and self.max_queue is None:
                        # pull model: backpressure by not pulling — nothing
                        # is read from a live source until we can actually
                        # start it
                        break
                    if (not can_start and self.max_queue is not None
                            and len(queue) >= self.max_queue + len(self.free)):
                        # push model at capacity: drain + structured
                        # rejection. Free slots count as extra queue
                        # capacity — a slot left idle only because the
                        # admission pool is at its concurrency cap will
                        # serve its queued request as soon as a pooled
                        # admission lands
                        req = pull()
                        if req is None:
                            break
                        yield RequestError(
                            req.req_id, "queue_full",
                            f"all {self.n_slots} slots busy or spoken for "
                            f"and queue limit {self.max_queue} reached")
                        continue
                    req = pull()
                    if req is None:
                        break
                    t_sub = time.perf_counter()
                    if can_start:
                        err = self._start(req, t_sub)
                        if err is not None:
                            yield err
                    else:
                        queue.append((req, t_sub))

            # ---- one fairness round over the in-flight admissions ----
            toks = masks = None
            if self._adms:
                toks, masks = self._advance_admissions()

            # ---- decode chunk (unless the fused launch already ran it) ----
            if toks is None and any(s.active for s in self.slots):
                with self.tel.span("sched.dispatch", "decode"):
                    (self.pool, self.tok, self.active, self.remaining,
                     toks, masks) = self._chunk_fn(
                        self.engine.params, self.pool, self.tok,
                        self.active, self.remaining)
            if toks is not None:
                yield from self._drain_chunk(toks, masks, len(queue))
            elif self._adms:
                # idle-drain: no decode slot is active, so there is no
                # chunk to interleave against — drain the pending
                # admissions in a tight loop instead of one k-group round
                # per full scheduling pass. Break out as soon as a
                # transplant lands (decode can resume) or a new request
                # could start (the pull loop must run — a free slot plus
                # pool headroom while the source may still have requests).
                while (self._adms
                       and not any(s.active for s in self.slots)
                       and not (self.free and self._can_admit()
                                and (queue or not exhausted))):
                    with self.tel.span("idle_drain_round", "idle",
                                       pending=len(self._adms)):
                        self._advance_admissions()
                    self.idle_drain_rounds += 1
            else:
                if not queue and exhausted:
                    return
                if not queue:
                    # fully idle on a live source that yielded None
                    # ("nothing ready yet"): back off briefly instead of
                    # spinning on next()
                    with self.tel.span("sched.wait", "idle"):
                        time.sleep(1e-3)
                # nothing active, nothing admitting: loop back to pull/admit


def _chunk_body_factory(cfg, serve_mode: str, seg_len: int, chunk: int):
    """The packed decode-chunk body as a pure (un-jitted) function —
    ``scheduler_fns`` jits it standalone; ``fused_fns`` composes it with
    the admission pipeline's stepper inside one program."""
    armt_on = serve_mode == "armt" and cfg.armt is not None

    def chunk_fn(params, state, tok, active, remaining):
        def body(carry, _):
            state, tok, active, remaining = carry
            emit, emit_mask = tok, active
            # inactive rows are masked at every write: their state (every
            # leaf and pos) stays bit for bit, so garbage rows never trigger
            # (or mask into) a flush, and — load-bearing for the session
            # store — a row that finished mid-chunk stays at its
            # end-of-generation state until the host extracts it at the
            # chunk boundary
            with jax.named_scope("serve.decode_step"):
                logits, new_state = decode_step(params, cfg, state, tok,
                                                serve_mode=serve_mode,
                                                active=active)
            if armt_on:
                boundary = active & (new_state["pos"] >= seg_len)

                def flush(s):
                    with jax.named_scope("serve.flush_segment"):
                        return flush_segment(params, cfg, s,
                                             slot_mask=boundary)

                # the chunk program's only conditional: on a TPU trace,
                # which carries no scopes, the flush is the operations it
                # encloses
                new_state = jax.lax.cond(boundary.any(), flush,
                                         lambda s: s, new_state)
            with jax.named_scope("serve.sample"):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            nxt = jnp.where(active, nxt, tok)
            remaining = remaining - emit_mask.astype(jnp.int32)
            active = active & (remaining > 0)
            return (new_state, nxt, active, remaining), (emit, emit_mask)

        # named_scope: XLA profiles label this scan to match the host-side
        # decode_chunk spans, and its step's parts inside it (DESIGN.md §13)
        with jax.named_scope("serve.decode_chunk"):
            (state, tok, active, remaining), (toks, masks) = jax.lax.scan(
                body, (state, tok, active, remaining), None, length=chunk)
        return state, tok, active, remaining, toks, masks

    return chunk_fn


def scheduler_fns(engine, chunk: int):
    """Build (or fetch from the engine's cache) the jitted packed-chunk,
    admission, and slot-extraction functions shared by every scheduler on
    this engine."""
    cache = engine._sched_fns
    if chunk in cache:
        return cache[chunk]
    donate_ok = jax.default_backend() != "cpu"
    chunk_fn = _chunk_body_factory(engine.cfg, engine.serve_mode,
                                   engine.seg_len, chunk)

    def admit_fn(pool, tok, active, remaining, slot, one_state,
                 first_tok, pos_val, n_new):
        prelude = jax.tree_util.tree_map(
            lambda pl, ol: pl.at[slot].set(ol[0].astype(pl.dtype)),
            tuple(pool["prelude"]), tuple(one_state["prelude"]))
        pattern = jax.tree_util.tree_map(
            lambda pl, ol: pl.at[:, slot].set(ol[:, 0].astype(pl.dtype)),
            tuple(pool["pattern"]), tuple(one_state["pattern"]))
        new_pool = {"prelude": prelude, "pattern": pattern,
                    "pos": pool["pos"].at[slot].set(pos_val)}
        return (new_pool,
                tok.at[slot].set(first_tok),
                active.at[slot].set(True),
                remaining.at[slot].set(n_new))

    def extract_fn(pool, tok, slot):
        """Inverse of admit_fn's transplant: lift slot row -> B=1 state
        (device-side; the host only pulls it when persisting a session)."""
        prelude = jax.tree_util.tree_map(
            lambda pl: jax.lax.dynamic_slice_in_dim(pl, slot, 1, axis=0),
            tuple(pool["prelude"]))
        pattern = jax.tree_util.tree_map(
            lambda pl: jax.lax.dynamic_slice_in_dim(pl, slot, 1, axis=1),
            tuple(pool["pattern"]))
        return ({"prelude": prelude, "pattern": pattern},
                pool["pos"][slot], tok[slot])

    fns = (jax.jit(chunk_fn, donate_argnums=(1, 2, 3, 4) if donate_ok else ()),
           jax.jit(admit_fn, donate_argnums=(0, 1, 2, 3) if donate_ok else ()),
           jax.jit(extract_fn))
    cache[chunk] = fns
    return fns


def fused_fns(engine, chunk: int, n_segments: int, capture: bool, k: int):
    """Jitted combined program for the *fused* admission mode (DESIGN.md
    §11): one launch runs the packed decode chunk over every slot AND ``k``
    anti-diagonal groups of the admitting request's suspended pipeline, so
    the admission's segment-cells ride the same dispatch window as the
    decode cells — XLA schedules both inside a single program (and both go
    through the grouped Pallas kernels when the engine runs
    grouped_impl='fused'). Donates the pool/control vectors and the
    pipeline carry (never the read-only ``xs``) on backends that honor
    donation; the carry therefore must be fresh-buffered at pipeline start
    (see serve.engine.PrefillPipeline)."""
    key = (chunk, n_segments, capture, k)
    cache = engine._fused_fns
    if key in cache:
        return cache[key]
    from repro.core import diagonal as diag
    from repro.core.schedule import StackLayout
    cfg = engine.cfg
    chunk_body = _chunk_body_factory(cfg, engine.serve_mode, engine.seg_len,
                                     chunk)
    layout = StackLayout.from_config(cfg)
    # the same apply/grouped pair the plain stepper uses — one source of
    # truth for the numerics-critical executor setup (engine.exec_apply)
    apply, gapply = engine.exec_apply()
    buf_spec = engine._slot_spec(1)      # admissions are B=1

    def fused(params, state, tok, active, remaining, xs, carry):
        with jax.named_scope("serve.fused_global_grid"):
            state, tok, active, remaining, toks, masks = chunk_body(
                params, state, tok, active, remaining)
            exec_params = {"prelude": params["prelude"],
                           "pattern": params["pattern"]}
            carry = diag.pipeline_step(layout, exec_params, xs, carry,
                                       apply, n_groups=k, buf_spec=buf_spec,
                                       grouped_apply=gapply,
                                       remat=cfg.remat != "none",
                                       retain_pos=engine.seg_len - 1)
        return state, tok, active, remaining, toks, masks, carry

    donate = (1, 2, 3, 4, 6) if jax.default_backend() != "cpu" else ()
    cache[key] = jax.jit(fused, donate_argnums=donate)
    return cache[key]


def fused_pool_fns(engine, chunk: int, sigs: tuple):
    """Jitted GLOBAL-GRID program (DESIGN.md §12): one launch runs the
    packed decode chunk over every slot AND k anti-diagonal groups from
    every pooled admission bucket — the whole round's ready cells, decode
    and N admissions alike, in a single dispatch (the N-carry
    generalization of ``fused_fns``).

    ``sigs`` is the per-bucket signature tuple ``((n_segments, capture,
    stream, k, n_pool), ...)``; the program takes (and returns) one
    ``(xs_tuple, carry_tuple)`` pair per bucket, each tuple pow2-padded to
    its ``n_pool`` (engine.pool_pack), so the compile count is bounded by
    the pow2 bucketing of both stage sizes and pool sizes times the few
    bucket combinations a workload actually produces. Donates the
    pool/control vectors and every carry tuple (never the read-only xs) on
    backends that honor donation."""
    key = ("pool", chunk) + tuple(sigs)
    cache = engine._fused_fns
    if key in cache:
        return cache[key]
    chunk_body = _chunk_body_factory(engine.cfg, engine.serve_mode,
                                     engine.seg_len, chunk)
    bodies = [engine._pool_step_body(g, 1, capture, k, n_pool)
              for (g, capture, _stream, k, n_pool) in sigs]

    def fused(params, state, tok, active, remaining, xs_bkts, carry_bkts):
        with jax.named_scope("serve.fused_global_grid"):
            state, tok, active, remaining, toks, masks = chunk_body(
                params, state, tok, active, remaining)
            out_bkts = tuple(body(params, xs_t, carry_t)
                             for body, xs_t, carry_t
                             in zip(bodies, xs_bkts, carry_bkts))
        return state, tok, active, remaining, toks, masks, out_bkts

    donate = (1, 2, 3, 4, 6) if jax.default_backend() != "cpu" else ()
    cache[key] = jax.jit(fused, donate_argnums=donate)
    return cache[key]
