"""Diagonal-batching executor (the paper's contribution, paper Alg. 1).

Carries a slot buffer ``buf[L, B, T, D]`` with the invariant *slot l holds the
segment currently entering layer l*. Each scan step executes one anti-diagonal:
every slot advances one layer via a single grouped application per pattern
position — either ``jax.vmap(apply_block)`` (the exactness oracle) or the
fused grouped-kernel path (``grouped_apply``, models/grouped_blocks.py), the
TPU analogue of the paper's CUTLASS GroupedGEMM + batched-attention launch —
then the buffer shifts down one slot.

S + L - 1 steps total (minimal, Lemma 3.1); recurrence is exact: per-layer
states are updated by the same functions in the same order as the sequential
executor, only grouped across slots.

Two drivers share one anti-diagonal step body (``_diag_body``):

  * ``run_diagonal`` — the one-shot executor: a single ``lax.scan`` over all
    S + L - 1 groups (training / blocking prefill).
  * ``pipeline_init`` / ``pipeline_step`` / ``pipeline_finalize`` — the
    *resumable* pipeline (DESIGN.md §11): the carry (slot buffer, executor
    state, group cursor, per-segment output buffer, optional recurrent-state
    capture) is explicit, and each ``pipeline_step`` call advances a bounded
    number of groups, so a long prefill can be suspended between calls —
    e.g. to let decode chunks run (serve/scheduler.py) — and resumed
    bit-exactly. Sharing the step body is what makes the two drivers
    token-identical by construction. ``pipeline_step_pool`` batches N such
    carries (with independent cursors) into one launch for the scheduler's
    pooled concurrent admissions (DESIGN.md §12).
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.memory import recurrent_state
from repro.core.schedule import StackLayout

ApplyBlock = Callable[[str, Any, jax.Array, Any], tuple]


def _mask_state(valid, new, old):
    """Keep old state where the slot was invalid (pipeline fill/drain)."""
    def sel(n, o):
        v = valid.reshape(valid.shape + (1,) * (n.ndim - valid.ndim))
        return jnp.where(v, n, o)
    return jax.tree_util.tree_map(sel, new, old)


def boundary_states_from_capture(layout: StackLayout, captured: Dict,
                                 n_segments: int) -> Dict:
    """Assemble per-segment-boundary recurrent states from a diagonal run's
    per-step capture (run_diagonal(capture_states=True)).

    In the diagonal schedule, layer at slot l processes segment s at step
    s + l — so the state "prefix through segment c" is spread across steps:
    layer l's piece of boundary c was emitted at step (c-1) + l. This gathers
    those pieces into a tree whose leaves lead with a boundary axis [S, ...]
    (boundary c at index c-1), entirely device-side: one gather per leaf, no
    host transfer until the caller moves a snapshot off-device.
    """
    S = n_segments
    steps = jnp.arange(S)
    prelude = tuple(
        jax.tree_util.tree_map(lambda a, _j=j: a[steps + _j], captured["prelude"][j])
        for j in range(len(layout.prelude)))
    pattern = []
    for p in range(len(layout.pattern)):
        slots = jnp.asarray(layout.position_slots(p))            # [n_super]
        idx = steps[:, None] + slots[None, :]                    # [S, n_super]
        sup = jnp.arange(len(slots))[None, :]
        pattern.append(jax.tree_util.tree_map(
            lambda a: a[idx, sup], captured["pattern"][p]))
    return {"prelude": prelude, "pattern": tuple(pattern)}


def _spec_axes(buf_spec):
    slot_axis = buf_spec[0] if buf_spec is not None else None
    batch_axis = (buf_spec[1] if buf_spec is not None and len(buf_spec) > 1
                  else None)
    return slot_axis, batch_axis


def _constrain_fn(buf_spec):
    def _constrain(b):
        if buf_spec is not None:
            return jax.lax.with_sharding_constraint(b, buf_spec)
        return b
    return _constrain


def _constrain_states_fn(buf_spec):
    """Pin per-layer recurrent state (A/z/h/conv) to the slot sharding —
    otherwise GSPMD re-gathers the stage-sharded activations every step.
    State layout is [n_super, B, ...]: slot axis on dim 0, the buffer's
    batch axis on dim 1."""
    slot_axis, batch_axis = _spec_axes(buf_spec)

    def _constrain_states(pattern_states):
        if slot_axis is None:
            return pattern_states
        from jax.sharding import PartitionSpec as PS

        def one(leaf):
            rest = [None] * (leaf.ndim - 1)
            if leaf.ndim >= 2 and batch_axis is not None:
                rest[0] = batch_axis
            return jax.lax.with_sharding_constraint(
                leaf, PS(slot_axis, *rest))
        return tuple(jax.tree_util.tree_map(one, st) for st in pattern_states)

    return _constrain_states


def _band_phases(S: int, L: int):
    """Partition the S+L-1 anti-diagonal steps into phases of constant
    *valid-slot band* size, for the banded fused driver.

    At step i the valid slots form the contiguous band
    [max(0, i-S+1), min(i, L-1)] — everything outside is fill/drain padding
    the full-width body computes and throws away (up to (S+L-1)/S x wasted
    cell-applies at small S). Each phase is ``(i0, n_steps, Gb, mode)``:
    ``n_steps`` consecutive steps whose band is exactly ``Gb`` slots wide.
    Exact widths mean the banded schedule executes exactly S*L cell
    applies — the sequential executor's count — at the cost of
    2*min(S, L) - 1 compiled step bodies (bounded by the layer count; the
    earlier pow2 bucketing halved the body count but re-ran up to ~20%
    padded cells, which is the wrong trade on every measured shape).

    mode: 'fill' (band [0, i], growing one slot per step), 'drain' (band
    ends at slot L-1, shrinking), 'mid' (constant width min(S, L): the
    full stack when S >= L, else a band sliding with i).
    """
    m = min(S, L)
    phases = []
    for i in range(m - 1):                 # fill: band [0, i], width i+1
        phases.append((i, 1, i + 1, "fill"))
    phases.append((m - 1, max(S, L) - (m - 1), m, "mid"))
    last = S + L - 2
    for i in range(max(S, L), last + 1):   # drain: band [i-S+1, L-1]
        phases.append((i, 1, S + L - 1 - i, "drain"))
    return phases


def _diag_body(layout: StackLayout, params: Dict, apply_block: ApplyBlock,
               n_segments: int, *, buf_spec=None, grouped_apply=None,
               capture_states: bool = False, band=None):
    """One anti-diagonal group as a pure step function

        body((buf, states), (seg_in, i)) -> ((buf_next, states_next), emit)

    shared — the same closure, hence the same math in the same order — by
    the one-shot scan executor (run_diagonal) and the resumable pipeline
    stepper (pipeline_step). ``emit`` is the drained slot's output (plus
    the per-step recurrent-state capture when capture_states). Groups with
    ``i`` outside [0, S+L-2] are masked no-ops on the executor state: every
    slot is invalid, so states freeze and only the (ignored) buffer churns.

    ``band=(Gb, mode)`` (single-position patterns, no prelude, no buf_spec)
    selects the *banded* body: only a ``Gb``-slot slice around the valid
    band is applied (``_band_phases``), skipping fill/drain padding compute.
    Valid slots see identical inputs/params/state as the full-width body —
    the per-slot math is group-size-independent — so outputs and state
    updates are unchanged (tests/test_executors.py::test_banded_*).
    """
    S = n_segments
    L = layout.n_layers
    P = len(layout.pattern)
    slot_ids = jnp.arange(L)
    pos_slots = [np.asarray(layout.position_slots(p)) for p in range(P)]
    _constrain = _constrain_fn(buf_spec)
    _constrain_states = _constrain_states_fn(buf_spec)

    if band is not None:
        assert P == 1 and not layout.prelude and buf_spec is None, (
            "banded body needs a single-position pattern, no prelude and "
            "no slot sharding")
        Gb, mode = band
        t0 = layout.pattern[0]
        # With exact band widths (_band_phases) every slot in the band is
        # valid, so the body touches ONLY the band: no full-buffer
        # seg-insert/validity selects, no full-width y materialization, no
        # roll — the write target shifts one slot instead (y[l] lives at
        # buf[l+1] next step; slots outside the write are zero or stale
        # never-again-read fill residue). That drops the driver overhead
        # from ~5 full [L,B,T,D] passes per step to ~1.
        sliding = mode == "mid" and S < L      # band [i-S+1, i], start moves

        def banded_step(carry, xs):
            with jax.named_scope("diag.antidiagonal_banded"):
                buf, states = carry
                seg_in, i = xs
                if mode == "drain":
                    start = L - Gb
                elif sliding:
                    start = jnp.maximum(i - Gb + 1, 0)
                else:                          # fill / full-width mid
                    start = 0

                def sl(a):
                    return jax.lax.dynamic_slice_in_dim(a, start, Gb, axis=0)

                xb = sl(buf)
                if mode != "drain":
                    # slot 0 takes the entering segment; it is in the band
                    # exactly when start == 0 (static for fill/full mid,
                    # first step only of a sliding mid)
                    seg = seg_in.astype(buf.dtype)
                    if sliding:
                        row0 = jnp.where(start == 0, seg, xb[0])
                    else:
                        row0 = seg
                    xb = jnp.concatenate([row0[None], xb[1:]], axis=0)
                pb = jax.tree_util.tree_map(sl, params["pattern"][0])
                sb = jax.tree_util.tree_map(sl, states["pattern"][0])
                if grouped_apply is not None:
                    yb, stb = grouped_apply(t0, pb, xb, sb)
                else:
                    grouped = jax.vmap(
                        lambda pp, xx, ss: apply_block(t0, pp, xx, ss))
                    yb, stb = grouped(pb, xb, sb)
                new_p = jax.tree_util.tree_map(
                    lambda full, b: jax.lax.dynamic_update_slice_in_dim(
                        full, b.astype(full.dtype), start, axis=0),
                    states["pattern"][0], stb)
                new_states = {"prelude": states["prelude"],
                              "pattern": (new_p,)}

                yb = yb.astype(buf.dtype)
                if mode == "fill":
                    # band top is at most L-2: the whole band shifts down
                    out = jnp.zeros_like(buf[0])    # no drain yet (discarded)
                    buf_next = jax.lax.dynamic_update_slice_in_dim(
                        jnp.zeros_like(buf), yb, 1, axis=0)
                elif sliding:
                    # drain emission only on the step whose band top is L-1
                    out = jnp.where(start == L - Gb, yb[-1],
                                    jnp.zeros_like(yb[-1]))
                    # scatter into an (L+1)-row buffer so start+1 == L-Gb+1
                    # (the last sliding step) stays in bounds, then trim
                    buf_next = jax.lax.dynamic_update_slice_in_dim(
                        jnp.zeros((L + 1,) + buf.shape[1:], buf.dtype),
                        yb, start + 1, axis=0)[:L]
                else:
                    # drain / full-width mid: band top is L-1 — its output
                    # drains out of the pipeline as this step's emission
                    out = yb[-1]
                    buf_next = jax.lax.dynamic_update_slice_in_dim(
                        jnp.zeros_like(buf), yb[:-1], start + 1, axis=0)
                emit = ((out, recurrent_state(new_states)) if capture_states
                        else out)
                return (buf_next, new_states), emit

        return banded_step

    def diag_step(carry, xs):
        # named_scope: the anti-diagonal group shows up as one labeled
        # region in XLA profiles, matching the serve stack's host spans
        # (DESIGN.md §13)
        with jax.named_scope("diag.antidiagonal"):
            return _diag_step(carry, xs)

    def _diag_step(carry, xs):
        buf, states = carry
        seg_in, i = xs
        # insert the new segment into slot 0 with an elementwise select (an
        # indexed write would re-layout the stage-sharded slot dim — the
        # select is local on every shard; seg_in is replicated over 'stage')
        is0 = (slot_ids == 0)[(...,) + (None,) * (buf.ndim - 1)]
        buf = _constrain(jnp.where(is0, seg_in[None].astype(buf.dtype), buf))
        # slot l holds segment i - l; valid iff 0 <= i - l < S. Clear invalid
        # fill/drain slots with a select, NOT a multiply: an inf/NaN produced
        # by a block applied to empty padding would survive `0 * inf = nan`
        # and poison any group-coupled application (grouped kernels, global
        # MoE dispatch) on the next step.
        valid = (i >= slot_ids) & (i - slot_ids < S)                     # [L]
        valid_b = valid[(...,) + (None,) * (buf.ndim - 1)]
        buf = jnp.where(valid_b, buf, jnp.zeros_like(buf))

        y = jnp.zeros_like(buf)
        new_prelude = []
        for j, t in enumerate(layout.prelude):
            yj, stj = apply_block(t, params["prelude"][j], buf[j],
                                  states["prelude"][j])
            y = y.at[j].set(yj)
            new_prelude.append(_mask_state(valid[j], stj, states["prelude"][j]))

        new_pattern = []
        for p, t in enumerate(layout.pattern):
            slots = pos_slots[p]
            contiguous = P == 1          # slots are base..base+n_super-1
            if contiguous:
                # plain slice: SPMD-transparent (a fancy-indexed gather would
                # all-gather the stage-sharded buffer every step)
                xp = jax.lax.slice_in_dim(buf, int(slots[0]),
                                          int(slots[0]) + len(slots), axis=0)
            else:
                xp = buf[slots]                               # [n_super, B, T, D]
            if grouped_apply is not None:
                yp, stp = grouped_apply(t, params["pattern"][p], xp,
                                        states["pattern"][p])
            else:
                grouped = jax.vmap(
                    lambda pp, xx, ss, _t=t: apply_block(_t, pp, xx, ss))
                yp, stp = grouped(params["pattern"][p], xp,
                                  states["pattern"][p])
            if contiguous:
                y = jax.lax.dynamic_update_slice_in_dim(
                    y, yp.astype(y.dtype), int(slots[0]), axis=0)
            else:
                y = y.at[slots].set(yp)
            new_pattern.append(
                _mask_state(valid[slots], stp, states["pattern"][p]))
        new_pattern = _constrain_states(tuple(new_pattern))

        out = y[L - 1]                      # segment i-(L-1) finished all layers
        y = _constrain(y)
        # shift as a roll: on a stage-sharded slot dim this lowers to ONE
        # boundary collective-permute instead of an all-gather of the buffer
        buf_next = jnp.roll(y, shift=1, axis=0)
        is0 = (slot_ids == 0)[(...,) + (None,) * (y.ndim - 1)]
        buf_next = _constrain(jnp.where(is0, jnp.zeros_like(buf_next),
                                        buf_next))
        new_states = {"prelude": tuple(new_prelude), "pattern": tuple(new_pattern)}
        emit = ((out, recurrent_state(new_states)) if capture_states
                else out)
        return (buf_next, new_states), emit

    return diag_step


def run_diagonal(layout: StackLayout, params: Dict, state0: Dict,
                 segments: jax.Array, apply_block: ApplyBlock,
                 *, remat: bool = False, buf_spec=None, grouped_apply=None,
                 capture_states: bool = False, band_skip=None,
                 stream_ys: bool = False, retain_pos: int = -1):
    """segments: [S, B, T, D] -> (ys [S, B, T, D], final_state).

    Same params/state structure as run_sequential — the two executors are
    interchangeable (that is the point of the paper: pure reordering).

    buf_spec: optional PartitionSpec for the slot buffer [L, B, T, D]. With
    the slot dim sharded over a mesh axis ('stage'), diagonal batching
    *becomes pipeline parallelism*: every stage applies its own layers with
    fully local weights and the shift lowers to one collective-permute per
    step — no per-layer tensor-parallel all-reduces (EXPERIMENTS.md §Perf).

    grouped_apply: optional fused grouped-block application
    ``(btype, stacked_params [n_super, ...], x [n_super, B, T, D],
    stacked_state) -> (y, new_state)`` replacing the default
    ``jax.vmap(apply_block)`` over each pattern position — the fast mode
    built by ``models.grouped_blocks.make_grouped_apply`` that launches the
    Pallas grouped kernels (grouped GEMM / batched flash attention / fused
    ARMT memory) over the whole group (EXPERIMENTS.md §Perf).

    capture_states: also return the per-step recurrent state (A/z/h/conv)
    of every layer as a third output with leading axis [S+L-1] — the raw
    material for segment-boundary snapshots (boundary_states_from_capture,
    serve/state_store.py). Constant-size per step, so the extra scan output
    is (S+L-1) x the recurrent-state footprint, not activations.

    band_skip: skip the fill/drain padding compute by running the schedule
    in valid-band phases (``_band_phases``) instead of one full-width scan.
    None (default) enables it exactly for the fused grouped path on
    single-position patterns without prelude/sharding — the configuration
    where the per-step grouped launch pays for every padded slot. The vmap
    path stays on the full-width body (the untouched exactness/autodiff
    oracle); results are equal either way.

    stream_ys: bounded-memory mode (DESIGN.md §15) — never materialize the
    full ``ys [S, B, T, D]``. Returns ``({"win": [W, B, T, D],
    "brow": [S, B, D]}, final_state[, captured])`` instead: ``win`` is a
    rolling window of the last ``W = min(L, S)`` drained segments (drained
    segment ``s`` lives at ``win[s % W]``; O(L·B·T·D), flat in S) and
    ``brow`` holds each segment's retained row ``ys[s, :, retain_pos]`` —
    the only per-segment data the serving consumers need
    (``boundary_logits`` / ``last_logits`` read exactly one position).
    Retained outputs are bit-exact vs the full path: the step body is the
    same closure, and ``win``/``brow`` writes are pure slices of the same
    emitted tensor. Stream mode always runs the full-width body (no banded
    phases) and indexes ``segments`` directly with a clamped cursor instead
    of building the O(S) drain-padded copy.
    """
    S = segments.shape[0]
    L = layout.n_layers
    n_steps = S + L - 1
    if stream_ys:
        return _run_diagonal_stream(
            layout, params, state0, segments, apply_block, remat=remat,
            buf_spec=buf_spec, grouped_apply=grouped_apply,
            capture_states=capture_states, retain_pos=retain_pos)
    if band_skip is None:
        band_skip = (grouped_apply is not None and len(layout.pattern) == 1
                     and not layout.prelude and buf_spec is None and L > 1)
    if band_skip:
        assert len(layout.pattern) == 1 and not layout.prelude \
            and buf_spec is None and L > 1, "band_skip unsupported here"
        return _run_diagonal_banded(
            layout, params, state0, segments, apply_block, remat=remat,
            grouped_apply=grouped_apply, capture_states=capture_states)

    pad = jnp.zeros((L - 1,) + segments.shape[1:], segments.dtype)
    xs_seg = jnp.concatenate([segments, pad], axis=0) if L > 1 else segments

    body = _diag_body(layout, params, apply_block, S, buf_spec=buf_spec,
                      grouped_apply=grouped_apply,
                      capture_states=capture_states)
    step_fn = jax.checkpoint(body) if remat else body

    _constrain = _constrain_fn(buf_spec)
    _constrain_states = _constrain_states_fn(buf_spec)
    buf0 = _constrain(jnp.zeros((L,) + segments.shape[1:], segments.dtype))
    state0 = dict(state0,
                  pattern=_constrain_states(tuple(state0["pattern"])))
    (_, final_state), emitted = jax.lax.scan(
        step_fn, (buf0, state0), (xs_seg, jnp.arange(n_steps)))
    if capture_states:
        ys, captured = emitted
        return ys[L - 1:], final_state, captured
    return emitted[L - 1:], final_state


def _run_diagonal_banded(layout: StackLayout, params: Dict, state0: Dict,
                         segments: jax.Array, apply_block: ApplyBlock, *,
                         remat: bool, grouped_apply, capture_states: bool):
    """``run_diagonal`` as a sequence of valid-band phases: each phase is a
    ``lax.scan`` whose step applies only a pow2-bucketed band of slots
    around the valid diagonal (``_band_phases``), so the fill/drain padding
    cells are never computed — total cell-applies drop from (S+L-1)*L
    toward the sequential executor's S*L while keeping the grouped launch.
    Emissions (and captures) from all phases concatenate to exactly the
    [S+L-1] streams the one-shot scan produces."""
    S = segments.shape[0]
    L = layout.n_layers
    pad = jnp.zeros((L - 1,) + segments.shape[1:], segments.dtype)
    xs_seg = jnp.concatenate([segments, pad], axis=0)

    carry = (jnp.zeros((L,) + segments.shape[1:], segments.dtype), state0)
    ys_parts, cap_parts = [], []
    for (i0, n, Gb, mode) in _band_phases(S, L):
        # a band's weight slice is a copy where a kernel consumes it (a
        # Pallas call takes no fused slice). The slices depend on params
        # alone, so XLA may schedule them all up front — 2*min(S, L) - 2
        # partial copies of the weight stack live at once (15 GB at
        # llama-1b-armt, S=16). Tying params to the carry keeps each
        # phase's slices inside that phase.
        params, carry = jax.lax.optimization_barrier((params, carry))
        body = _diag_body(layout, params, apply_block, S,
                          grouped_apply=grouped_apply,
                          capture_states=capture_states, band=(Gb, mode))
        step_fn = jax.checkpoint(body) if remat else body
        if n == 1:
            # every fill/drain phase (and the mid phase when S == L) is a
            # single step: call the body directly instead of a trip-count-1
            # lax.scan. The step index becomes a static constant (so the
            # band start folds at trace time) and XLA can fuse each phase's
            # buffer scatter into the next phase's slice — a while loop is
            # an optimization barrier and copies the carry both ways.
            carry, emitted = step_fn(carry, (xs_seg[i0], i0))
            emitted = jax.tree_util.tree_map(lambda a: a[None], emitted)
        else:
            carry, emitted = jax.lax.scan(
                step_fn, carry, (xs_seg[i0:i0 + n], jnp.arange(i0, i0 + n)))
        if capture_states:
            ys_parts.append(emitted[0])
            cap_parts.append(emitted[1])
        else:
            ys_parts.append(emitted)
    ys = jnp.concatenate(ys_parts, axis=0)
    final_state = carry[1]
    if capture_states:
        captured = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *cap_parts)
        return ys[L - 1:], final_state, captured
    return ys[L - 1:], final_state


def _run_diagonal_stream(layout: StackLayout, params: Dict, state0: Dict,
                         segments: jax.Array, apply_block: ApplyBlock, *,
                         remat: bool, buf_spec, grouped_apply,
                         capture_states: bool, retain_pos: int):
    """``run_diagonal(stream_ys=True)``: one full-width scan whose carry
    holds the O(L·B·T·D) rolling window instead of emitting full drained
    segments, and whose per-step emission is the [B, D] retained row — so
    the scan's stacked output is O(S·B·D), not O(S·B·T·D). The input is
    indexed with a clamped cursor (no drain-padded O(S) copy; the inserted
    value at overshoot steps is discarded by the validity select, exactly
    as in ``pipeline_step``)."""
    S = segments.shape[0]
    L = layout.n_layers
    n_steps = S + L - 1
    W = min(L, S)
    body = _diag_body(layout, params, apply_block, S, buf_spec=buf_spec,
                      grouped_apply=grouped_apply,
                      capture_states=capture_states)
    step_fn = jax.checkpoint(body) if remat else body

    _constrain = _constrain_fn(buf_spec)
    _constrain_states = _constrain_states_fn(buf_spec)
    buf0 = _constrain(jnp.zeros((L,) + segments.shape[1:], segments.dtype))
    state0 = dict(state0,
                  pattern=_constrain_states(tuple(state0["pattern"])))
    win0 = jnp.zeros((W,) + segments.shape[1:], segments.dtype)
    rows0 = jnp.zeros((S,) + segments.shape[1:2] + segments.shape[3:],
                      segments.dtype)

    def step(carry, i):
        buf, states, win, rows = carry
        seg_in = jax.lax.dynamic_index_in_dim(
            segments, jnp.minimum(i, S - 1), 0, keepdims=False)
        (buf, states), emit = step_fn((buf, states), (seg_in, i))
        out, cap_e = emit if capture_states else (emit, None)
        # segment i-(L-1) drained this step: rotate it into the window and
        # keep its retained row (fill steps write nothing — idx < 0). Both
        # land in the *carry* (guarded in-place updates, clamped index)
        # rather than a scan emission: an emitted stream would stack
        # [S+L-1] rows into a fresh buffer that only exists to be sliced —
        # an O(S·B·D) temp the carry-resident buffer avoids (the flatness
        # curve in BENCH_longctx.json is measured on this program).
        idx = i - (L - 1)
        ok = idx >= 0                      # idx < S always (i <= S+L-2)
        ci = jnp.maximum(idx, 0)
        wi = jax.lax.rem(ci, jnp.int32(W))
        cur = jax.lax.dynamic_index_in_dim(win, wi, 0, keepdims=False)
        win = jax.lax.dynamic_update_index_in_dim(
            win, jnp.where(ok, out.astype(win.dtype), cur), wi, 0)
        row = out[:, retain_pos]
        cur_row = jax.lax.dynamic_index_in_dim(rows, ci, 0, keepdims=False)
        rows = jax.lax.dynamic_update_index_in_dim(
            rows, jnp.where(ok, row.astype(rows.dtype), cur_row), ci, 0)
        return (buf, states, win, rows), cap_e

    (_, final_state, win, rows), captured = jax.lax.scan(
        step, (buf0, state0, win0, rows0), jnp.arange(n_steps))
    if capture_states:
        return {"win": win, "brow": rows}, final_state, captured
    return {"win": win, "brow": rows}, final_state


# ---------------------------------------------------------------------------
# Resumable pipeline (interleaved chunked prefill, DESIGN.md §11)
# ---------------------------------------------------------------------------

def pipeline_init(layout: StackLayout, state0: Dict, segments: jax.Array,
                  *, capture_states: bool = False, stream_ys: bool = False):
    """Build ``(xs, carry)`` for a resumable diagonal prefill over
    ``segments [S, B, T, D]``.

    The carry is everything a suspended pipeline needs to resume bit-exactly:

      * ``buf``   [L, B, T, D] — the slot buffer;
      * ``state`` — the per-layer executor state tree;
      * ``step``  — int32 group cursor (fill/drain position; see
        core.schedule.segments_completed / segments_entered);
      * ``ys``    [S, B, T, D] — per-segment outputs, written as each
        segment drains from slot L-1;
      * ``cap``   (only with capture_states) — the per-group recurrent-state
        capture, leading axis [S+L-1], same layout the one-shot executor
        emits (so ``boundary_states_from_capture`` applies unchanged).

    ``stream_ys`` (DESIGN.md §15) replaces the O(S·B·T·D) ``ys`` buffer
    with the bounded-memory pair

      * ``win``  [min(L, S), B, T, D] — rolling window of the most recent
        drained segments (segment ``s`` at ``win[s % W]``);
      * ``brow`` [S, B, D] — each drained segment's retained row at the
        ``retain_pos`` the stepper is called with (the segment-boundary
        position ``boundary_logits``/``last_logits`` read),

    so the per-admission activation footprint is flat in S. The cell math
    is the shared step body either way — retained outputs are bit-exact.

    ``xs`` is the drain-padded segment input [S+L-1, B, T, D]; it is
    read-only, passed alongside the carry on every ``pipeline_step`` call
    and never donated.
    """
    S = segments.shape[0]
    L = layout.n_layers
    pad = jnp.zeros((L - 1,) + segments.shape[1:], segments.dtype)
    xs = jnp.concatenate([segments, pad], axis=0) if L > 1 else segments
    carry = {
        "buf": jnp.zeros((L,) + segments.shape[1:], segments.dtype),
        "state": state0,
        "step": jnp.zeros((), jnp.int32),
    }
    if stream_ys:
        W = min(L, S)
        B, D = segments.shape[1], segments.shape[3]
        carry["win"] = jnp.zeros((W,) + segments.shape[1:], segments.dtype)
        carry["brow"] = jnp.zeros((S, B, D), segments.dtype)
    else:
        carry["ys"] = jnp.zeros_like(segments)
    if capture_states:
        n_steps = S + L - 1
        carry["cap"] = jax.tree_util.tree_map(
            lambda a: jnp.zeros((n_steps,) + a.shape, a.dtype),
            recurrent_state(state0))
    return xs, carry


def pipeline_step(layout: StackLayout, params: Dict, xs: jax.Array,
                  carry: Dict, apply_block: ApplyBlock, *, n_groups: int = 1,
                  buf_spec=None, grouped_apply=None, remat: bool = False,
                  retain_pos: int = -1) -> Dict:
    """Advance a suspended pipeline by ``n_groups`` anti-diagonal groups.

    Pure ``(params, xs, carry) -> carry`` — jit (and donate the carry) at
    the caller; serve/engine.py's ``prefill_step`` does. Uses the same step
    body as ``run_diagonal``, so interleaving pipeline calls with anything
    else cannot change the result. Groups past the end of the grid are
    masked no-ops: the validity mask freezes the executor state and no
    ``ys``/``cap`` slot is written, so overshooting the final group (the
    last fixed-size call of a grid whose S+L-1 is not a multiple of
    n_groups) is safe — compile count stays one program per (S, n_groups).

    ``remat`` wraps the shared step body in ``jax.checkpoint`` — the same
    rematerialization ``run_diagonal(remat=True)`` applies, so the serve
    stepper honors ``cfg.remat`` like the blocking path (checkpoint does
    not change forward values; the two drivers stay bit-identical).

    Streaming carries (``pipeline_init(stream_ys=True)``) are detected by
    structure: the drained segment rotates into ``carry['win']`` and its
    ``retain_pos`` row lands in ``carry['brow']`` instead of a full ``ys``
    write (DESIGN.md §15).
    """
    _constrain_states = _constrain_states_fn(buf_spec)
    carry = dict(carry, state=dict(
        carry["state"],
        pattern=_constrain_states(tuple(carry["state"]["pattern"]))))
    sub = _group_advance(layout, params, apply_block, carry,
                         buf_spec=buf_spec, grouped_apply=grouped_apply,
                         remat=remat, retain_pos=retain_pos)
    # loop on the carry's own cursor: its trip count is not a compile-time
    # constant, so XLA keeps the loop for every n_groups — a one-iteration
    # scan would be inlined and compiled with other fusions and layouts,
    # off run_diagonal's scan by an ulp that the ARMT recurrence amplifies
    end = carry["step"] + n_groups
    return jax.lax.while_loop(lambda c: c["step"] < end,
                              lambda c: sub(xs, c), carry)


def _group_advance(layout: StackLayout, params: Dict, apply_block: ApplyBlock,
                   carry: Dict, *, buf_spec, grouped_apply, remat: bool,
                   retain_pos: int):
    """``sub(xs, c) -> c``: one anti-diagonal group of a suspended pipeline
    shaped like ``carry`` — the shared step body plus the ys/win/cap writes
    and the cursor advance."""
    stream = "win" in carry
    S = carry["brow"].shape[0] if stream else carry["ys"].shape[0]
    L = layout.n_layers
    n_steps = S + L - 1
    capture = "cap" in carry
    body = _diag_body(layout, params, apply_block, S, buf_spec=buf_spec,
                      grouped_apply=grouped_apply, capture_states=capture)
    if remat:
        body = jax.checkpoint(body)

    def sub(xs, c):
        i = c["step"]
        seg_in = jax.lax.dynamic_index_in_dim(
            xs, jnp.minimum(i, xs.shape[0] - 1), 0, keepdims=False)
        (buf, states), emit = body((c["buf"], c["state"]), (seg_in, i))
        out, cap_e = emit if capture else (emit, None)
        # segment i-(L-1) drained this group: write it into ys — or, in
        # stream mode, rotate it into the window and keep its retained row
        # (guarded — fill steps and overshoot steps write nothing)
        idx = i - (L - 1)
        ok = (idx >= 0) & (idx < S)
        ci = jnp.clip(idx, 0, S - 1)
        if stream:
            W = c["win"].shape[0]
            wi = jax.lax.rem(ci, jnp.int32(W))
            curw = jax.lax.dynamic_index_in_dim(c["win"], wi, 0,
                                                keepdims=False)
            win = jax.lax.dynamic_update_index_in_dim(
                c["win"], jnp.where(ok, out.astype(c["win"].dtype), curw),
                wi, 0)
            row = out[:, retain_pos]
            curb = jax.lax.dynamic_index_in_dim(c["brow"], ci, 0,
                                                keepdims=False)
            brow = jax.lax.dynamic_update_index_in_dim(
                c["brow"], jnp.where(ok, row.astype(c["brow"].dtype), curb),
                ci, 0)
            new = dict(c, buf=buf, state=states, step=i + 1, win=win,
                       brow=brow)
        else:
            cur = jax.lax.dynamic_index_in_dim(c["ys"], ci, 0, keepdims=False)
            ys = jax.lax.dynamic_update_index_in_dim(
                c["ys"], jnp.where(ok, out.astype(c["ys"].dtype), cur), ci, 0)
            new = dict(c, buf=buf, state=states, step=i + 1, ys=ys)
        if capture:
            si = jnp.minimum(i, n_steps - 1)
            sok = i < n_steps

            def wr(b, e):
                old = jax.lax.dynamic_index_in_dim(b, si, 0, keepdims=False)
                return jax.lax.dynamic_update_index_in_dim(
                    b, jnp.where(sok, e.astype(b.dtype), old), si, 0)

            new["cap"] = jax.tree_util.tree_map(wr, c["cap"], cap_e)
        return new

    return sub


def pipeline_step_pool(layout: StackLayout, params: Dict, xs_pool: jax.Array,
                       carry_pool: Dict, apply_block: ApplyBlock, *,
                       n_groups: int = 1, grouped_apply=None,
                       pool_spec=None, remat: bool = False,
                       retain_pos: int = -1) -> Dict:
    """Advance a *pool* of suspended pipelines by ``n_groups`` groups each
    (pooled concurrent admissions, DESIGN.md §12).

    ``xs_pool`` / ``carry_pool`` are ``pipeline_step``'s arguments with a
    leading pool axis [N, ...] — N same-shape (S, B, T, D) carries stacked
    leaf-wise, including N independent ``step`` cursors [N]. Each group of
    the pool is one ``jax.vmap`` of the single-carry group advance, so each
    member runs the exact same math as its own ``pipeline_step`` call —
    the pooled==blocking token-identity argument.
    Heterogeneous progress is safe for the same reason fixed-budget
    stepping is: a member whose cursor overshot its grid (or a pow2 pad
    entry parked at the end, ``pipeline_pool_pad``) executes masked no-ops.

    ``pool_spec``: optional pytree of shardings matching ``carry_pool``
    (parallel/sharding.pool_carry_specs) applied to the pooled tree outside
    the vmap — the per-member internal buf/state constraints are disabled
    (``buf_spec=None``) because raw PartitionSpecs do not compose with the
    vmapped rank.

    Pure ``(params, xs_pool, carry_pool) -> carry_pool`` — jit (and donate
    the carry pool) at the caller; serve/engine.py's ``pool_prefill_step``
    does."""
    def constrain(tree):
        if pool_spec is None:
            return tree
        return jax.tree_util.tree_map(
            lambda a, s: jax.lax.with_sharding_constraint(a, s),
            tree, pool_spec)

    carry_pool = constrain(carry_pool)
    one = jax.tree_util.tree_map(lambda a: a[0], carry_pool)
    sub = jax.vmap(_group_advance(layout, params, apply_block, one,
                                  buf_spec=None, grouped_apply=grouped_apply,
                                  remat=remat, retain_pos=retain_pos))
    # one loop for the whole pool, on a counter that starts at a run-time
    # value (so XLA keeps the loop, as in pipeline_step) and is not batched
    # (so no per-member select of the carry on every iteration)
    j0 = jnp.min(carry_pool["step"])
    _, carry_pool = jax.lax.while_loop(
        lambda jc: jc[0] < j0 + n_groups,
        lambda jc: (jc[0] + 1, sub(xs_pool, jc[1])), (j0, carry_pool))
    return constrain(carry_pool)


def pipeline_pool_pad(xs: jax.Array, carry: Dict, n_steps: int):
    """A no-op pool member shaped like ``(xs, carry)``: zero buffers with
    the group cursor parked at ``n_steps``, so every group it runs is a
    masked no-op (the same overshoot masking fixed-budget stepping relies
    on; zeroed inputs are safe because ``_diag_body`` already applies
    blocks to zeroed invalid slots). Every leaf is a FRESH array — pooled
    steppers donate their carries, so a pad entry must never alias a live
    member or another pad."""
    pad_carry = jax.tree_util.tree_map(jnp.zeros_like, carry)
    pad_carry["step"] = jnp.full((), n_steps, jnp.int32)
    return jnp.zeros_like(xs), pad_carry


def pipeline_finalize(layout: StackLayout, carry: Dict):
    """Unpack a *completed* pipeline carry (``carry['step'] >= S+L-1``):
    returns ``(ys [S, B, T, D], final_state, captured)`` — the same triple
    (captured None unless the carry was built with capture_states) the
    one-shot ``run_diagonal`` produces, with ``captured`` already
    re-gathered into per-boundary snapshots. A streaming carry
    (``pipeline_init(stream_ys=True)``) finalizes to
    ``({"win": ..., "brow": ...}, final_state, captured)`` — the same pair
    ``run_diagonal(stream_ys=True)`` returns."""
    stream = "win" in carry
    S = carry["brow"].shape[0] if stream else carry["ys"].shape[0]
    captured = None
    if "cap" in carry:
        captured = boundary_states_from_capture(layout, carry["cap"], S)
    if stream:
        return {"win": carry["win"], "brow": carry["brow"]}, \
            carry["state"], captured
    return carry["ys"], carry["state"], captured
