"""Continuous-batching serving: the multi-request scheduler is
token-identical (greedy) to running each request alone through the
single-request engine; per-slot positions and masked flushes; the packed
chunk's in-place state writes against the whole-state formulation; the
on-device decode loop matches a host-stepped reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.memory import mem_read, mem_update
from repro.core.schedule import StackLayout
from repro.core.sequential import run_sequential
from repro.models import (decode_state_init, decode_step, flush_segment,
                          init_params)
from repro.models.attention import decode_attend, decode_qkv
from repro.models.blocks import _is_attn
from repro.models.layers import ffn, norm
from repro.models.mamba import mamba_mixer
from repro.models.model import _head_matmul
from repro.models.moe import moe_ffn
from repro.serve import scheduler as sched_mod
from repro.serve import (ContinuousScheduler, Request, RequestError,
                         ServeEngine, StreamEvent)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("h2o-danube-1.8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg, lens, max_new, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(req_id=f"r{i}",
                    prompt=rng.integers(8, cfg.vocab, (L,)).astype(np.int32),
                    max_new=max_new)
            for i, L in enumerate(lens)]


def _collect(events):
    outs = {}
    done = {}
    for ev in events:
        outs.setdefault(ev.req_id, []).append(ev.token)
        assert ev.index == len(outs[ev.req_id]) - 1
        if ev.done:
            done[ev.req_id] = True
    return outs, done


def test_scheduler_token_identical_to_single_request(setup):
    """Acceptance: mixed prompt lengths and segment-boundary phases through
    the pooled scheduler == each request alone, greedy, token for token.
    More requests than slots exercises freeing + re-admission; chunk not
    dividing max_new exercises mid-chunk completion."""
    cfg, params = setup
    seg = cfg.armt.segment_len                     # 16 in the smoke config
    eng = ServeEngine(params, cfg, serve_mode="armt", max_len=256)
    # phases: tail-free (2*seg), one off either side of a boundary, odd tails
    lens = [2 * seg, 2 * seg + 1, seg - 1, 13, 3 * seg + seg // 2]
    max_new = 7
    reqs = _requests(cfg, lens, max_new)
    outs, done = _collect(eng.serve(reqs, n_slots=3, chunk=4))
    assert set(done) == {r.req_id for r in reqs}
    for r in reqs:
        ref = eng.generate(jnp.asarray(r.prompt)[None], max_new).tokens[0]
        assert outs[r.req_id] == ref.tolist(), r.req_id


def test_scheduler_cache_mode(setup):
    cfg, params = setup
    cfg = dataclasses.replace(cfg, armt=None)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(params, cfg, serve_mode="cache", max_len=64)
    # KV-cache overflow is refused, not silently clamped: generate raises,
    # the scheduler streams a structured RequestError (never raises
    # mid-serve — see test_state_store.py for the full error-event matrix)
    with pytest.raises(ValueError, match="max_len"):
        eng.generate(jnp.zeros((1, 60), jnp.int32), 5)
    evs = list(eng.serve(_requests(cfg, [60], 5), n_slots=1))
    assert [type(e) for e in evs] == [RequestError]
    assert evs[0].code == "invalid_request" and "max_len" in evs[0].message
    reqs = _requests(cfg, [9, 21, 14], 5)
    outs, done = _collect(eng.serve(reqs, n_slots=2, chunk=3))
    assert len(done) == 3
    for r in reqs:
        ref = eng.generate(jnp.asarray(r.prompt)[None], 5).tokens[0]
        assert outs[r.req_id] == ref.tolist(), r.req_id


def test_generate_matches_host_stepped_reference(setup):
    """The on-device lax.scan decode loop (flush via lax.cond, sampling on
    device) reproduces a token-by-token host loop exactly."""
    cfg, params = setup
    seg = cfg.armt.segment_len
    eng = ServeEngine(params, cfg, serve_mode="armt", max_len=128)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, seg + 5), 8,
                                 cfg.vocab)
    max_new = 2 * seg    # crosses at least one segment boundary mid-decode
    got = eng.generate(prompts, max_new).tokens

    logits, st, pos, _cached = eng._prefill(prompts)
    step = jax.jit(lambda s, t: decode_step(params, cfg, s, t,
                                            serve_mode="armt"))
    flush = jax.jit(lambda s: flush_segment(params, cfg, s))
    want = np.zeros((2, max_new), np.int32)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for i in range(max_new):
        want[:, i] = np.asarray(tok)
        if i == max_new - 1:
            break
        logits, st = step(st, tok)
        pos += 1
        if pos >= seg:
            st = flush(st)
            pos = 0
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    np.testing.assert_array_equal(got, want)


def test_generate_sampling_determinism_and_validity(setup):
    cfg, params = setup
    eng = ServeEngine(params, cfg, serve_mode="armt", max_len=64)
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 20), 8, cfg.vocab)
    r1 = eng.generate(prompts, 6, temperature=0.7, top_k=4, seed=11)
    r2 = eng.generate(prompts, 6, temperature=0.7, top_k=4, seed=11)
    np.testing.assert_array_equal(r1.tokens, r2.tokens)   # same seed, same out
    assert r1.tokens.min() >= 0 and r1.tokens.max() < cfg.vocab
    r3 = eng.generate(prompts, 6, temperature=5.0, top_k=0, seed=12)
    assert r3.tokens.shape == (2, 6)


def test_per_slot_pos_matches_scalar_pos(setup):
    """decode_step with a per-slot pos vector (all rows at the same phase)
    == the scalar-pos path, logits and cache contents."""
    cfg, params = setup
    B = 2
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, 6), 8, cfg.vocab)
    st_s = decode_state_init(cfg, B, serve_mode="armt", max_len=64,
                             dtype=jnp.float32)
    st_v = decode_state_init(cfg, B, serve_mode="armt", max_len=64,
                             dtype=jnp.float32, per_slot_pos=True)
    assert st_s["pos"].shape == () and st_v["pos"].shape == (B,)
    step = jax.jit(lambda s, t: decode_step(params, cfg, s, t,
                                            serve_mode="armt"))
    for t in range(toks.shape[1]):
        la, st_s = step(st_s, toks[:, t])
        lb, st_v = step(st_v, toks[:, t])
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(st_v["pos"]),
                                  np.full((B,), toks.shape[1]))
    for a, b in zip(jax.tree_util.tree_leaves(
            {"prelude": st_s["prelude"], "pattern": st_s["pattern"]}),
            jax.tree_util.tree_leaves(
            {"prelude": st_v["prelude"], "pattern": st_v["pattern"]})):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_masked_flush_touches_only_masked_rows(setup):
    """flush_segment(slot_mask): flushed rows get the memory update + cache
    and pos reset; unmasked rows are bit-identical untouched."""
    cfg, params = setup
    B = 3
    st = decode_state_init(cfg, B, serve_mode="armt", max_len=64,
                           dtype=jnp.float32, per_slot_pos=True)
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, cfg.armt.segment_len),
                              8, cfg.vocab)
    step = jax.jit(lambda s, t: decode_step(params, cfg, s, t,
                                            serve_mode="armt"))
    for t in range(toks.shape[1]):
        _, st = step(st, toks[:, t])
    mask = jnp.array([True, False, True])
    out = flush_segment(params, cfg, st, slot_mask=mask)
    full = flush_segment(params, cfg, st)            # all-rows reference

    np.testing.assert_array_equal(np.asarray(out["pos"]),
                                  [0, cfg.armt.segment_len, 0])
    for name in ("prelude", "pattern"):
        ax = 0 if name == "prelude" else 1
        for o, s, f in zip(jax.tree_util.tree_leaves(out[name]),
                           jax.tree_util.tree_leaves(st[name]),
                           jax.tree_util.tree_leaves(full[name])):
            o, s, f = np.asarray(o), np.asarray(s), np.asarray(f)
            np.testing.assert_array_equal(np.take(o, 1, axis=ax),
                                          np.take(s, 1, axis=ax))
            np.testing.assert_array_equal(np.take(o, 0, axis=ax),
                                          np.take(f, 0, axis=ax))
            np.testing.assert_array_equal(np.take(o, 2, axis=ax),
                                          np.take(f, 2, axis=ax))
    # the flush actually did something: memory written, caches cleared
    A0 = np.asarray(st["pattern"][0]["A"][:, 0])
    A1 = np.asarray(out["pattern"][0]["A"][:, 0])
    assert not np.array_equal(A0, A1)
    assert np.asarray(out["pattern"][0]["k"][:, 0]).max() == 0


# -- the previous formulation of the packed chunk, kept as the oracle of
# the in-place one: each layer's whole state goes through run_sequential
# (scan xs in, ys out), and every leaf of an inactive row is restored by a
# whole-state jnp.where afterwards

def _where_rows(mask, new, old):
    def sel(axis):
        def one(n, o):
            shape = [1] * n.ndim
            shape[axis] = mask.shape[0]
            return jnp.where(mask.reshape(shape), n, o)
        return one
    return {"prelude": jax.tree_util.tree_map(sel(0), new["prelude"],
                                              old["prelude"]),
            "pattern": jax.tree_util.tree_map(sel(1), new["pattern"],
                                              old["pattern"]),
            "pos": jnp.where(mask, new["pos"], old["pos"])}


def _whole_state_apply(cfg, pos, flush):
    def apply(t, p, x, st):
        new = dict(st)
        if _is_attn(t):
            x = x + mem_read(p["mem"], st, x, cfg.armt)
            q, k, v = decode_qkv(norm(cfg.norm, x, p["ln1"]), p["attn"], cfg,
                                 pos)
            put = jax.vmap(lambda c, u, s: jax.lax.dynamic_update_slice_in_dim(
                c, u, s, axis=0))
            new["k"], new["v"] = put(st["k"], k, pos), put(st["v"], v, pos)
            h = x + decode_attend(q, new["k"], new["v"], p["attn"], cfg, pos)
        else:
            mix, ssm = mamba_mixer(norm(cfg.norm, x, p["ln1"]), p["mixer"],
                                   cfg.ssm, {"h": st["h"], "conv": st["conv"]})
            new.update(ssm)
            h = x + mix
        hn = norm(cfg.norm, h, p["ln2"])
        y = h + (moe_ffn(hn, p["moe"], cfg.moe, cfg.act) if t.endswith("moe")
                 else ffn(cfg.act, hn, p["ffn"]))
        if flush and _is_attn(t):
            new.update(mem_update(p["mem"], {"A": st["A"], "z": st["z"]}, y,
                                  cfg.armt))
            new["k"], new["v"] = (jnp.zeros_like(st["k"]),
                                  jnp.zeros_like(st["v"]))
        return y, new
    return apply


def _whole_state_chunk(params, cfg, state, tok, active, remaining, chunk):
    """The previous chunk body: decode and the boundary flush each through
    run_sequential, inactive (and non-crossing) rows restored by a
    whole-state jnp.where, chunk steps under one lax.scan."""
    layout = StackLayout.from_config(cfg)
    exec_params = {"prelude": params["prelude"], "pattern": params["pattern"]}
    seg = cfg.armt.segment_len
    mem = jnp.broadcast_to(params["mem_tokens"][None],
                           (tok.shape[0],) + params["mem_tokens"].shape)

    def run(state, x, flush):
        layers = {"prelude": state["prelude"], "pattern": state["pattern"]}
        return run_sequential(layout, exec_params, layers, x[None],
                              _whole_state_apply(cfg, state["pos"], flush))

    def body(carry, _):
        state, tok, active, remaining = carry
        ys, fin = run(state, params["embed"][tok][:, None], False)
        logits = _head_matmul(params, cfg, norm(cfg.norm, ys[0, :, -1],
                                                params["final_norm"]))
        new = _where_rows(active, {**fin, "pos": state["pos"] + 1}, state)
        boundary = active & (new["pos"] >= seg)

        def flush(s):
            _, flushed = run(s, mem, True)
            return _where_rows(boundary, {**flushed,
                                          "pos": jnp.zeros_like(s["pos"])}, s)

        new = jax.lax.cond(boundary.any(), flush, lambda s: s, new)
        nxt = jnp.where(active, jnp.argmax(logits.astype(jnp.float32), -1)
                        .astype(jnp.int32), tok)
        remaining = remaining - active.astype(jnp.int32)
        return (new, nxt, active & (remaining > 0), remaining), (tok, active)

    (state, tok, active, _), (toks, masks) = jax.lax.scan(
        body, (state, tok, active, remaining), None, length=chunk)
    return state, tok, active, toks, masks


def _rows(state, b):
    return jax.tree_util.tree_leaves(
        {"prelude": [np.take(np.asarray(a), b, axis=0)
                     for a in jax.tree_util.tree_leaves(state["prelude"])],
         "pattern": [np.take(np.asarray(a), b, axis=1)
                     for a in jax.tree_util.tree_leaves(state["pattern"])],
         "pos": np.asarray(state["pos"])[b]})


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "jamba-1.5-large-398b"])
def test_chunk_writes_state_rows_in_place(arch):
    """The packed chunk writes only the rows it changes, masked at the
    write: against the whole-state oracle above it emits the same tokens,
    leaves every active row's state as the oracle's (to the last bits, see
    below), and leaves a row that is inactive from the start, or finishes
    mid-chunk, bit-exactly at its state before the chunk, or at its
    finish. Slot 0 is inactive, slot 1 finishes after 2 of 4 steps, slot 2
    crosses position seg_len (flush)."""
    cfg = get_smoke_config(arch)
    if cfg.moe is not None:
        # global MoE dispatch couples rows through capacity drops, and an
        # inactive row's activations differ between the two formulations
        # (its K/V row is written back, not replaced): dispatch per row
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch="per_row"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    seg, n, chunk = cfg.armt.segment_len, 4, 4
    pool = decode_state_init(cfg, n, serve_mode="armt", max_len=64,
                             dtype=jnp.float32, per_slot_pos=True)
    # a state with something in every leaf; z > 0 as the update keeps it
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    pool = jax.tree_util.tree_map(
        lambda a: jnp.abs(jax.random.normal(next(keys), a.shape)) * 0.1,
        {"prelude": pool["prelude"], "pattern": pool["pattern"]})
    pool["pos"] = jnp.array([5, 3, seg - 2, 7], jnp.int32)
    tok = jnp.array([11, 12, 13, 14], jnp.int32)
    active = jnp.array([False, True, True, True])
    remaining = jnp.array([0, 2, 9, 9], jnp.int32)

    def program(length):
        return jax.jit(sched_mod._chunk_body_factory(cfg, "armt", seg,
                                                     length))(
            params, pool, tok, active, remaining)

    got, g_tok, g_active, _, g_toks, g_masks = program(chunk)
    at_finish = program(2)[0]                       # slot 1's last step
    want, w_tok, w_active, w_toks, w_masks = jax.jit(
        lambda s: _whole_state_chunk(params, cfg, s, tok, active, remaining,
                                     chunk))(pool)

    np.testing.assert_array_equal(np.asarray(g_toks), np.asarray(w_toks))
    np.testing.assert_array_equal(np.asarray(g_masks), np.asarray(w_masks))
    np.testing.assert_array_equal(np.asarray(g_tok), np.asarray(w_tok))
    np.testing.assert_array_equal(np.asarray(g_active), np.asarray(w_active))
    assert np.asarray(g_masks)[:, 2].all() and np.asarray(got["pos"])[2] == 2
    assert not np.asarray(g_masks)[2:, 1].any()
    # active rows: the same math, but XLA compiles it differently on the
    # CPU in the two programs — the same decode_step reads up to 7 ulps
    # apart on jamba's Mamba state inside the chunk program and alone —
    # so each row is held within 32 ulps of its largest value
    for b in range(n):
        for g, w in zip(_rows(got, b), _rows(want, b)):
            ulp = np.spacing(np.abs(w).max()) if w.dtype.kind == "f" else 0
            assert np.abs(g - w).max() <= 32 * ulp, (b, np.abs(g - w).max())
    for g, w in zip(_rows(got, 0), _rows(pool, 0)):     # inactive throughout
        np.testing.assert_array_equal(g, w)
    for g, w in zip(_rows(got, 1), _rows(at_finish, 1)):  # finished mid-chunk
        np.testing.assert_array_equal(g, w)


def test_scheduler_streaming_order_and_slot_reuse(setup):
    """Events stream in index order per request; slots are reused (more
    requests than slots) and every request completes exactly once."""
    cfg, params = setup
    eng = ServeEngine(params, cfg, serve_mode="armt", max_len=128)
    reqs = _requests(cfg, [5, 9, 17, 33, 21, 8, 12], 4, seed=7)
    sched = ContinuousScheduler(eng, n_slots=2, chunk=3)
    events = list(sched.run(reqs))
    assert all(isinstance(e, StreamEvent) for e in events)
    outs, done = _collect(events)
    assert len(done) == len(reqs)
    assert all(len(v) == 4 for v in outs.values())
