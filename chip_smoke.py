"""Chip smoke: llama-1b-armt serving end to end on a TPU, at published widths.

Run from the repository root on a machine with a TPU:

    python3 chip_smoke.py              # one chip: phases a-c below
    python3 chip_smoke.py --chips 4    # a four-chip host: mesh serving only

The model is ``get_config("llama-1b-armt")`` (d_model 2048, 16 layers, 32/8
heads, d_ff 8192, vocab 128256, segments of 1024 tokens plus 128 memory
tokens) in bf16, with random weights from ``init_params`` and a fixed seed.
Everything runs in this one process, through the entry points a user calls.

One chip:
  a. ``ServeEngine.generate`` of 16 tokens after a prompt of 17 * 1024 - 8
     tokens: 16 full segments, so the 16-layer diagonal reaches its steady
     band, and a tail that ends 8 tokens short of a segment, so decode
     crosses a segment flush. Diagonal schedule on the fused grouped path
     (compiled Pallas kernels) and on the vmap path, then the sequential
     schedule.
  b. ``ServeEngine.serve`` (the continuous scheduler, interleaved admission)
     with 2 slots, a prefix cache and 4 requests of 2-4 segments; two of
     them share their first 2 segments, so one admission hits the cache.
  c. Checks: finite logits; every request gets the tokens it asked for; the
     fused phase dispatched every kernel as ``impl=pallas`` on ``tpu``; the
     scheduler hit the prefix cache and flushed a segment in decode; the
     next-token logits of fused vs vmap and of diagonal vs sequential at
     the end of each of the 16 full segments (``forward_hidden``, the
     executor the engine's prefill runs) within ``LOGIT_TOL``. With these
     random weights the memory recurrence amplifies bf16 rounding until
     two paths' logits decorrelate by 16 segments (DESIGN.md §7), so the
     limit holds at all 16 segments with the memory's value projection
     zeroed (A stays 0, every read is 0, nothing carries rounding across
     segments), and with the memory on at the first ``CARRY_SEGMENTS``
     segments, where the state has been carried but not yet amplified.
     The whole curve with the memory on is printed.

Four chips (``--chips 4``): ``generate``, prefill and a pooled scheduler
run on a ``data=2,model=2`` mesh against a single-device engine on the first
chip, compared as in c, with the placement of every parameter and pool
array.

Times are host-clock wall seconds; "set-up" includes compilation. None of
them is a device metric. The last line of standard output is the JSON
result. A failed check, or a machine without a TPU, exits non-zero
without it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import parse_mesh  # noqa: E402
from repro.models import (boundary_logits, forward_hidden,  # noqa: E402
                          init_params)
from repro.serve import (ContinuousScheduler, PrefixCache, Request,  # noqa: E402
                         ServeEngine, StreamEvent)
from repro.serve.telemetry import default_registry  # noqa: E402
from repro.utils import use_compile_cache  # noqa: E402

ARCH = "llama-1b-armt"
MAX_NEW = 16
# Relative L2 distance allowed between two paths' next-token logits with the
# memory inert: bf16 rounds at a relative 2^-9 (2e-3); two paths that group
# or fuse the same math differently round in different places, about 8
# times per layer, and independent roundings add in quadrature over 16
# layers: sqrt(16 * 8) * 2e-3 = 2.3e-2, doubled for margin (DESIGN.md §7).
LOGIT_TOL = 5e-2
# Segments over which the memory-on logits are held to LOGIT_TOL: segment 1
# reads an empty memory, segment 2 reads what segment 1 wrote in every layer.
CARRY_SEGMENTS = 2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class Counters:
    """Deltas of the process metrics registry (kernel dispatch, XLA
    compiles, scheduler counters) and of the persistent compile cache."""

    cache = {"cache_hits": 0, "cache_misses": 0}

    @classmethod
    def install(cls) -> None:

        def on_event(event: str, **kw) -> None:
            name = event.rsplit("/", 1)[-1]
            if event.startswith("/jax/compilation_cache/") and \
                    name in cls.cache:
                cls.cache[name] += 1

        jax.monitoring.register_event_listener(on_event)

    def __init__(self):
        self.reg = default_registry()
        self.before = dict(self.reg.counters)
        self.cache_before = dict(self.cache)

    def delta(self, name: str) -> dict:
        return {k: v - self.before.get(k, 0)
                for k, v in self.reg.counters.items()
                if (k == name or k.startswith(name + "{"))
                and v != self.before.get(k, 0)}

    def total(self, name: str) -> float:
        return sum(self.delta(name).values())

    def show(self, *names: str) -> None:
        for name in names:
            for k, v in sorted(self.delta(name).items()):
                print(f"  {k} = {int(v)}")

    def compile_line(self) -> str:
        hits = self.cache["cache_hits"] - self.cache_before["cache_hits"]
        miss = self.cache["cache_misses"] - self.cache_before["cache_misses"]
        return (f"XLA compile {self.total('xla_backend_compile_secs_total'):.1f}"
                f" s over {int(self.total('xla_backend_compiles_total'))}"
                f" programs; persistent cache {hits} hits, {miss} misses")


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def timed(label: str, fn):
    """Run ``fn`` to completion; print its wall time, the compiles in it and
    the first device's peak memory so far."""
    c = Counters()
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    print(f"  {label}: wall {time.perf_counter() - t0:.2f} s; "
          f"{c.compile_line()}; peak_bytes_in_use "
          f"{peak_bytes(jax.devices()[0])}", flush=True)
    return out


def prompt_tokens(cfg, n_tokens: int, seed: int):
    rng = np.random.default_rng(seed)
    return rng.integers(8, cfg.vocab, (n_tokens,)).astype(np.int32)


def inert_memory(params):
    """``params`` with every layer's memory value projection W_V zeroed:
    v = 0 and A = 0 stay exact, so every memory read is exactly 0 and no
    state carries rounding from one segment to the next. All other leaves
    are shared with ``params``."""

    def one(p):
        if "mem" not in p:
            return p
        return dict(p, mem=dict(p["mem"], wv=jnp.zeros_like(p["mem"]["wv"])))

    return dict(params, prelude=tuple(one(p) for p in params["prelude"]),
                pattern=tuple(one(p) for p in params["pattern"]))


def next_logits(eng, prompt):
    logits = np.asarray(eng.prefill(jnp.asarray(prompt))[0], np.float32)
    check(bool(np.isfinite(logits).all()), "non-finite logits")
    return logits


def segment_logits(params, cfg, toks, **path):
    """Next-token logits at the end of every segment of ``toks`` (whole
    segments), [S, B, V] float32, from ``forward_hidden`` on the given
    schedule and grouped path — the executor ``ServeEngine.prefill`` runs
    over a prompt's full segments."""
    hidden, _ = forward_hidden(params, cfg, toks, **path)
    logits = np.asarray(boundary_logits(params, cfg, hidden), np.float32)
    check(bool(np.isfinite(logits).all()), "non-finite segment logits")
    return logits


def run_generate(name: str, params, inert, cfg, prompt, n_full: int,
                 **engine_kw):
    """One path: prefill (set-up), generate (set-up of decode), generate
    again (warm), then the segment logits of the prompt's ``n_full`` full
    segments with the memory on and inert. Returns those two [S, B, V]
    arrays, the tokens and the kernel dispatches counted."""

    print(f"[{name}] {engine_kw}", flush=True)
    c = Counters()
    eng = ServeEngine(params, cfg, **engine_kw)
    toks = jnp.asarray(prompt)
    timed("prefill, set-up", lambda: next_logits(eng, toks))
    first = timed("generate, set-up", lambda: eng.generate(toks, MAX_NEW))
    warm = timed("generate, warm", lambda: eng.generate(toks, MAX_NEW))
    print(f"  ttft (warm, host clock) {warm.ttft_s:.3f} s; decode "
          f"{warm.tok_s:.1f} tok/s (host clock)")
    print(f"  first tokens {warm.tokens[0, :8].tolist()}")
    check(warm.tokens.shape == (prompt.shape[0], MAX_NEW),
          f"{name}: tokens {warm.tokens.shape}")
    check((warm.tokens == first.tokens).all(),
          f"{name}: a repeated generate gave other tokens")
    full = toks[:, :n_full * cfg.armt.segment_len]
    path = dict(schedule=eng.schedule, grouped_impl=eng.grouped_impl)
    on = timed("segment logits, memory on",
               lambda: segment_logits(params, cfg, full, **path))
    off = timed("segment logits, memory inert",
                lambda: segment_logits(inert, cfg, full, **path))
    c.show("kernel_dispatch_total", "armt_update_epilogue_total")
    return on, off, warm.tokens, c.delta("kernel_dispatch_total")


def rel_l2_per_segment(a, b) -> np.ndarray:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    axes = tuple(range(1, a.ndim))
    return (np.sqrt(((a - b) ** 2).sum(axes)) / np.sqrt((b ** 2).sum(axes)))


def compare(what: str, a, b, *, gated: int) -> bool:
    """Print the relative L2 distance of two [S, B, V] logit arrays at every
    segment; True if the first ``gated`` segments are within ``LOGIT_TOL``."""
    d = rel_l2_per_segment(a, b)
    print(f"logit delta {what}: max {d[:gated].max():.3e} over the first "
          f"{gated} of {len(d)} segment(s) (limit {LOGIT_TOL}); per segment "
          + " ".join(f"{x:.2e}" for x in d), flush=True)
    return bool(d[:gated].max() <= LOGIT_TOL)


def phase_generate(params, cfg, seed: int):
    seg = cfg.armt.segment_len
    n_full = cfg.n_layers
    n = (n_full + 1) * seg - 8
    prompt = prompt_tokens(cfg, n, seed + 1)[None]
    print(f"phase a: generate, prompt {n} tokens "
          f"({n // seg} full segments + {n % seg}), {MAX_NEW} new",
          flush=True)
    inert = inert_memory(params)
    out = {}
    for name, kw in (("diagonal-fused", dict(schedule="diagonal",
                                             grouped_impl="fused")),
                     ("diagonal-vmap", dict(schedule="diagonal",
                                            grouped_impl="vmap")),
                     ("sequential", dict(schedule="sequential"))):
        out[name] = run_generate(name, params, inert, cfg, prompt, n_full,
                                 **kw)
    dispatch = out["diagonal-fused"][3]
    check(bool(dispatch), "fused: no kernel dispatched")
    for k in dispatch:
        check("impl=pallas," in k and "backend=tpu" in k,
              f"fused: {k} is not a compiled Pallas kernel on the TPU")
    failed = []
    for a, b in (("diagonal-fused", "diagonal-vmap"),
                 ("diagonal-vmap", "sequential")):
        (on_a, off_a, ta, _), (on_b, off_b, tb, _) = out[a], out[b]
        for what, x, y, gated in (("memory inert", off_a, off_b, len(off_a)),
                                  ("memory on", on_a, on_b, CARRY_SEGMENTS)):
            if not compare(f"{a} vs {b}, {what}", x, y, gated=gated):
                failed.append(f"{a} vs {b}, {what}")
        print(f"  generated tokens equal {int((ta == tb).sum())}/{ta.size}"
              " (not gated)")
    check(not failed, f"logits past {LOGIT_TOL}: {failed}")


def serve_requests(cfg, seed: int):
    """4 requests of 2-4 segments, each 8 tokens short of a segment end;
    r2 shares r0's first 2 segments."""
    seg = cfg.armt.segment_len
    r0 = prompt_tokens(cfg, 3 * seg - 8, seed + 10)
    r2 = np.concatenate([r0[:2 * seg],
                         prompt_tokens(cfg, seg - 8, seed + 12)])
    prompts = [r0, prompt_tokens(cfg, 4 * seg - 8, seed + 11), r2,
               prompt_tokens(cfg, 2 * seg - 8, seed + 13)]
    return [Request(req_id=f"r{i}", prompt=p, max_new=MAX_NEW)
            for i, p in enumerate(prompts)]


def collect(events, reqs) -> dict:
    outs = {}
    for ev in events:
        check(isinstance(ev, StreamEvent), f"scheduler rejected: {ev}")
        outs.setdefault(ev.req_id, []).append(ev.token)
    for r in reqs:
        got = outs.get(r.req_id, [])
        check(len(got) == r.max_new,
              f"{r.req_id}: {len(got)} tokens, asked for {r.max_new}")
    return outs


def phase_serve(params, cfg, seed: int):
    seg = cfg.armt.segment_len
    reqs = serve_requests(cfg, seed)
    print(f"phase b: serve, 2 slots, prompts "
          f"{[len(r.prompt) for r in reqs]} tokens, {MAX_NEW} new each",
          flush=True)
    cache = PrefixCache(seg, max_bytes=1 << 30)
    eng = ServeEngine(params, cfg, schedule="diagonal", grouped_impl="fused",
                      prefix_cache=cache)
    c = Counters()
    outs = timed("serve, set-up",
                 lambda: collect(eng.serve(reqs, n_slots=2), reqs))
    for r in reqs:
        print(f"  {r.req_id}: {len(r.prompt)} prompt tokens -> "
              f"{outs[r.req_id][:8]}...")
    hits = cache.stats.as_dict()["hits"]
    flushes = c.total("decode_flushes_total")
    print(f"  prefix cache {cache.stats.as_dict()}")
    print(f"  decode flushes {int(flushes)}; admissions "
          f"{int(c.total('admissions_total'))}")
    c.show("kernel_dispatch_total", "armt_update_epilogue_total")
    check(hits >= 1, "no prefix-cache hit")
    check(flushes >= 1, "no segment flush in decode")


def placement(name: str, tree) -> None:
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if isinstance(leaf, jax.Array):
            devs = sorted(d.id for d in leaf.sharding.device_set)
            spec = getattr(leaf.sharding, "spec", None)
            print(f"  {name}{jax.tree_util.keystr(path)} {leaf.shape} "
                  f"spec={spec} devices={devs}")


def phase_mesh(params, cfg, seed: int, n_full: int = 4):
    """Mesh serving on data=2,model=2 against one device: generate on a
    batch of 2, the prefill's segment logits (memory inert over
    ``n_full`` segments, memory on over ``CARRY_SEGMENTS``), and a
    scheduler run whose first two admissions are pooled."""

    seg = cfg.armt.segment_len
    mesh = parse_mesh("data=2,model=2")
    print(f"mesh {dict(mesh.shape)} over devices "
          f"{[d.id for d in mesh.devices.flat]}", flush=True)
    inert = inert_memory(params)
    engines = {"one-device": (ServeEngine(params, cfg),
                              ServeEngine(inert, cfg)),
               "mesh": (ServeEngine(params, cfg, mesh=mesh),
                        ServeEngine(inert, cfg, mesh=mesh))}
    placement("mesh params", engines["mesh"][0].params)
    check(any(len(a.sharding.device_set) == mesh.size
              for a in jax.tree_util.tree_leaves(engines["mesh"][0].params)),
          "no parameter is spread over the whole mesh")
    n = (n_full + 1) * seg - 8
    prompt = jnp.asarray(np.stack([prompt_tokens(cfg, n, seed + 20),
                                   prompt_tokens(cfg, n, seed + 21)]))
    print(f"generate: batch 2, prompt {n} tokens, {MAX_NEW} new", flush=True)
    out = {}
    for name, (e, ei) in engines.items():
        res = timed(f"{name} generate, set-up",
                    lambda e=e: e.generate(prompt, MAX_NEW))
        check(res.tokens.shape == (2, MAX_NEW), f"{name}: {res.tokens.shape}")
        off = timed(f"{name} prefill {n_full} segments, memory inert",
                    lambda ei=ei: next_logits(ei, prompt[:, :n_full * seg]))
        on = timed(f"{name} prefill {CARRY_SEGMENTS} segments, memory on",
                   lambda e=e: next_logits(
                       e, prompt[:, :CARRY_SEGMENTS * seg]))
        placement(f"{name} decode state",
                  e.prefill(prompt[:, :n_full * seg + 8])[1])
        out[name] = (on[None], off[None], res.tokens)
    (on1, off1, t1), (onm, offm, tm) = out["one-device"], out["mesh"]
    ok = [compare(f"mesh vs one device, memory inert, segment {n_full}",
                  offm, off1, gated=1),
          compare(f"mesh vs one device, memory on, segment {CARRY_SEGMENTS}",
                  onm, on1, gated=1)]
    check(all(ok), f"mesh logits past {LOGIT_TOL}")
    print(f"generated tokens equal {int((tm == t1).sum())}/{t1.size}"
          " (not gated)")

    reqs = [Request(req_id=f"r{i}", max_new=MAX_NEW,
                    prompt=prompt_tokens(cfg, 3 * seg - 8, seed + 30 + i))
            for i in range(4)]
    print(f"scheduler: 2 slots, prompts {[len(r.prompt) for r in reqs]}",
          flush=True)
    toks = {}
    for name, (e, _) in engines.items():
        sched = ContinuousScheduler(e, n_slots=2)
        toks[name] = timed(f"{name} serve, set-up",
                           lambda s=sched: collect(s.run(reqs), reqs))
        pooled = e.compile_counts()["pool_steps"]
        print(f"  {name}: pooled admission programs {pooled}")
        check(pooled >= 1, f"{name}: no admission was pooled")
        placement(f"{name} pool", sched.pool)
    same = sum(a == b for r in reqs for a, b in
               zip(toks["one-device"][r.req_id], toks["mesh"][r.req_id]))
    print(f"scheduler tokens equal {same}/{len(reqs) * MAX_NEW} (not gated)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: mesh serving on a four-chip host, nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = use_compile_cache()      # before the first compile
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    print(f"device_kind {dev.device_kind}; {len(devices)} device(s); "
          f"compile cache {cache_dir}", flush=True)
    Counters.install()
    cfg = get_config(ARCH)
    check(cfg.dtype == "bfloat16", f"{ARCH} dtype {cfg.dtype}")
    params = timed("init_params, set-up", lambda: jax.jit(
        lambda k: init_params(cfg, k))(jax.random.PRNGKey(args.seed)))
    n_bytes = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    print(f"{ARCH}: d_model {cfg.d_model}, {cfg.n_layers} layers, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab}, {cfg.dtype}; parameter bytes {n_bytes}", flush=True)
    check(all(a.dtype == jnp.bfloat16
              for a in jax.tree_util.tree_leaves(params)), "params not bf16")

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(params, cfg, args.seed)
    else:
        phase_generate(params, cfg, args.seed)
        phase_serve(params, cfg, args.seed)
    print(f"all phases: wall {time.perf_counter() - t0:.1f} s; "
          f"peak_bytes_in_use {peak_bytes(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
