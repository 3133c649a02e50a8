"""Compile guard for the chip: the six Pallas kernels of the serving path,
at llama-1b-armt's published widths and with the TPU heuristic blocks of
kernels/dispatch.py, compiled for a described TPU v5e; then the serving
programs that hold the most device memory, whose compiled footprint must
fit the chip. Nothing runs, so no chip is needed; what interpret mode
cannot see fails here — a block whose last two dims are not a legal TPU
tile, more VMEM than a kernel may use, or more HBM than the chip has.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library at a time, and every test
worker imports this file. Keep these tests in this one file."""
import dataclasses
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import dispatch, ops

CFG = get_config("llama-1b-armt")
G = CFG.n_layers                                   # one anti-diagonal group
R = CFG.armt.segment_len + CFG.armt.num_mem_tokens  # segment + memory rows
D, F, M = CFG.d_model, CFG.d_ff, CFG.armt.num_mem_tokens
DM = CFG.armt.d_mem
P = 2 * CFG.armt.nu * DM                           # DPFP feature dim
H, KV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
SLOTS = 4                                          # decode batch


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:             # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache off meanwhile
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _cases(sh):
    bf, f32 = jnp.bfloat16, jnp.float32

    def s(shape, dt=bf):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    def cfg(op):
        return dispatch.heuristic(op, "tpu")

    return {
        "grouped_matmul": (
            lambda x, w, b: ops.grouped_gemm(
                x, w, b, activation="silu", config=cfg("grouped_matmul")),
            (s((G, R, D)), s((G, D, F)), s((G, F)))),
        "grouped_matmul_armt_update": (
            lambda x, w, res, wk, wv, wb, A, z: ops.grouped_gemm_armt_update(
                x, w, res, wk, wv, wb, A, z, M=M,
                config=cfg("grouped_matmul_armt_update")),
            (s((G, R, F)), s((G, F, D)), s((G, R, D)), s((G, D, DM)),
             s((G, D, D)), s((G, D, 1)), s((G, P, D), f32),
             s((G, P), f32))),
        "flash_attention": (
            lambda q, k, v: ops.segment_attention(
                q, k, v, config=cfg("flash_attention")),
            (s((G, H, R, HD)), s((G, KV, R, HD)), s((G, KV, R, HD)))),
        "decode_attention": (
            lambda q, k, v, n: ops.decode_attention(
                q, k, v, n, config=cfg("decode_attention")),
            (s((SLOTS, H, HD)), s((SLOTS, R, KV, HD)), s((SLOTS, R, KV, HD)),
             s((SLOTS,), jnp.int32))),
        "armt_read": (
            lambda x, wq, A, z: ops.assoc_read(
                x, wq, A, z, config=cfg("armt_read")),
            (s((G, R, D)), s((G, D, DM)), s((G, P, D), f32),
             s((G, P), f32))),
        "armt_update": (
            lambda m, wk, wv, wb, A, z: ops.assoc_update(
                m, wk, wv, wb, A, z, config=cfg("armt_update")),
            (s((G, M, D)), s((G, D, DM)), s((G, D, D)), s((G, D, 1)),
             s((G, P, D), f32), s((G, P), f32))),
    }


@pytest.mark.parametrize("op", [
    "grouped_matmul", "grouped_matmul_armt_update", "flash_attention",
    "decode_attention", "armt_read", "armt_update"])
def test_kernel_compiles_for_v5e(one_chip, op):
    assert dispatch.heuristic(op, "tpu").impl == "pallas"
    fn, args = _cases(one_chip)[op]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), op


HBM_BYTES = 16 * 2**30                              # one TPU v5e chip


def _serving_program(sh, program):
    """(jitted fn, abstract args) of one serving program at published
    widths; kernel_backend='pallas' picks the TPU kernels off the TPU."""
    from repro.core import diagonal as diag
    from repro.core.schedule import StackLayout
    from repro.models import (decode_state_init, forward_hidden, init_params,
                              init_state, last_logits)
    from repro.serve import ServeEngine
    from repro.serve.scheduler import scheduler_fns

    cfg = dataclasses.replace(CFG, kernel_backend="pallas")

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree)

    params = place(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    seg = cfg.armt.segment_len
    if program == "fused_prefill_16_segments":
        # the blocking prefill's banded driver, jitted: its per-phase weight
        # slices once needed 15.3 GB of temp here (core/diagonal.py)
        fn = jax.jit(lambda p, t: last_logits(p, cfg, forward_hidden(
            p, cfg, t, schedule="diagonal", grouped_impl="fused")[0]))
        return fn, (params, jax.ShapeDtypeStruct((1, 16 * seg), jnp.int32,
                                                 sharding=sh))
    eng = ServeEngine(params, cfg, grouped_impl="fused")
    if program == "admission_step":
        state0 = init_state(cfg, 1, "segmented", jnp.bfloat16)
        xs, carry = place(jax.eval_shape(
            lambda x: diag.pipeline_init(StackLayout.from_config(cfg),
                                         state0, x, capture_states=True),
            jax.ShapeDtypeStruct((2, 1, R, D), jnp.bfloat16)))
        return eng.pool_prefill_step(2, 1, True, 4, 2), (
            params, (xs, xs), (carry, carry))
    pool = place(jax.eval_shape(lambda: decode_state_init(
        cfg, SLOTS, serve_mode="armt", max_len=eng.max_len,
        dtype=jnp.bfloat16, per_slot_pos=True)))
    vec = [jax.ShapeDtypeStruct((SLOTS,), dt, sharding=sh)
           for dt in (jnp.int32, jnp.bool_, jnp.int32)]
    return scheduler_fns(eng, 8)[0], (params, pool, *vec)


@pytest.mark.parametrize("program", [
    "fused_prefill_16_segments", "admission_step", "decode_chunk"])
def test_serving_program_fits_v5e(one_chip, program):
    fn, args = _serving_program(one_chip, program)
    ma = fn.lower(*args).compile().memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used <= HBM_BYTES, (program, used)


# -- the scheduler's decode chunk as `danube.chat` runs it: the
# h2o-danube-1.8b-armt configuration of the chip benchmark, 16 slots,
# chunk 8, the pool donated

DANUBE_SLOTS, DANUBE_CHUNK = 16, 8
# compiled temp bytes of that chunk program: 0.79 GB with the in-place
# decode writes, 6.56 GB with the whole-state select of every step
# (PERF.md §5)
DANUBE_CHUNK_TEMP_BYTES = 1_250_000_000


@pytest.fixture(scope="module")
def danube_chunk(one_chip):
    """(HLO text, memory analysis, pool leaf shapes) of the compiled chunk
    program, compiled once for every test that reads it."""
    import json
    import sys
    from pathlib import Path
    from repro.models import decode_state_init, init_params
    from repro.serve.scheduler import _chunk_body_factory

    chip = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    sys.path.insert(0, str(chip))
    import run
    conf = json.loads((chip / "configs" / "h2o-danube-1.8b-armt.json")
                      .read_text())
    cfg = run.build_cfg(conf)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = place(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    pool = place(jax.eval_shape(lambda: decode_state_init(
        cfg, DANUBE_SLOTS, serve_mode="armt", max_len=4096,
        dtype=jnp.bfloat16, per_slot_pos=True)))
    vec = [jax.ShapeDtypeStruct((DANUBE_SLOTS,), dt, sharding=one_chip)
           for dt in (jnp.int32, jnp.bool_, jnp.int32)]
    fn = jax.jit(_chunk_body_factory(cfg, "armt", cfg.armt.segment_len,
                                     DANUBE_CHUNK),
                 donate_argnums=(1, 2, 3, 4))
    compiled = fn.lower(params, pool, *vec).compile()
    shapes = {tuple(a.shape) for a in jax.tree_util.tree_leaves(
        {"prelude": pool["prelude"], "pattern": pool["pattern"]})}
    return compiled.as_text(), compiled.memory_analysis(), shapes


def _hlo_computations(text):
    """{computation name: its instruction lines} of an HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


_CALLEE = re.compile(r"(?:body|condition|calls|to_apply|true_computation|"
                     r"false_computation)=%([\w.\-]+)")


def _callees(line):
    names = _CALLEE.findall(line)
    branches = re.search(r"branch_computations=\{([^}]*)\}", line)
    if branches:
        names += [b.strip().lstrip("%") for b in branches.group(1).split(",")]
    return names


def _closure(comps, roots, skip=frozenset()):
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen or c in skip or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            if " conditional(" not in line:
                todo += _callees(line)
    return seen


def test_decode_chunk_writes_no_whole_pool_leaf(danube_chunk):
    """Each decode step writes the rows it changes in place: inside the
    program's loops, apart from the flush the conditional encloses, no
    select, broadcast or copy has the shape of a whole pool leaf."""
    text, _, shapes = danube_chunk
    assert text.startswith("HloModule jit_chunk_fn")
    assert text.count(" conditional(") == 1
    comps = _hlo_computations(text)
    flush = _closure(comps, [c for lines in comps.values() for line in lines
                             if " conditional(" in line
                             for c in _callees(line)])
    bodies = [b for c, lines in comps.items() if c not in flush
              for line in lines if " while(" in line
              for b in re.findall(r"body=%([\w.\-]+)", line)]
    assert bodies
    op = re.compile(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                    r"(select|broadcast|copy|copy-start)\(")
    whole = [(c, line.strip()[:160]) for c in _closure(comps, bodies, flush)
             for line in comps[c] if (m := op.match(line))
             and tuple(int(d) for d in m.group(1).split(",") if d) in shapes]
    assert not whole, whole


def test_decode_chunk_temp_bytes(danube_chunk):
    _, ma, _ = danube_chunk
    assert ma.temp_size_in_bytes <= DANUBE_CHUNK_TEMP_BYTES, \
        ma.temp_size_in_bytes
