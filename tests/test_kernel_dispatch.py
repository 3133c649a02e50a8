"""Dispatch + autotune subsystem (kernels/dispatch.py, kernels/autotune.py;
DESIGN.md §14): resolution order, shape bucketing, the sweep/validate/cache
loop, and the acceptance invariants — a warm cache performs ZERO sweep
launches, and every config the dispatcher can hand out bit-validates in
interpret mode against the ref.py oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, ops, ref
from repro.kernels.autotune import _REFS, Autotuner, config_space, run_op
from repro.kernels.dispatch import KernelConfig
from repro.serve.telemetry import MetricsRegistry, default_registry


@pytest.fixture
def cache(tmp_path):
    """Point the dispatch cache at a throwaway file; restore after."""
    path = str(tmp_path / "kernel_cache.json")
    dispatch.set_cache_path(path)
    yield path
    dispatch.set_cache_path(None)


def _tiny_args(op):
    """Small, fast operand sets per op (interpret-mode friendly)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 10)
    if op == "grouped_matmul":
        return (jax.random.normal(ks[0], (2, 16, 12)),
                jax.random.normal(ks[1], (2, 12, 20))), {}
    if op == "grouped_matmul_armt_update":
        G, R, K, D, dm, M = 2, 12, 8, 16, 4, 2
        P = 6 * dm
        return (jax.random.normal(ks[0], (G, R, K)) * 0.3,
                jax.random.normal(ks[1], (G, K, D)) * 0.3,
                jax.random.normal(ks[2], (G, R, D)) * 0.3,
                jax.random.normal(ks[3], (G, D, dm)) * 0.3,
                jax.random.normal(ks[4], (G, D, D)) * 0.3,
                jax.random.normal(ks[5], (G, D, 1)) * 0.3,
                jax.random.normal(ks[6], (G, P, D)) * 0.1,
                jax.random.normal(ks[7], (G, P)) * 0.1), {"M": M}
    if op == "flash_attention":
        q = jax.random.normal(ks[0], (2, 2, 16, 8))
        k = jax.random.normal(ks[1], (2, 2, 16, 8))
        v = jax.random.normal(ks[2], (2, 2, 16, 8))
        return (q, k, v), {}
    if op == "decode_attention":
        return (jax.random.normal(ks[0], (2, 2, 8)),
                jax.random.normal(ks[1], (2, 16, 2, 8)),
                jax.random.normal(ks[2], (2, 16, 2, 8)),
                jnp.array([3, 16], jnp.int32)), {}
    if op == "armt_read":
        dm = 4
        return (jax.random.normal(ks[0], (2, 8, 12)),
                jax.random.normal(ks[1], (12, dm)) * 0.3,
                jax.random.normal(ks[2], (2, 6 * dm, 16)) * 0.1,
                jax.random.uniform(ks[3], (2, 6 * dm))), {}
    if op == "armt_update":
        dm = 4
        return (jax.random.normal(ks[0], (2, 2, 12)),
                jax.random.normal(ks[1], (12, dm)) * 0.3,
                jax.random.normal(ks[2], (12, 16)) * 0.3,
                jax.random.normal(ks[3], (12, 1)) * 0.3,
                jax.random.normal(ks[4], (2, 6 * dm, 16)) * 0.1,
                jax.random.uniform(ks[5], (2, 6 * dm))), {}
    if op == "mamba_scan":
        return (jax.random.normal(ks[0], (1, 8, 8)) * 0.5,
                jax.nn.softplus(jax.random.normal(ks[1], (1, 8, 8))),
                jax.random.normal(ks[2], (1, 8, 4)) * 0.5,
                jax.random.normal(ks[3], (1, 8, 4)) * 0.5,
                jnp.log(jnp.tile(jnp.arange(1., 5.)[None], (8, 1))),
                jnp.ones(8),
                jax.random.normal(ks[4], (1, 8, 4)) * 0.1), {}
    raise ValueError(op)


# ---------------------------------------------------------------- resolution

def test_cpu_default_dispatches_to_xla(cache):
    cfg = dispatch.resolve("grouped_matmul", ((2, 16, 12), (2, 12, 20)),
                           jnp.float32)
    assert cfg.impl == "xla"


def test_per_call_override_beats_everything(cache):
    cfg = dispatch.resolve("grouped_matmul", ((2, 16, 12), (2, 12, 20)),
                           jnp.float32, use_kernel=True, interpret=True)
    assert cfg.impl == "pallas" and cfg.interpret
    cfg = dispatch.resolve("flash_attention", ((2, 2, 16, 8),) * 2,
                           jnp.float32, use_kernel=False)
    assert cfg.impl == "xla"


def test_kernel_backend_knob(cache):
    shapes = ((2, 16, 12), (2, 12, 20))
    cfg = dispatch.resolve("grouped_matmul", shapes, jnp.float32,
                           kernel_backend="pallas_interpret")
    assert cfg.impl == "pallas" and cfg.interpret
    cfg = dispatch.resolve("grouped_matmul", shapes, jnp.float32,
                           kernel_backend="xla")
    assert cfg.impl == "xla"
    # explicit per-call override still wins over the knob
    cfg = dispatch.resolve("grouped_matmul", shapes, jnp.float32,
                           kernel_backend="pallas", use_kernel=False)
    assert cfg.impl == "xla"


def test_shape_bucketing_pow2():
    k1 = dispatch.cache_key("cpu", "grouped_matmul",
                            ((2, 60, 33), (2, 33, 100)), jnp.float32)
    k2 = dispatch.cache_key("cpu", "grouped_matmul",
                            ((2, 64, 64), (2, 64, 128)), jnp.float32)
    assert k1 == k2                      # same pow2 bucket
    k3 = dispatch.cache_key("cpu", "grouped_matmul",
                            ((2, 65, 64), (2, 64, 128)), jnp.float32)
    assert k3 != k2                      # crossed a pow2 boundary
    assert dispatch.cache_key("tpu", "grouped_matmul",
                              ((2, 64, 64), (2, 64, 128)),
                              jnp.bfloat16) != k2   # backend+dtype keyed


def test_dispatch_counters_in_registry(cache):
    reg = default_registry()
    reg.remove_series("kernel_dispatch_total")
    dispatch.resolve("armt_read", ((2, 8, 12), (2, 24, 16)), jnp.float32)
    key = ("kernel_dispatch_total{backend=cpu,impl=xla,op=armt_read,"
           "source=heuristic}")
    assert reg.counters.get(key) == 1


def test_interpret_dispatch_is_counted_as_interpret(cache):
    reg = default_registry()
    reg.remove_series("kernel_dispatch_total")
    dispatch.resolve("armt_read", ((2, 8, 12), (2, 24, 16)), jnp.float32,
                     use_kernel=True, interpret=True)
    key = ("kernel_dispatch_total{backend=cpu,impl=pallas_interpret,"
           "op=armt_read,source=override}")
    assert reg.counters.get(key) == 1


def test_unknown_backend_is_an_error(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "metal")
    with pytest.raises(RuntimeError, match="metal"):
        dispatch.backend()


def test_corrupt_cache_is_an_error(cache):
    with open(cache, "w") as f:
        f.write("{not json")
    with pytest.raises(ValueError, match="corrupt kernel autotune cache"):
        dispatch.resolve("armt_read", ((2, 8, 12), (2, 24, 16)), jnp.float32)


def test_default_cache_path_is_inside_the_checkout(monkeypatch):
    from repro.utils import CHECKOUT_ROOT
    monkeypatch.delenv("REPRO_KERNEL_CACHE", raising=False)
    assert dispatch.default_cache_path() == str(
        CHECKOUT_ROOT / ".kernel_cache.json")


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR is left to
    JAX untouched; unset, the cache goes to a fixed path in the checkout."""
    from repro.utils import CHECKOUT_ROOT, use_compile_cache
    was = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    try:
        got = use_compile_cache()
        if env is None:
            assert got == str(CHECKOUT_ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env
            assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_heuristic_table_covers_every_op_and_backend():
    for bk in dispatch.BACKENDS:
        for op in dispatch.OPS:
            cfg = dispatch.heuristic(op, bk)
            assert cfg.impl in ("xla", "pallas")
            if bk == "cpu":
                assert cfg.impl == "xla"
            if bk == "interpret":
                assert cfg.interpret


# ---------------------------------------------------------------- autotuner

def test_cold_sweep_then_warm_cache_hits_zero_sweeps(cache):
    """The acceptance invariant: first run sweeps + validates + persists;
    a second run (fresh tuner, fresh registry, reloaded disk cache)
    performs ZERO sweep launches and serves the same winner."""
    args, kw = _tiny_args("grouped_matmul")
    reg1 = MetricsRegistry()
    tuner1 = Autotuner(cache, registry=reg1)
    winner = tuner1.get_or_tune("grouped_matmul", args, backend="interpret",
                                repeats=1, op_kwargs=kw)
    sweeps = sum(v for k, v in reg1.counters.items()
                 if k.startswith("autotune_sweep_total"))
    assert sweeps > 0
    assert reg1.counters.get(
        "autotune_validate_total{op=grouped_matmul,result=pass}", 0) >= 1

    dispatch.set_cache_path(cache)       # drop in-memory table -> disk read
    reg2 = MetricsRegistry()
    tuner2 = Autotuner(cache, registry=reg2)
    again = tuner2.get_or_tune("grouped_matmul", args, backend="interpret",
                               repeats=1, op_kwargs=kw)
    assert again == winner
    assert sum(v for k, v in reg2.counters.items()
               if k.startswith("autotune_sweep_total")) == 0
    assert reg2.counters.get(
        "autotune_cache_hit_total{op=grouped_matmul}") == 1


def test_dispatch_serves_tuned_winner(cache):
    """After tuning, plain dispatch.resolve (the trace-time path) returns
    the cached winner for any shape in the same bucket."""
    args, kw = _tiny_args("armt_update")
    reg = MetricsRegistry()
    tuner = Autotuner(cache, registry=reg)
    winner = tuner.get_or_tune("armt_update", args, backend="cpu",
                               repeats=1, op_kwargs=kw)
    shapes = (args[0].shape, args[4].shape)
    got = dispatch.resolve("armt_update", shapes, args[0].dtype)
    assert got == winner


def test_validation_rejects_wrong_results(cache, monkeypatch):
    """A candidate whose output disagrees with the oracle must not win."""
    args, kw = _tiny_args("grouped_matmul")
    reg = MetricsRegistry()
    tuner = Autotuner(cache, registry=reg)
    monkeypatch.setitem(
        _REFS, "grouped_matmul",
        lambda x, w, b=None, **_: jnp.einsum("gmk,gkn->gmn", x, w) * 1.5)
    assert not tuner.validate("grouped_matmul", args,
                              KernelConfig(impl="xla"), op_kwargs=kw)
    assert reg.counters.get(
        "autotune_validate_total{op=grouped_matmul,result=fail}") == 1


def test_sweep_drops_unlowerable_candidates(cache):
    """Candidates that violate an op's shape constraints (e.g. the fused
    ARMT epilogue with mem rows straddling the last m-tile) are dropped,
    not fatal."""
    args, kw = _tiny_args("grouped_matmul_armt_update")
    reg = MetricsRegistry()
    tuner = Autotuner(cache, registry=reg)
    ranked = tuner.sweep("grouped_matmul_armt_update", args,
                         backend="interpret", repeats=1, op_kwargs=kw)
    assert ranked                         # something survived
    for cfg, t in ranked:
        assert t >= 0.0


# ------------------------------------------------- config bit-validation

@pytest.mark.parametrize("op", dispatch.OPS)
@pytest.mark.parametrize("bk", ["tpu", "gpu"])
def test_every_heuristic_config_bit_validates(op, bk):
    """Every config the heuristic table can dispatch runs the actual
    kernel body (interpret lowering) and matches the jnp oracle — the
    'every dispatched kernel config is bit-validated' acceptance gate."""
    cfg = dataclasses.replace(dispatch.heuristic(op, bk), impl="pallas",
                              interpret=True)
    args, kw = _tiny_args(op)
    got = run_op(op, args, cfg, **kw)
    want = _REFS[op](*args, **kw)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-5, rtol=2e-4), got, want)


def test_config_space_sane():
    for op in dispatch.OPS:
        cpu = config_space(op, "cpu")
        # CPU never sweeps pallas (interpret is a validation lowering,
        # not an execution engine); flash_attention additionally sweeps
        # the XLA-lowering variants (fast_softmax / causal_blocks)
        assert cpu[0] == dispatch.XLA
        assert all(c.impl == "xla" for c in cpu)
        if op == "flash_attention":
            assert any(c.fast_softmax for c in cpu)
            assert any(c.causal_blocks for c in cpu)
        else:
            assert cpu == [dispatch.XLA]
        interp = config_space(op, "interpret")
        assert interp and all(c.interpret for c in interp)
        tpu = config_space(op, "tpu")
        assert dispatch.XLA in tpu       # XLA-native always competes
        assert any(c.impl == "pallas" for c in tpu)


def test_cpu_attention_variants_validate_against_oracle():
    """Every CPU flash_attention candidate (and the heuristic winner) is
    numerically validated against the grouped oracle on the 5-D layout —
    the same gate autotuned winners pass before entering the cache."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 1, 16, 2, 8))
    k = jax.random.normal(ks[1], (2, 1, 16, 2, 8))
    v = jax.random.normal(ks[2], (2, 1, 16, 2, 8))
    reg = MetricsRegistry()
    tuner = Autotuner(persist=False, registry=reg)
    for cfg in config_space("flash_attention", "cpu"):
        assert tuner.validate("flash_attention", (q, k, v), cfg)
    assert tuner.validate("flash_attention", (q, k, v),
                          dispatch.heuristic("flash_attention", "cpu"))
    # the exact oracle config stays bitwise-equal to the grouped ref
    got = run_op("flash_attention", (q, k, v), dispatch.XLA)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_REFS["flash_attention"](q, k, v)))
