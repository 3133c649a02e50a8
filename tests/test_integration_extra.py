"""Extra integration coverage: Pallas attention inside the model, decode
smoke for every assigned arch, elastic checkpoint resharding."""
import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_smoke_config
from repro.models import (decode_state_init, decode_step, encode, init_params)
from repro.models.model import _fill_cross_kv


def test_model_pallas_attention_matches_dense():
    from repro.models import forward_hidden
    cfg = get_smoke_config("h2o-danube-1.8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 8, cfg.vocab)
    h1, _ = forward_hidden(params, cfg, toks, seg_len=16)
    cfgp = dataclasses.replace(cfg, attn_impl="pallas",
                               kernel_backend="pallas_interpret")
    h2, _ = forward_hidden(params, cfgp, toks, seg_len=16)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_decode_smoke_every_arch(arch):
    """One ARMT/SSM-mode decode step per assigned architecture."""
    cfg = get_smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B = 2
    st = decode_state_init(cfg, B, serve_mode="armt", max_len=64,
                           dtype=jnp.float32)
    if cfg.encoder is not None:
        frames = jax.random.normal(jax.random.PRNGKey(2),
                                   (B, cfg.encoder.n_frames, cfg.d_model))
        eo = encode(params, cfg, frames)
        sub = _fill_cross_kv(params, cfg,
                             {"prelude": st["prelude"],
                              "pattern": st["pattern"]}, eo)
        st = {**st, **sub}
    toks = jax.random.randint(jax.random.PRNGKey(1), (B,), 8, cfg.vocab)
    logits, st2 = decode_step(params, cfg, st, toks, serve_mode="armt")
    assert logits.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all(), arch
    assert int(st2["pos"]) == 1


_ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.configs import get_smoke_config
from repro.models import init_params
from repro.models.model import param_specs
from repro.parallel import sharding as shd

cfg = get_smoke_config("qwen2.5-32b")
params = init_params(cfg, jax.random.PRNGKey(0))
d = tempfile.mkdtemp()
mgr = CheckpointManager(d, async_save=False)
mgr.save(1, params)                       # saved from single-device layout

# restore RESHARDED onto a 2x4 mesh (elastic restart on a new topology)
mesh = jax.make_mesh((2, 4), ("data", "model"))
with mesh:
    specs = shd.param_specs(jax.eval_shape(lambda: params), mesh)
    restored = mgr.restore(params, shardings=specs)
leaf = jax.tree_util.tree_leaves(restored)[0]
ok = np.allclose(np.asarray(leaf), np.asarray(jax.tree_util.tree_leaves(params)[0]))
n_shards = len(leaf.sharding.device_set)
print("ELASTIC_OK", ok, n_shards)
assert ok and n_shards == 8
"""


def test_elastic_resharding_restore():
    r = subprocess.run([sys.executable, "-c", _ELASTIC_SCRIPT],
                       capture_output=True, text=True, timeout=420,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert "ELASTIC_OK True" in r.stdout, (r.stdout[-400:], r.stderr[-1200:])
