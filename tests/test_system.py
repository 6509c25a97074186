"""End-to-end behaviour tests of the generated system (replaces the
scaffold placeholder): the paper's design-flow invariants at system level."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model, dse, manycore, tiling
from repro.parallel import sharding as shd


def test_manycore_config_generates_consistent_plan():
    mc = manycore.ManyCoreConfig()
    assert mc.num_chips == 256
    t = mc.matmul_tile(8192, 8192, 8192)
    used = (t.y * t.z + 2 * t.z * t.x) * 2 + t.y * t.x * 4
    assert used <= mc.usable_vmem
    assert "256 chips" in mc.describe()


def test_table1_style_efficiency_from_machine_model():
    """The paper's Table-I structure: efficiency (peak/measured) of the
    eq.2-tiled blocked matmul under the analytical machine model is high
    (paper reports 84-86% on FPGA; the TPU machine model with VMEM-scale
    L gives >95% for MXU-scale matrices)."""
    t = tiling.solve_tpu(m=8192, n=8192, k=8192)
    res = cost_model.matmul_time_model(8192, 8192, 8192, t)
    assert res["efficiency"] > 0.84  # at least the paper's own number


def test_dse_autotune_never_worse_than_eq2_seed():
    m = n = k = 4096
    seed = tiling.solve_tpu(m=m, n=n, k=k)
    tuned = dse.autotune_matmul_tile(m, n, k)
    q_seed = cost_model.matmul_time_model(m, n, k, seed)["time_s"]
    q_tuned = cost_model.matmul_time_model(m, n, k, tuned)["time_s"]
    assert q_tuned <= q_seed * 1.001


def test_roofline_terms_and_dominance():
    r = cost_model.roofline(flops=1e15, bytes_accessed=1e12,
                            collective_bytes=1e11, chips=256,
                            model_flops=9e14)
    assert r.dominant == "compute"
    assert 0 < r.useful_fraction <= 1
    assert r.bound_s == r.compute_s
    r2 = cost_model.roofline(1e12, 1e15, 1e11, 256)
    assert r2.dominant == "memory"


def test_sharding_rules_drop_indivisible_dims():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = shd.single_pod_rules().with_sizes(mesh)
    # sizes say model=1 => constraint becomes fully replicated, no error
    with shd.use_rules(rules):
        x = jnp.zeros((4, 6, 8))
        y = shd.constrain(x, "batch", "seq", "heads")
        assert y.shape == x.shape


def test_sharding_candidates_enumeration():
    cands = dse.sharding_candidates(256)
    assert {"data": 16, "model": 16} in cands
    assert all(c["data"] * c["model"] == 256 for c in cands)
