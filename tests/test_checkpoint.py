"""Checkpointing: roundtrip, atomic commit, retention, async semantics."""

import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 16)),
                   "b": jnp.zeros(16)},
        "opt": {"step": jnp.asarray(3), "m": {"w": jnp.ones((8, 16))}},
    }


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    state = _state()
    mgr.save(10, state, blocking=True)
    restored, meta = mgr.restore(None, jax.eval_shape(lambda: state))
    assert meta["step"] == 10
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_uncommitted_checkpoints_ignored(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    state = _state()
    mgr.save(10, state, blocking=True)
    # simulate a crash mid-save at step 20: dir exists, no COMMITTED marker
    fake = tmp_path / "step_0000000020"
    fake.mkdir()
    (fake / "0.npy").write_bytes(b"garbage")
    assert mgr.latest_step() == 10
    restored, meta = mgr.restore(None, jax.eval_shape(lambda: state))
    assert meta["step"] == 10


def test_keep_n_retention(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    state = _state()
    for s in (1, 2, 3, 4):
        mgr.save(s, state, blocking=True)
    steps = sorted(mgr._committed_steps())
    assert steps == [3, 4]


def test_structure_mismatch_rejected(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _state(), blocking=True)
    wrong = {"params": {"w": jnp.zeros((8, 16))}}  # missing leaves
    with pytest.raises(AssertionError):
        mgr.restore(None, jax.eval_shape(lambda: wrong))


def test_async_save_overlaps_then_waits(tmp_path):
    mgr = CheckpointManager(tmp_path)
    state = _state()
    mgr.save(5, state)          # non-blocking
    mgr.wait()
    assert mgr.latest_step() == 5


def test_restore_with_target_shardings(tmp_path):
    """Mesh-agnostic restore: device_put onto explicit shardings."""
    mgr = CheckpointManager(tmp_path)
    state = _state()
    mgr.save(7, state, blocking=True)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = jax.tree.map(
        lambda _: jax.sharding.NamedSharding(mesh,
                                             jax.sharding.PartitionSpec()),
        state)
    restored, _ = mgr.restore(None, jax.eval_shape(lambda: state), sh)
    leaf = jax.tree.leaves(restored)[0]
    assert leaf.sharding.mesh.shape == {"data": 1}
