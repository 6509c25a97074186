"""int8-compressed all-reduce: correctness vs plain mean (subprocess with a
multi-device mesh so devices genuinely disagree)."""

import os
import subprocess
import sys

SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.parallel.compression import compressed_psum

mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(4), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
# per-device distinct values, laid out sharded on a leading axis then summed
vals = rng.standard_normal((4, 300)).astype(np.float32) * 5
x = jnp.asarray(vals)

with jax.set_mesh(mesh):
    # build a device-varying replicated-layout tensor via shard_map
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    out = jax.jit(lambda v: compressed_psum(
        jax.shard_map(lambda t: t[0], mesh=mesh, in_specs=P("data", None),
                      out_specs=P(None), check_vma=False)(v),
        "data"))(xs)
ref = vals.mean(axis=0)
err = np.abs(np.asarray(out) - ref)
bound = np.abs(vals).max() / 127 / 2 * 1.5 + 1e-6
assert err.max() <= bound * 4, (err.max(), bound)
print("OK", float(err.max()))
"""


def test_compressed_psum_matches_mean():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SNIPPET],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
