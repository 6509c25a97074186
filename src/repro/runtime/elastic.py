"""Elastic scaling: rebuild the mesh from surviving devices and reshard.

Checkpoints are mesh-agnostic (full arrays per leaf), so scaling down after
losing a pod slice — or up after repair — is: pick the largest supported mesh
that fits the survivors, rebuild shardings from the SAME logical rules, and
`device_put` the restored leaves.  Data-shard assignment is recomputed from
the new data-axis size; the (seed, step, shard)-deterministic pipeline then
yields exactly the right global batch order.
"""

from __future__ import annotations

import jax

from repro.parallel import sharding as shd


def largest_mesh_shape(num_devices: int, model_parallel: int,
                       min_data: int = 1) -> tuple[int, int]:
    """Largest (data, model) grid with the given TP degree that fits."""
    if num_devices < model_parallel:
        # degrade TP to what's available (powers of two)
        mp = 1
        while mp * 2 <= num_devices:
            mp *= 2
        model_parallel = mp
    data = max(num_devices // model_parallel, min_data)
    return data, model_parallel


def remesh(devices, model_parallel: int) -> jax.sharding.Mesh:
    data, model = largest_mesh_shape(len(devices), model_parallel)
    used = devices[: data * model]
    import numpy as np
    dmesh = np.asarray(used).reshape(data, model)
    return jax.sharding.Mesh(dmesh, ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)


def reshard_state(state_host, mesh: jax.sharding.Mesh, pspecs):
    """Place host-restored state onto a (new) mesh via its PartitionSpecs."""
    def put(leaf, ps):
        return jax.device_put(leaf,
                              jax.sharding.NamedSharding(mesh, ps))
    return jax.tree.map(
        put, state_host, pspecs,
        is_leaf=lambda x: not isinstance(x, dict))
