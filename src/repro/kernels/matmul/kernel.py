"""Blocked dense matmul Pallas kernel — the paper's §V-A workload on the MXU.

The BlockSpec tiling is the paper's eq.2 law adapted to VMEM
(`core.tiling.solve_tpu`): the C tile (y, x) is the stationary accumulator in
VMEM (f32), A (y, z) and B (z, x) tiles stream HBM->VMEM with Pallas's
automatic double-buffering — the hardware analogue of the paper's doubled B
buffer.  The A tile's reuse across the N grid axis plays the role of the
paper's broadcast of A to all cores.

The kernel accumulates in f32 regardless of the input dtype (bf16 inputs hit
the MXU's native mixed-precision path) and supports a fused bias/activation
epilogue applied while the C tile is still resident in VMEM — the alternative
is a second elementwise pass that re-reads and re-writes all of C through HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hardware, tiling

# Fused epilogue nonlinearities.  Static strings (jit/cache friendly) rather
# than callables; extend here when a new serving activation shows up.
ACTIVATIONS = {
    None: lambda v: v,
    "relu": lambda v: jnp.maximum(v, 0.0),
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
}


def _epilogue(acc, bias, activation):
    if bias is not None:
        acc = acc + bias
    return ACTIVATIONS[activation](acc)


def _matmul_kernel(*refs, k_steps: int, activation: str | None,
                   has_bias: bool):
    if has_bias:
        a_ref, b_ref, bias_ref, o_ref, acc_ref = refs
    else:
        (a_ref, b_ref, o_ref, acc_ref), bias_ref = refs, None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        # bias block is (1, x) and broadcasts over the tile's y rows.
        bias = None if bias_ref is None else bias_ref[...].astype(jnp.float32)
        o_ref[...] = _epilogue(acc_ref[...], bias, activation).astype(
            o_ref.dtype)


def blocked_matmul(
    a: jax.Array,
    b: jax.Array,
    tile: tiling.Tile,
    bias: jax.Array | None = None,
    activation: str | None = None,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """(M, K) @ (K, N) with explicit (y, x, z) VMEM tiling.

    Shapes must be multiples of the tile (ops.py pads).  ``bias`` is a
    (1, N) row added to C in the epilogue; ``activation`` is a key of
    ``ACTIVATIONS`` applied after the bias, both fused into the final
    k-step's store so C makes exactly one HBM round-trip.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    y, x, z = tile.y, tile.x, tile.z
    assert m % y == 0 and n % x == 0 and k % z == 0, (a.shape, b.shape, tile)
    assert activation in ACTIVATIONS, activation
    out_dtype = out_dtype or a.dtype
    k_steps = k // z

    grid = (m // y, n // x, k_steps)
    in_specs = [
        pl.BlockSpec((y, z), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((z, x), lambda i, j, kk: (kk, j)),
    ]
    operands = [a, b]
    if bias is not None:
        assert bias.shape == (1, n), (bias.shape, n)
        in_specs.append(pl.BlockSpec((1, x), lambda i, j, kk: (0, j)))
        operands.append(bias)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps,
                          activation=activation, has_bias=bias is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((y, x), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((y, x), jnp.float32)],
        # M/N grid axes are independent; only the K axis carries the
        # accumulator, so Mosaic may parallelize the first two.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=hardware.TPU_V5E.usable_vmem()),
        interpret=interpret,
    )(*operands)
