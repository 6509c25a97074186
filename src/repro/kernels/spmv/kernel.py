"""Sparse matrix-vector multiply Pallas kernel — the paper's §V-B workload.

Hardware adaptation (DESIGN.md): the paper's MIMD cores absorb nnz imbalance
in *time*; a SIMD/systolic TPU core absorbs it as *padding* in a regular
layout.  So the CSC + round-robin-rows scheme becomes: rows are permuted by
the same balancing law (`core.loadbalance`: round_robin or LPT over nnz),
packed into an ELLPACK (rows, W) layout, and the kernel processes row blocks
of shape (bm, W) with the x vector resident in VMEM (the paper's DMA
cacheline buffer becomes the VMEM-resident gather source).  Balance quality
shows up as the active/fetched ratio reported by the benchmark — the direct
analogue of the paper's "~25% of nnz per core" measurement.

The TPU vector unit gathers only within one 128-lane register row, so
both variants sweep each (bm, W) row block against x 128 columns at a
time: every 128-wide chunk of the ELL block gathers the entries whose
column falls in the current 128-column slab of x and masks the rest
(one masked pass over the ELL block per 128 columns of x).

Two variants:

* ``ell_spmv``          — whole x vector resident in VMEM (caps n at the
                          VMEM budget); the sweep over x runs inside the
                          kernel;
* ``ell_spmv_blocked``  — x streamed in ``block_cols``-sized column slabs
                          by the grid; each (row-block, slab) grid step
                          gathers only the columns that fall inside the
                          current slab and accumulates partial sums in an
                          f32 scratch.  This unlocks n far beyond VMEM, and
                          is the knob the autotuner trades against the
                          active/fetched balance metric.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hardware

LANES = 128


def _slab_products(xs, cols_ref, vals_ref, start):
    """(bm, 128) f32 partial products of one row block against the x slab
    ``x[start:start + 128]`` (``xs``, broadcast over the block's rows).

    Mosaic gathers only within one 128-lane vreg row, so the ELL block is
    walked in 128-wide column chunks and each chunk gathers, lane by
    lane, the entries whose column falls inside the slab; the others are
    masked to 0 and picked up by the slab that holds them."""
    acc = jnp.zeros(xs.shape, jnp.float32)
    for c in range(0, cols_ref.shape[1], LANES):
        local = cols_ref[:, c:c + LANES] - start
        hit = (local >= 0) & (local < LANES)
        gathered = jnp.take_along_axis(xs, jnp.where(hit, local, 0), axis=1)
        vals = vals_ref[:, c:c + LANES].astype(jnp.float32)
        acc += jnp.where(hit, vals * gathered, 0.0)
    return acc


def _spmv_kernel(x_ref, cols_ref, vals_ref, y_ref):
    bm = cols_ref.shape[0]

    def slab(s, acc):
        # x rides as (n_slabs, 128): slab s is one row of it.
        xs = jnp.broadcast_to(x_ref[pl.ds(s, 1), :].astype(jnp.float32),
                              (bm, LANES))
        return acc + _slab_products(xs, cols_ref, vals_ref, s * LANES)

    acc = jax.lax.fori_loop(0, x_ref.shape[0], slab,
                            jnp.zeros((bm, LANES), jnp.float32))
    y_ref[...] = jnp.sum(acc, axis=1, keepdims=True).astype(y_ref.dtype)


def ell_spmv(x: jax.Array, ell_cols: jax.Array, ell_vals: jax.Array,
             block_rows: int = 8, interpret: bool = False) -> jax.Array:
    """y = A @ x with A in padded ELL form.  Rows must divide block_rows;
    x is padded to a multiple of 128 here (every column index is < n, so
    the pad is never hit)."""
    rows, width = ell_cols.shape
    assert rows % block_rows == 0, (rows, block_rows)
    assert width % LANES == 0, (width, LANES)
    xs = jnp.pad(x, (0, -x.shape[0] % LANES)).reshape(-1, LANES)
    grid = (rows // block_rows,)
    y = pl.pallas_call(
        _spmv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(xs.shape, lambda i: (0, 0)),       # x: whole vector
            pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
        ],
        # A (block_rows, 1) column: Mosaic refuses a rank-1 output block
        # that is not a multiple of 128 long.
        out_specs=pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 1), ell_vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=hardware.TPU_V5E.usable_vmem()),
        interpret=interpret,
    )(xs, ell_cols, ell_vals)
    return y[:, 0]


def _spmv_blocked_kernel(x_ref, cols_ref, vals_ref, y_ref, acc_ref, *,
                         n_slabs: int, block_cols: int):
    j = pl.program_id(1)
    bm = cols_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The (1, block_cols) slab is walked 128 lanes at a time.
    for c in range(0, block_cols, LANES):
        xs = jnp.broadcast_to(x_ref[:, c:c + LANES].astype(jnp.float32),
                              (bm, LANES))
        acc_ref[...] += _slab_products(xs, cols_ref, vals_ref,
                                       j * block_cols + c)

    @pl.when(j == n_slabs - 1)
    def _store():
        y_ref[...] = jnp.sum(acc_ref[...], axis=1,
                             keepdims=True).astype(y_ref.dtype)


def ell_spmv_blocked(x: jax.Array, ell_cols: jax.Array, ell_vals: jax.Array,
                     block_rows: int = 8, block_cols: int = 512,
                     interpret: bool = False) -> jax.Array:
    """y = A @ x with x streamed slab-by-slab (n may exceed VMEM).

    ``x`` must be padded to a multiple of ``block_cols`` (ops.py pads; the
    pad region is never referenced because every column index is < n),
    and ``block_cols`` is a multiple of 128.  The ELL block index map is
    constant along the slab axis, so Pallas's revisiting optimization
    fetches cols/vals once per row-block while x slabs stream underneath.
    """
    rows, width = ell_cols.shape
    (n_padded,) = x.shape
    assert rows % block_rows == 0, (rows, block_rows)
    assert n_padded % block_cols == 0, (n_padded, block_cols)
    assert block_cols % LANES == 0 and width % LANES == 0, (block_cols, width)
    n_slabs = n_padded // block_cols
    grid = (rows // block_rows, n_slabs)
    y = pl.pallas_call(
        functools.partial(_spmv_blocked_kernel, n_slabs=n_slabs,
                          block_cols=block_cols),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_cols), lambda i, j: (0, j)),
            pl.BlockSpec((block_rows, width), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, width), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 1), ell_vals.dtype),
        scratch_shapes=[pltpu.VMEM((block_rows, LANES), jnp.float32)],
        # Row blocks are independent; the slab axis carries the accumulator.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=hardware.TPU_V5E.usable_vmem()),
        interpret=interpret,
    )(x.reshape(1, n_padded), ell_cols, ell_vals)
    return y[:, 0]
