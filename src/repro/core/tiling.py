"""Communication-minimizing blocked-matmul tiling (the paper's eq. 2), adapted
from FPGA BRAM to TPU VMEM.

Paper model (section V-A, following their ref. [25])
-----------------------------------------------------
``C = A @ B`` with ``n x n`` operands.  A group of ``p`` cores computes an
``n x (x*p)`` column panel of C; each core owns an ``n x x`` strip processed in
``y x x`` blocks ``C_ij``.  For one row-block ``i`` the ``y x n`` strip of A is
*broadcast once* to all ``p`` cores while each core streams its own ``n x x``
strip of B.  Per-core local memory must hold the B sub-block (``z*x``, doubled
for double-buffering) and the C block (``x*y``).

External traffic for the whole product:

    Q(x, y) = n^3 / (p*x)   (A, broadcast)
            + n^3 / y       (B, reloaded once per row-block)
            + n^2           (C, written once)

subject to ``x*(2z + y) <= L`` with ``z = 1`` (Q is z-independent, so the
paper shrinks z to minimize memory).  Lagrange minimization gives eq. 2:

    y = sqrt(p*L),     x = L / (2 + sqrt(p*L))

TPU adaptation
--------------
``L`` becomes the usable VMEM budget in *elements*.  Two facts change:

* the MXU is a 128x128 systolic array, so tiles must be multiples of 128 and
  ``z = 1`` would waste the contraction dimension entirely.  Q is independent
  of z, so we raise z to an MXU-friendly depth "for free" in traffic — but z
  now occupies VMEM.  The constraint is the scoped VMEM the Pallas kernel
  actually allocates (`Tile.kernel_vmem_bytes`): the pipeline
  double-buffers the A tile ``y*z``, the B tile ``z*x`` and the output
  tile ``y*x``, and the f32 accumulator ``y*x`` is scratch —

      2*(y*z + z*x)*b + 2*x*y*b_out + 4*x*y <= L_bytes

  — the same budget each kernel asks the compiler for, so a tile the model
  admits is one the compiler grants.

* the broadcast of A across cores becomes A-tile *reuse across the grid's N
  axis* inside one chip (p = 1 in-kernel) and an all-gather of the stationary
  operand across chips (p = number of chips sharing the panel).

`solve_paper` returns the faithful eq.2 point; `solve_tpu` returns the
MXU-aligned point found by local search around it.  Both are validated against
brute force in tests/test_tiling.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterable

from repro.core import hardware


@dataclasses.dataclass(frozen=True)
class Tile:
    """A (y, x, z) block assignment: C tile is y*x, contraction depth z."""

    y: int  # rows of the C tile (M axis)
    x: int  # cols of the C tile (N axis)
    z: int  # contraction tile (K axis)

    def kernel_vmem_bytes(self, dtype_bytes: int, out_bytes: int | None = None,
                          accum_bytes: int = 4, buffers: int = 2) -> int:
        """Scoped VMEM of the blocked-matmul kernel on this tile: A, B and
        output blocks, ``buffers``-deep in the Pallas pipeline, plus the
        accumulator scratch."""
        out_bytes = dtype_bytes if out_bytes is None else out_bytes
        return (buffers * ((self.y * self.z + self.z * self.x) * dtype_bytes
                           + self.y * self.x * out_bytes)
                + self.y * self.x * accum_bytes)

    def as_block_shapes(self):
        """BlockSpec shapes for (A, B, C) of a y/x/z-tiled matmul."""
        return (self.y, self.z), (self.z, self.x), (self.y, self.x)


def max_depth(y: int, x: int, budget: int, dtype_bytes: int,
              accum_bytes: int = 4, buffers: int = 2) -> int:
    """Deepest (unaligned) z whose `Tile.kernel_vmem_bytes` fits ``budget``
    for a y x x output tile — that footprint solved for z."""
    return (budget - y * x * (buffers * dtype_bytes + accum_bytes)) // max(
        buffers * (y + x) * dtype_bytes, 1)


def comm_volume(n: int, tile: Tile, p: int = 1) -> float:
    """External-memory traffic (elements) for an n x n matmul — paper's Q."""
    if tile.x <= 0 or tile.y <= 0:
        return math.inf
    return n**3 / (p * tile.x) + n**3 / tile.y + n**2


def comm_volume_rect(m: int, n: int, k: int, tile: Tile, p: int = 1) -> float:
    """Rectangular generalization of Q for an (m,k) @ (k,n) product.

    Each operand is streamed at least once (the ``max(1, ...)`` floors):
    below one tile per axis the fractional panel counts would otherwise
    charge *less* than one full pass over B — exactly the decode regime
    (m = batch << tile.y) where the weight stream is the traffic floor the
    serving batch sweep trades against.
    """
    if tile.x <= 0 or tile.y <= 0:
        return math.inf
    a_traffic = (m * k) * max(1.0, n / (p * tile.x))  # A loaded once per N-panel
    b_traffic = (k * n) * max(1.0, m / tile.y)        # B reloaded per row-block
    c_traffic = m * n
    return a_traffic + b_traffic + c_traffic


def solve_paper(L: int, p: int = 1) -> Tile:
    """Eq. 2 of the paper, verbatim: z = 1, y = sqrt(pL), x = L/(2+sqrt(pL))."""
    if L <= 4:
        return Tile(1, 1, 1)
    y_star = math.sqrt(p * L)
    x_star = L / (2.0 + y_star)
    # Integer repair of the continuous optimum.  The feasible set x(2+y)<=L
    # is a sawtooth in integers, so probe both axes: for integer y near y*,
    # the best x is the constraint maximum L//(2+y); for integer x near x*,
    # the best y is L//x - 2.  Pick the lowest-traffic candidate.
    cands = set()
    for y in {max(1, math.floor(y_star)), max(1, math.ceil(y_star))}:
        cands.add((int(y), max(1, L // (2 + int(y)))))
    for x in {max(1, math.floor(x_star)), max(1, math.ceil(x_star))}:
        y = max(1, L // int(x) - 2)
        cands.add((int(y), int(x)))
    best, best_q = None, math.inf
    for y, x in cands:
        if x * (2 + y) > L:
            continue
        t = Tile(y, x, 1)
        q = comm_volume(4096, t, p)
        if q < best_q:
            best, best_q = t, q
    return best if best is not None else Tile(1, 1, 1)


def _aligned_candidates(upper: int, align: int) -> Iterable[int]:
    v = align
    while v <= max(align, upper):
        yield v
        v += align


def solve_tpu(
    vmem_bytes: int | None = None,
    dtype_bytes: int = 2,
    accum_bytes: int = 4,
    p: int = 1,
    align: int = hardware.MXU_DIM,
    m: int | None = None,
    n: int | None = None,
    k: int | None = None,
    double_buffer: bool = True,
) -> Tile:
    """MXU-aligned tile minimizing traffic under the refined VMEM constraint.

    Searches 128-aligned (y, x, z) near the eq.2 analytical point.  The C
    accumulator is held at ``accum_bytes`` (f32 accumulation on the MXU);
    streamed A/B tiles at ``dtype_bytes``.
    """
    chip = hardware.TPU_V5E
    budget = vmem_bytes if vmem_bytes is not None else chip.usable_vmem()
    db = 2 if double_buffer else 1

    def fits(y: int, x: int, z: int) -> bool:
        return Tile(y, x, z).kernel_vmem_bytes(
            dtype_bytes, accum_bytes=accum_bytes, buffers=db) <= budget

    # Analytical seed: treat L as budget in "effective elements".
    L_eff = budget // max(dtype_bytes, 1)
    seed = solve_paper(L_eff, p)

    def clampdim(v: int, dim: int | None) -> int:
        if dim is None:
            return v
        return min(v, max(align, math.ceil(dim / align) * align))

    best: Tile | None = None
    best_q = math.inf
    y_hi = clampdim(max(align, int(seed.y * 2)), m)
    x_hi = clampdim(max(align, int(seed.x * 4)), n)
    mm = m or 8192
    nn = n or 8192
    kk = k or 8192
    for y in _aligned_candidates(y_hi, align):
        for x in _aligned_candidates(x_hi, align):
            # Largest aligned z that still fits — traffic is z-independent,
            # deeper z amortizes accumulator read/write and MXU pipelining.
            z_max = clampdim(max_depth(y, x, budget, dtype_bytes,
                                       accum_bytes, db), k)
            z = (z_max // align) * align
            if z < align:
                continue
            if not fits(y, x, z):
                continue
            q = comm_volume_rect(mm, nn, kk, Tile(y, x, z), p)
            if q < best_q:
                best_q = q
                best = Tile(y, x, z)
    if best is None:
        # Degenerate VMEM budget: fall back to one MXU tile.
        best = Tile(align, align, align)
    return best


def brute_force_paper(L: int, p: int = 1, n: int = 4096) -> Tile:
    """Exhaustive integer search of the paper's constrained problem (tests).
    x >= 1 requires 2 + y <= L, so y ranges over [1, L-2]."""
    best, best_q = Tile(1, 1, 1), math.inf
    for y in range(1, max(L - 1, 2)):
        x = L // (2 + y)
        if x >= 1:
            q = comm_volume(n, Tile(y, x, 1), p)
            if q < best_q:
                best_q, best = q, Tile(y, x, 1)
    return best
