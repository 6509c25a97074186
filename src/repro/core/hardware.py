"""Hardware constants for the target machine (TPU v5e pod).

The paper parameterizes an FPGA (LUT/DSP/BRAM budgets, frequency).  On a fixed
TPU target the analogous description is the peak-rate triple below plus the
VMEM capacity that plays the role of the paper's per-core local memory ``L``.

`CHIPS` keys each chip's constants by the ``device_kind`` JAX reports for
it; `chip_for` raises for a kind that has no row, so no chip is ever
priced with another's numbers.  The module stays free of jax device state:
callers pass the kind they observed.
"""

from __future__ import annotations

import dataclasses

# Per-chip peaks (TPU v5e): Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
PEAK_FLOPS_BF16 = 197e12      # FLOP/s per chip (bf16 MXU)
HBM_BW = 819e9                # bytes/s per chip
ICI_BW_PER_LINK = 50e9        # bytes/s per ICI link

# Memory capacities.
HBM_BYTES = 16 * 2**30        # 16 GiB HBM per v5e chip
VMEM_BYTES = 128 * 2**20      # ~128 MiB VMEM per core (v5e); the paper's "L"
# Headroom for pipelining/semaphores/spills.  The DSE prices tiles against
# `Chip.usable_vmem()` and every Pallas kernel asks the compiler for the
# same number as its scoped-VMEM limit (`vmem_limit_bytes`), so a tile the
# model admits is a tile the compiler grants room for.
VMEM_USABLE_FRACTION = 0.75

# MXU systolic array dimension — tiles should be multiples of this.
MXU_DIM = 128
# Lane/sublane granularity for the VPU (last dim 128, second-minor 8 for f32).
LANE = 128
SUBLANE = 8

DTYPE_BYTES = {
    "float32": 4, "f32": 4,
    "bfloat16": 2, "bf16": 2,
    "float16": 2, "f16": 2,
    "int8": 1, "s8": 1, "u8": 1,
    "int32": 4, "s32": 4, "u32": 4,
    "int64": 8, "s64": 8, "u64": 8,
    "float64": 8, "f64": 8,
    "bool": 1, "pred": 1,
    "int16": 2, "s16": 2, "u16": 2,
    "f8e4m3": 1, "f8e5m2": 1,
    "c64": 8, "c128": 16,
}


@dataclasses.dataclass(frozen=True)
class Chip:
    """One accelerator chip — the paper's 'core', scaled up."""

    peak_flops: float = PEAK_FLOPS_BF16
    hbm_bw: float = HBM_BW
    hbm_bytes: int = HBM_BYTES
    vmem_bytes: int = VMEM_BYTES
    ici_bw_per_link: float = ICI_BW_PER_LINK

    def usable_vmem(self) -> int:
        return int(self.vmem_bytes * VMEM_USABLE_FRACTION)


# device_kind -> constants, one row per chip this repo has been run on.
CHIPS = {
    "TPU v5 lite": Chip(),   # TPU v5e: the module constants above
}
TPU_V5E = CHIPS["TPU v5 lite"]


def chip_for(device_kind: str) -> Chip:
    """The constants of the chip JAX reports as ``device_kind``."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware constants for device_kind {device_kind!r}; known: "
            f"{sorted(CHIPS)} (add a row to core/hardware.py CHIPS)") from None
