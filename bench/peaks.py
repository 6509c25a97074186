"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind with no row is an error, never a
default: a share of a peak is only meaningful against that chip's peak.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s in
bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device_kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
