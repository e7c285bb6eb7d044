"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A chip that is not here is an error.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect per chip.
"""
from __future__ import annotations

_V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9, "source": "Google Cloud documentation, TPU v5e"}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
