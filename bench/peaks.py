"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture, chip
    # specifications): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at
    # 819 GB/s per chip
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind that
    has no entry."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
