"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` jax reports.  A kind that is not here is an error: a
roofline or utilization against a guessed peak would mean nothing.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,      # FLOP/s, bf16 matrix units
        "hbm_bytes_s": 819e9,      # bytes/s
        "hbm_bytes": 16e9,         # bytes of HBM
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    """The peaks of one chip of this kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f"; known: {sorted(PEAKS)}") from None
