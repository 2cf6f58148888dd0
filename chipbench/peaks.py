"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A device that is not listed is an error:
no roofline or utilisation is ever computed against a guessed peak."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,      # bf16 matrix units, per chip
        "bytes_per_s": 819e9,       # HBM bandwidth, per chip
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to chipbench/peaks.py "
                       f"with their source") from None
