"""Published peaks of one chip, keyed by JAX's ``device_kind``.

TPU v5e: 197 TFLOP/s bf16 and 16 GB of HBM at 819 GB/s (Google Cloud
documentation, "TPU v5e"). A device that is not here is an error.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
