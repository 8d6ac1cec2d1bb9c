"""Published single-chip peaks, keyed by JAX's ``device_kind``.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
197 TFLOP/s in bfloat16, 16 GB of HBM at 819 GB/s. A device that is not in
the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "hbm_bytes": 16e9},
}


def of(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}") from None
