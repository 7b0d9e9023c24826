"""Published peak rates of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB of
HBM at 819 GB/s). A device that is not in the table is an error, not a
default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates recorded for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None
