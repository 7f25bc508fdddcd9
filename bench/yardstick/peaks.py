"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
JAX names this chip "TPU v5 lite".  A kind that is not in the table
is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

_V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU v5e documentation: 197 TFLOP/s bf16, "
                  "819 GB/s HBM, 16 GB"}

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (have {sorted(PEAKS)})") from None
