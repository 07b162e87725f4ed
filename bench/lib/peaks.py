"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A device that is not in this table is an error, never a default.
"""

from __future__ import annotations

from typing import Dict

_V5E = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e"}

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/lib/peaks.py "
                       f"with their source") from None
