"""Published peaks per device kind, as ``jax.Device.device_kind`` names it.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
819 GB/s of HBM bandwidth, 197 TFLOP/s bf16 and 393 TOP/s int8 per chip,
16 GB of HBM.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
