"""Peak rates of one chip, keyed by ``device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s in bfloat16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s.
JAX reports a v5e chip's kind as "TPU v5 lite".  A kind that is not here
is an error, not a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}
SOURCE = 'Google Cloud documentation, "TPU v5e"'


def peak(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
