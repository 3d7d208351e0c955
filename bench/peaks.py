"""Published peak rates of the devices the benchmark runs on.

Keyed by ``jax.Device.device_kind``.  A kind that is not in the table is an
error, never a default: a roofline share against a guessed peak is no
measurement.

Source of the "TPU v5 lite" row (TPU v5e): Google Cloud documentation,
"TPU v5e" -- 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table row of ``device_kind``; ``KeyError`` when unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add a row to bench/peaks.py with its source") from None
