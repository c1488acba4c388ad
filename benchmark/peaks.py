"""Published peaks of the chips the benchmark may run on, keyed by
JAX's ``device_kind``.  A device that is not here is an error, never
a default.

Source: Google Cloud documentation, "TPU v5e" system architecture:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM2e at 819 GB/s per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_TFLOPs": 197.0,
        "int8_TOPs": 393.0,
        "hbm_GBps": 819.0,
        "hbm_GB": 16.0,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: "
            "add a row with its source to benchmark/peaks.py"
        ) from None
