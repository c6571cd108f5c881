"""Peaks of the chips the benchmark runs on, keyed by JAX's ``device_kind``.

Copied from ``paddle_tpu/device/peaks.py`` (the table only: no CPU
calibration, no environment override). A device that is not listed is an
error, never a default.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s, 16 GB.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks listed for device kind {device_kind!r}; add it to "
            f"benchmark/lib/peaks.py with its source") from None
