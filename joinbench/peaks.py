"""Published peaks of each chip, keyed by ``device_kind``.

A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture page):
# 16 GB of HBM2 at 819 GB/s, 197 TFLOP/s bf16, 393 TOP/s int8 per chip.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to joinbench/peaks.py "
                       "with their source") from None
