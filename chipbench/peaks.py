"""Chip peaks keyed by ``device_kind``, with their source. A device
kind not in the table is an error, never a default."""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": per chip, 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add "
                       "them to chipbench/peaks.py with their source") from None
