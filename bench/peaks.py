"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

TPU v5e ("TPU v5 lite"): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM
at 819 GB/s (Google Cloud documentation, "TPU v5e").  A kind missing
here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to PEAKS with their "
                         f"source") from None
