"""Published peaks of each accelerator the benchmark may run on, keyed by the
``device_kind`` JAX reports.

A device that is not in the table is an error, never a default: a roofline
share or an MFU against the wrong chip's peak would be a wrong number that
looks right.
"""
from __future__ import annotations

#: device_kind -> peaks.  Source: Google Cloud documentation, "TPU v5e"
#: (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip).
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


def least_time_s(flops: float, bytes_moved: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of compute and memory."""
    return max(flops / peaks["bf16_flops"], bytes_moved / peaks["hbm_bytes_per_s"])
