"""Published peaks of each accelerator the benchmark runs on, by `device_kind`.

A device kind that is not in the table is an error, never a default: a share
of a peak that silently used another chip's peak would be a wrong number.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s, dense bf16 matrix multiplication
    hbm_bytes: float       # bytes/s of HBM bandwidth
    hbm_capacity: float    # bytes of HBM
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes=819e9, hbm_capacity=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; add its "
            f"row to bench/peaks.py (known: {sorted(PEAKS)})") from None
