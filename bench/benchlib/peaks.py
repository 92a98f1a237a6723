"""Published peaks per chip, keyed by JAX's ``device_kind``.

A device that is not in the table is an error: a share of a peak is only
meaningful against the peak of the chip that ran."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float          # dense bf16 matrix units
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


TABLE = {
    "TPU v5 lite": Peaks(
        flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s "
               "bf16, 819 GB/s HBM, 16 GB HBM per chip"),
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; add its published "
            f"peaks to bench/benchlib/peaks.py") from None


def least_seconds(flops: float, nbytes: float, p: Peaks) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / p.flops_per_s, nbytes / p.hbm_bytes_per_s)
