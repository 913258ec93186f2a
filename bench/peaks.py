"""Published peaks of each accelerator the benchmark accepts, keyed by the
``device_kind`` JAX reports. A kind that is missing is an error, never a
default: a share of a peak means nothing against a guessed peak."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float        # dense bf16 FLOP/s of one chip
    hbm_bytes_per_s: float   # HBM bandwidth of one chip
    hbm_bytes: float         # HBM capacity of one chip
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e' (197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip)"),
}


class UnknownDevice(KeyError):
    """The device kind has no entry in :data:`PEAKS`."""


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in the peaks table "
            f"({sorted(PEAKS)})") from None
