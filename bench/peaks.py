"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` jax reports. A kind that is not here is an error, never a
default."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM2 at 819 GB/s per chip"),
}


class UnknownChip(KeyError):
    """A ``device_kind`` with no published peaks in :data:`PEAKS`."""


def peak_for(kind: str) -> Peak:
    try:
        return PEAKS[kind]
    except KeyError:
        raise UnknownChip(f"no peaks for device_kind {kind!r}; known: "
                          f"{sorted(PEAKS)}") from None
