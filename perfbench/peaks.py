"""The benchmark's own table of accelerator peaks, keyed by JAX's
``device_kind``.  A copy of the repo's ``device_peaks.py`` kept under the
benchmark's paths so that no later PR can move the yardstick.  A device
that is not in the table is an error, never a default."""

from __future__ import annotations

from typing import Dict, NamedTuple


class Peak(NamedTuple):
    bf16_flops: float       # dense bf16 FLOP/s per chip
    hbm_bytes_per_s: float  # HBM bandwidth per chip
    hbm_bytes: float        # HBM capacity per chip
    source: str


PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": Peak(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM at 819 GB/s"),
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak numbers for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
