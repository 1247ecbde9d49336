"""The one table of accelerator peaks, keyed by JAX's ``device_kind``.

``chip_smoke.py`` and ``tools/measure_overlap.py`` read it (the benchmark
keeps its own copy, ``perfbench/peaks.py``).  A
device that is not in the table is an error, never a default: a utilization
computed against a guessed peak is worse than none.
"""

from __future__ import annotations

from typing import Dict, NamedTuple


class Peak(NamedTuple):
    bf16_flops: float       # dense bf16 FLOP/s per chip
    hbm_bytes_per_s: float  # HBM bandwidth per chip
    ici_bytes_per_s: float  # chip-to-chip interconnect, all links, per chip
    ici_links: int          # links that bandwidth is spread over
    source: str


PEAKS: Dict[str, Peak] = {
    # device_kind as jax.devices()[0].device_kind reports it for a v5e chip
    # (libtpu 0.0.34; the compile-only "v5e:2x2" topology says the same).
    "TPU v5 lite": Peak(
        bf16_flops=197e12,
        hbm_bytes_per_s=819e9,
        ici_bytes_per_s=1600e9 / 8,
        ici_links=4,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s interconnect per chip "
               "(a 2D torus: four links)"),
}


def peak(device_kind: str) -> Peak:
    """The peaks of ``device_kind``; raises for a device not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak numbers for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add the device to device_peaks.py with its "
            "source before reporting a utilization on it.") from None
