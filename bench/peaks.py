"""Published chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (4 links of
50 GB/s). A device the table does not list has no peaks: a share of a peak
is then not reported, never read against another chip's numbers.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float          # bf16 FLOP/s per chip
    hbm_bw: float         # HBM B/s per chip
    ici_link_bw: float    # chip-to-chip B/s per link


PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_link_bw=50e9),
}


def for_kind(device_kind: str) -> Optional[ChipPeaks]:
    return PEAKS.get(device_kind)
