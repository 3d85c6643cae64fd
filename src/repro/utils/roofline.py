"""Published chip peaks, and the three-term roofline model of the dry-run:
compute / memory / collective times from the compiled artifact, the
dominant bottleneck, and the useful-FLOPs ratio."""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float          # bf16 FLOP/s per chip
    hbm_bw: float         # HBM B/s per chip
    ici_link_bw: float    # chip-to-chip B/s per link


#: Published per-chip peaks keyed by ``jax.Device.device_kind``. Source:
#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
#: 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (4 links of 50 GB/s).
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_link_bw=50e9),
}

#: the chip the dry-run roofline is drawn for
V5E = PEAKS["TPU v5 lite"]


def device_peaks(device_kind: Optional[str] = None) -> Optional[ChipPeaks]:
    """Peaks of ``device_kind`` (default: the first visible device's), or
    None for a device the table does not list — never a default."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    return PEAKS.get(device_kind)


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    model_flops_total: float          # 6·N·D (train) or 2·N·D (inference)

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / V5E.flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / V5E.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.wire_bytes_per_device / V5E.ici_link_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        hlo_total = self.flops_per_device * self.chips
        return self.model_flops_total / hlo_total if hlo_total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the chip's peak FLOP/s achieved at the bound, counting
        only useful (model) FLOPs: (model_flops/chips / t_bound) / peak."""
        if self.t_bound == 0:
            return 0.0
        return (self.model_flops_total / self.chips / self.t_bound) / V5E.flops

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "wire_bytes_per_device": self.wire_bytes_per_device,
            "model_flops_total": self.model_flops_total,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }

