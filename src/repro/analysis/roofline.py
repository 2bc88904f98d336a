"""Roofline-term derivation from a compiled (dry-run) artifact.

Per-chip peaks live in one table, ``PEAKS``, keyed by the device kind
JAX reports (``jax.Device.device_kind``); a kind the table lacks is an
error (``peaks_for``), never a default.

Terms (seconds, per training/serving step, per chip):
  compute    = HLO_FLOPs / (chips * peak)
  memory     = HLO_bytes / (chips * hbm_bw)
  collective = collective_traffic_bytes_per_chip / ici_bw

cost_analysis() reports PER-DEVICE flops/bytes for SPMD programs (the
partitioned module is what gets analyzed — verified against analytic
6·N·D counts in the dry-run). Collective traffic is parsed from the same
per-device module, so all three terms are per-chip quantities.
"""
from __future__ import annotations

import dataclasses
import json

from .hlo import collective_summary


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops: float     # peak bf16 FLOP/s per chip
    hbm_bw: float    # HBM bytes/s per chip
    ici_bw: float    # ICI bytes/s per link


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s of ICI over 4 links (50 GB/s each).
V5E = "TPU v5 lite"
PEAKS = {V5E: DevicePeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9)}


def peaks_for(device_kind: str) -> DevicePeaks:
    """The published peaks of ``device_kind``; raises for a kind the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


@dataclasses.dataclass
class Roofline:
    arch: str
    cell: str
    mesh: str
    chips: int
    hlo_flops: float                  # per chip
    hlo_bytes: float                  # per chip
    collective_bytes: float           # per chip
    model_flops: float
    per_device_memory: float          # bytes (peak, from memory_analysis)
    collectives: dict
    device_kind: str                  # the chip the terms are priced on

    @property
    def peaks(self) -> DevicePeaks:
        return peaks_for(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / self.peaks.ici_bw

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
        """MODEL_FLOPS / (chips * HLO_FLOPs) — remat/redundancy waste."""
        return self.model_flops / max(self.chips * self.hlo_flops, 1.0)

    @property
    def mfu_bound(self) -> float:
        """Model-flops utilization at the roofline bound (the score)."""
        t_model = self.model_flops / (self.chips * self.peaks.flops)
        return t_model / max(self.t_bound, 1e-30)

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "cell": self.cell, "mesh": self.mesh,
            "device_kind": self.device_kind,
            "chips": self.chips, "hlo_flops": self.hlo_flops,
            "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "model_flops": self.model_flops,
            "per_device_memory": self.per_device_memory,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu_bound": self.mfu_bound,
            "collectives": self.collectives,
        }


def analyze_compiled(arch, cell, mesh_name, chips, compiled,
                     model_flops, *, device_kind: str) -> Roofline:
    """Roofline terms of ``compiled`` priced on ``device_kind``'s peaks
    (the chip the program targets, which a dry-run compiled on other
    devices must name)."""
    peaks_for(device_kind)
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    try:
        ma = compiled.memory_analysis()
        mem = float(getattr(ma, "peak_memory_in_bytes", 0) or
                    getattr(ma, "temp_size_in_bytes", 0)
                    + getattr(ma, "argument_size_in_bytes", 0)
                    + getattr(ma, "output_size_in_bytes", 0))
    except Exception:
        mem = 0.0
    text = compiled.as_text()
    summ = collective_summary(text)
    return Roofline(arch, cell, mesh_name, chips, flops, byts,
                    float(summ["total_traffic_bytes"]), model_flops, mem,
                    summ, device_kind)


def save_json(records, path):
    with open(path, "w") as f:
        json.dump([r if isinstance(r, dict) else r.to_dict()
                   for r in records], f, indent=1)


def fmt_seconds(t: float) -> str:
    if t >= 1.0:
        return f"{t:.2f}s"
    if t >= 1e-3:
        return f"{t * 1e3:.2f}ms"
    return f"{t * 1e6:.1f}us"
