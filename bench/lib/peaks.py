"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float     # FLOP/s
    hbm_bytes: float      # bytes/s, for a memory-bound kernel's roofline


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes=819e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to {__name__}") from None
