"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A device kind that is not in :data:`PEAKS` is an error, never a default:
the harness refuses to run on it.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float       # bf16 FLOP/s
    hbm_bw: float      # HBM bytes/s
    hbm_bytes: float   # HBM capacity
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s"),
}


def peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
