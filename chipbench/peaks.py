"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A device kind that is not in the table is an error, never a default: a
share of a peak is only meaningful against the chip it ran on.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float  # FLOP/s, dense bf16 matrix units
    hbm_bytes_s: float  # HBM bytes/s
    hbm_bytes: float  # HBM capacity, bytes
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI",
    ),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}")
    return PEAKS[device_kind]
