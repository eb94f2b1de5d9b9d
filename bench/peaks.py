"""Published peaks per accelerator, keyed by JAX's ``device_kind``.

Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e"
(system architecture page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip.

A device that is not in the table is an error, never a default: a share of
a peak computed against the wrong chip is a wrong number.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    hbm_bytes_per_s: float
    bf16_flops_per_s: float
    hbm_bytes: float
    ici_bits_per_s: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12,
                         hbm_bytes=16e9, ici_bits_per_s=1600e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for any other."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f"; known: {sorted(PEAKS)}") from None
