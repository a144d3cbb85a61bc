"""Published per-chip peak rates, keyed by ``jax.Device.device_kind``.

The one table every roofline and utilization figure divides by.  A
device kind missing here is an error, never a default.

Source for ``TPU v5 lite`` (TPU v5e): Google Cloud documentation,
"TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, and 1,600 Gbit/s of
chip-to-chip interconnect per chip, i.e. 50 GB/s per direction on each of
its four ICI links.  The 25 GB/s per-host data-centre network figure is
this repository's planning assumption; that page does not publish one.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float         # FLOP/s
    hbm_bytes_per_s: float
    ici_bytes_per_s: float    # per link, per direction
    dcn_bytes_per_s: float    # per host, across pods
    chips_per_host: int


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                             ici_bytes_per_s=50e9, dcn_bytes_per_s=25e9,
                             chips_per_host=4),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
