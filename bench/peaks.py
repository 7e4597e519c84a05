"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A copy of the program's table, so that the yardstick does not move with the
program.  A device that is not here has no peak: asking for it raises.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float  # FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s of chip-to-chip interconnect per chip
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12,
        hbm_bw=819e9,
        ici_bw=1600e9 / 8,  # 1,600 Gbit/s
        source='Google Cloud documentation, "TPU v5e" page',
    ),
}


def peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        ) from None
