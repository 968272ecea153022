"""Published peaks of the chips this repo targets, keyed by
``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per-chip specifications).
A device kind that is not in the table is an error, not a default: a
roofline against the wrong chip's peaks is a wrong number.  Code that runs
on a device looks its kind up here (``chip_smoke.py`` does); code without a
device (the planner's cost-model seeds, the dry-run rooflines) uses
``TARGET``.

This module imports nothing from ``repro``, so every layer may read it.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float      # FLOP/s
    hbm_bw: float          # HBM bytes/s
    ici_bw: float          # chip-to-chip interconnect bytes/s per chip
    source: str


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12,
        hbm_bw=819e9,
        ici_bw=1600e9 / 8,             # 1,600 Gbit/s
        source='Google Cloud documentation, "TPU v5e"'),
}

# VPU transcendental retire rate, elements/s.  Not published; an estimate
# that only seeds the cost model until measurements calibrate it.
TRANSCENDENTAL_RATE = 1.0e12


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; raises KeyError for a kind
    the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


# the chip assumed where no device is attached
TARGET_KIND = "TPU v5 lite"
TARGET = peaks(TARGET_KIND)
