"""Published per-chip peaks, keyed by `jax.Device.device_kind`.

The yardstick for every roofline share and MFU the benchmark reports.
Copied from the program's launch/roofline.py so that a change there
cannot move it. A kind not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""
    bf16_flops: float            # FLOP/s
    hbm_bw: float                # bytes/s
    hbm_bytes: float             # device memory
    source: str


# TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s.
CHIP_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bw=819e9,
                             hbm_bytes=16e9,
                             source="Google Cloud documentation, TPU v5e"),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of `device_kind`; an unknown kind raises."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}")
