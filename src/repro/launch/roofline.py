"""Published chip peaks and the roofline step-time estimate.

`CHIP_PEAKS` holds each accelerator kind's published peaks, and
`estimate_step_time` turns a step's FLOP and byte counts into a
roofline time against peaks. The training calibrator
(`repro.fl.training.calibrate`) cross-checks its measured round with
them, and `repro.cloud.report` derives epoch times from them.
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
    ici_bw_per_link: float       # bytes/s per link, one direction


# Keyed by `jax.Device.device_kind`. TPU v5e: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect (200 GB/s over 4 links).
CHIP_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bw=819e9,
                             hbm_bytes=16e9, ici_bw_per_link=50e9),
}
# TPU v5e, the chip the benchmark runs on
V5E = CHIP_PEAKS["TPU v5 lite"]


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of `device_kind`; a kind not in the table is
    an error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}")


def estimate_step_time(flops: float, bytes_accessed: float, *,
                       peak_flops: float, hbm_bw: float,
                       combine: str = "max") -> float:
    """Roofline wall-clock estimate for one step from its FLOP and byte
    counts, against the given hardware peaks.

    The training calibrator (`repro.fl.training.calibrate`) passes a
    chip's published peaks (`chip_peaks`) or peaks measured on the host.
    `combine="max"` is the classic roofline bound (terms overlap);
    `"sum"` models a serial host where compute and memory traffic share
    one pipe — the right shape for the CPU host-device trick.
    """
    compute_s = flops / peak_flops
    memory_s = bytes_accessed / hbm_bw
    if combine == "sum":
        return compute_s + memory_s
    return max(compute_s, memory_s)
