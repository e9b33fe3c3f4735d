"""Roofline-term extraction from AOT-compiled artifacts.

Per (arch x shape x mesh) cell we derive, WITHOUT hardware:

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = collective_bytes_per_device / ICI_link_bw

`cost_analysis()` supplies FLOPs/bytes of the *partitioned per-device*
module. Collective bytes are not in cost_analysis: we parse the optimized
HLO, sum result-shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute, and multiply ops inside
`while` bodies (scan-over-layers) by their trip counts.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple


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
# the chip the dry-run's production meshes are made of
V5E = CHIP_PEAKS["TPU v5 lite"]


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of `device_kind`; a kind not in the table is
    an error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string, incl. tuple types."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, int]
    count_by_kind: Dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str,
                      while_trip_count: int = 1) -> CollectiveStats:
    """Sum collective result bytes; ops inside while bodies scale by
    `while_trip_count` (the scan-over-layers length)."""
    # map computation name -> its text block
    comp_starts: List[Tuple[str, int]] = []
    for m in re.finditer(
            r"^(?:ENTRY )?%?([\w\.\-]+)[^\n]*\{", hlo_text, re.M):
        comp_starts.append((m.group(1), m.start()))
    comp_starts.sort(key=lambda x: x[1])

    # which computations are while bodies/conditions?
    loop_comps = set()
    for m in re.finditer(r"(?:body|condition)=%?([\w\.\-]+)", hlo_text):
        loop_comps.add(m.group(1))

    def comp_of(pos: int) -> str:
        name = ""
        for n, s in comp_starts:
            if s <= pos:
                name = n
            else:
                break
        return name

    bytes_by: Dict[str, int] = {k: 0 for k in COLLECTIVE_KINDS}
    count_by: Dict[str, int] = {k: 0 for k in COLLECTIVE_KINDS}
    for m in re.finditer(
            r"^\s*(?:ROOT )?%?[\w\.\-]+\s*=\s*([^=\n]*?)\s*"
            r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
            r"reduce-scatter|all-to-all|collective-permute-start|"
            r"collective-permute)\(", hlo_text, re.M):
        type_str, kind_raw = m.group(1), m.group(2)
        kind = kind_raw.replace("-start", "")
        if kind not in bytes_by:
            continue
        b = _shape_bytes(type_str)
        comp = comp_of(m.start())
        mult = while_trip_count if comp in loop_comps else 1
        bytes_by[kind] += b * mult
        count_by[kind] += mult
    return CollectiveStats(bytes_by, count_by)


@dataclasses.dataclass
class Roofline:
    flops: float                 # per device
    bytes_accessed: float        # per device (HBM traffic proxy)
    collective_bytes: float      # per device
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float           # 6ND / 2ND useful work (whole step, global)
    useful_ratio: float          # model_flops / (flops * chips)
    peak_fraction: float         # compute_s / max(all terms)
    collective_by_kind: Optional[Dict[str, float]] = None

    def as_dict(self):
        return dataclasses.asdict(self)


def analyze(compiled, *, n_chips: int, scan_trip_count: int,
            model_flops_global: float,
            hlo_text: Optional[str] = None) -> Roofline:
    """Derive the three roofline terms from the compiled per-device module.

    FLOPs / HBM bytes / collective bytes come from the call-graph-weighted
    HLO analysis (repro.launch.hlo_analysis), which — unlike XLA's
    cost_analysis() — multiplies `while` (scan) bodies by their trip
    counts. `scan_trip_count` is kept as a cross-check fallback only.
    """
    from repro.launch import hlo_analysis as HA
    text = hlo_text if hlo_text is not None else compiled.as_text()
    hc = HA.analyze_hlo_text(text)
    flops = hc.flops
    nbytes = hc.hbm_bytes

    compute_s = flops / V5E.bf16_flops
    memory_s = nbytes / V5E.hbm_bw
    collective_s = hc.total_collective_bytes / V5E.ici_bw_per_link
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    total_device_flops = flops * n_chips
    useful = (model_flops_global / total_device_flops
              if total_device_flops else 0.0)
    bound = max(terms.values())
    return Roofline(
        flops=flops, bytes_accessed=nbytes,
        collective_bytes=float(hc.total_collective_bytes),
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, model_flops=model_flops_global,
        useful_ratio=useful,
        peak_fraction=(compute_s / bound) if bound > 0 else 0.0,
        collective_by_kind={k: v for k, v in hc.collective_bytes.items()
                            if v})


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


# ---------------------------------------------------------------------------
# Model-FLOPs (the "useful work" yardstick).
# ---------------------------------------------------------------------------
def active_param_count(cfg) -> float:
    """Params touched per token: MoE expert weights scale by top_k/E."""
    from repro.models import lm as _lm
    import numpy as np
    import jax
    total = 0.0
    flat = jax.tree_util.tree_flatten_with_path(
        _lm.abstract_params(cfg))[0]
    for path, leaf in flat:
        keys = "/".join(str(getattr(p, "key", "")) for p in path)
        n = float(np.prod(leaf.shape))
        if cfg.moe is not None and any(
                k in keys for k in ("wi_gate", "wi_up", "wi", "wo")) \
                and "mlp" in keys:
            n *= cfg.moe.top_k / cfg.moe.num_experts
        total += n
    return total


def model_flops(cfg, shape) -> float:
    """6·N·D train / 2·N·D forward; D = tokens processed by the step."""
    n = active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch
