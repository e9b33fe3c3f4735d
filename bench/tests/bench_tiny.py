"""Tiny cells for the benchmark's CPU tests: the published cells' files
with every width cut to a size a test run can hold, on CPU host devices,
with the Pallas kernels in interpret mode."""
from __future__ import annotations

import copy
import os
import sys
from pathlib import Path

# one CPU host device per FL client; read once, when jax's backend starts
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import cell as cells  # noqa: E402

# limits for the tiny sizes, set from sound tiny runs (loss ~0.01,
# norms ~0.005) with room: the tiny model's bf16 noise is far wider
# than the full-size cells'
TINY_LIMITS = {"loss_gap": {"limit": 0.05},
               "grad_norm_gap": {"limit": 0.05},
               "change_norm_gap": {"limit": 0.15}}


def tiny_cell(config: str, traffic: str) -> cells.Cell:
    """A cell of `config` under `traffic` (their files under bench/) at
    a tiny size."""
    cfg = cells.load_json(BENCH / "configs" / f"{config}.json")
    m = cfg["model"]
    m.update(num_layers=2, d_model=32, vocab_size=64, remat=False)
    if m["family"] == "ssm":
        m["ssm"].update(d_state=8, head_dim=8, chunk_size=8)
    else:
        m.update(num_heads=2, num_kv_heads=2, d_ff=64)
    tr = cells.load_json(BENCH / "traffic" / f"{traffic}.json")
    tr["seq"] = 16
    bench = cells.load_benchmark()
    return cells.Cell(name=f"tiny.{config}.{traffic}", chips=tr["clients"],
                      config=cfg, traffic=tr,
                      limits=copy.deepcopy(TINY_LIMITS),
                      end_to_end=bench["end_to_end"], per_layer=[])


def run_tiny(cell, seed: int = 2 ** 31 + 5, seconds: float = 1.0):
    """One run of the harness on CPU, past its look for a chip."""
    import run as bench_run
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        return bench_run.run_cell(cell, seed, seconds, trace=False,
                                  require_tpu=False)
