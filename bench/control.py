"""Readings that the limits of bench/limits/<cell>.json are set from.

    python3 bench/control.py --workload <cell> --program-seeds 1,2,... \
        --control-seeds 1,2,3 [--out FILE]

In one process, on the chip, at the cell's own size:

1. the program: for each program seed, the seed's weights and rows and
   the first three FL rounds through `FLCloudRunner.run()` -> SyncEngine
   -> `MeshTrainerHooks.aggregate`, the set-up of bench/run.py (one set
   of hooks serves every seed);
2. with the program freed, the fp32 reference of the same rounds, and
   the compared numbers of harness/check.py for each seed (the lower
   readings);
3. for each control seed, the control (the reference computed with
   scaled fp8 matmuls, a precision step below the configuration's
   bfloat16) and each planted fault the cell can have, in the program's
   place, against the fp32 reference (the upper readings).

A state left unchanged reads 1 on change_norm_gap by its definition and
needs no run. The benchmark's own runs never run this. It prints one
JSON object with every reading, and writes it to --out.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as bench_run  # noqa: E402
from harness import cell as cells  # noqa: E402


def faults_of(cell):
    """The planted faults a cell can have."""
    return (["half_batch", "token_altered"]
            + (["half_clients", "no_exchange"]
               if cell.traffic["clients"] > 1 else []))


def as_program(out):
    """A reference run put in the program's place: client-stacked
    norms, as the program's own readings are."""
    import numpy as np
    stack = lambda per: {k: np.stack([d[k] for d in per]) for k in per[0]}
    return {"losses": out["losses"], "mom": stack(out["mom"]),
            "change": stack(out["change"])}


def plain(reads):
    return {k: {"value": v, "worst": w} for k, (v, w) in reads.items()}


def collect(cell, program_seeds, control_seeds):
    import jax
    from harness.check import readings
    from harness.clock import Spans
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("error: JAX's first device is not a TPU")
    out = {"cell": cell.name, "program": {}, "control": {}, "faults": {}}
    bench = bench_run.Bench(cell, program_seeds[0], Spans())
    runs = {}
    for seed in program_seeds:
        bench.reset(seed)
        runs[seed] = (bench.first_rounds(), bench.batches())
    bench.free()
    for seed in program_seeds:
        prog, batches = runs[seed]
        t = time.perf_counter()
        ref = bench_run.reference_readings(cell, seed, batches)
        out["program"][seed] = plain(readings(prog, ref))
        out["program"][seed]["reference_s"] = time.perf_counter() - t
        bench_run.log(f"seed {seed}: program {out['program'][seed]}")
        if seed not in control_seeds:
            continue
        ctl = bench_run.reference_readings(cell, seed, batches,
                                           precision="fp8")
        out["control"][seed] = plain(readings(as_program(ctl), ref))
        bench_run.log(f"seed {seed}: control {out['control'][seed]}")
        for fault in faults_of(cell):
            f = bench_run.reference_readings(cell, seed, batches,
                                             fault=fault)
            out["faults"].setdefault(fault, {})[seed] = plain(
                readings(as_program(f), ref))
            bench_run.log(f"seed {seed}: {fault} "
                          f"{out['faults'][fault][seed]}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    bench_run.configure_compile_cache()
    seeds = [int(s) for s in args.program_seeds.split(",")]
    ctl = [int(s) for s in args.control_seeds.split(",") if s]
    out = collect(cell, seeds, set(ctl))
    text = json.dumps(out, indent=1)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
