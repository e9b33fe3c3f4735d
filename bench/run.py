"""Chip benchmark of the real-training FL round.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of BENCHMARK.json once, on the machine it is started on:

1. Without a TPU as JAX's first device, or with fewer chips than the
   cell asks for, it exits non-zero and prints no result.
2. Set-up (timed as `setup_s`, from process start): the persistent
   compile cache at <checkout>/.jax_cache (or JAX_COMPILATION_CACHE_DIR
   where set); `MeshTrainerHooks` for the cell's configuration and
   traffic; weights made on the device from the seed in one jitted call
   and token streams seeded from it; then the first three FL rounds,
   driven as the window drives them (`FLCloudRunner.run()` -> SyncEngine
   -> `aggregate`), which compile and warm every program the window
   runs. The reference later follows these rounds.
3. The window: `FLCloudRunner.run()` -> SyncEngine ->
   `MeshTrainerHooks.aggregate` (local program, then the FedAvg
   program), closed by the first round that ends after `--seconds`
   and a `block_until_ready` of the global state. `round_s` is the
   window's wall time over the rounds completed in it; `round_p90_s`
   the 90th percentile of the rounds' wall times (aggregate end to
   aggregate end). With `--trace 1` the window lasts at most
   TRACE_SECONDS under the profiler and the per-layer metrics are read
   from the trace, the benchmark's host spans and the round programs'
   compiled HLO text (each op's op_name, for device time by scope).
4. `correct`: with the program's state freed, the plain fp32 reference
   (bench/reference) runs the same three rounds from the same weights
   and rows, and the numbers of harness/check.py are held to the cell's
   limits (bench/limits/<cell>.json).

The last line of standard output is one JSON object: correct,
attempted, failed, metrics, device, [breakdown], checks.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
import typing  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from harness import cell as cells  # noqa: E402

SETUP_ROUNDS = 3          # rounds driven in set-up; the reference follows
TRACE_SECONDS = 10.0      # longest traced window
SIM_SEED = 0              # the simulated cloud is the same for every seed


class WindowClosed(Exception):
    """Raised from the benchmark's wrapper of `aggregate` to end the
    run once a round ends after the window's deadline, or once set-up
    has its rounds."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def configure_compile_cache() -> str:
    """Every program in the persistent cache: at the fixed
    <checkout>/.jax_cache unless JAX_COMPILATION_CACHE_DIR says where.
    The TPU runtime writes no log files (by default it would, under a
    fixed path in /tmp). Programs are keyed by their metadata too:
    otherwise an executable loaded from the cache keeps the `op_name`s
    of the program first compiled under its key, which the scopes are
    read from. Traced and untraced runs share the key, so either finds
    what the other compiled. Call before JAX starts its backend."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _from_json(hint, value):
    """`value`, read from JSON, as the type `hint` declares it: a
    dataclass built from its object, field by field; a tuple from a
    list, item by item."""
    if value is None:
        return None
    if dataclasses.is_dataclass(hint) and isinstance(value, dict):
        hints = typing.get_type_hints(hint)
        return hint(**{k: _from_json(hints.get(k), v)
                       for k, v in value.items()})
    origin, args = typing.get_origin(hint), typing.get_args(hint)
    if origin is typing.Union:
        for arg in args:
            if arg is not type(None):
                out = _from_json(arg, value)
                if out is not value:
                    return out
        return value
    if origin is tuple and isinstance(value, list):
        if len(args) == 2 and args[1] is Ellipsis:
            args = (args[0],) * len(value)
        return tuple(_from_json(a, v)
                     for a, v in zip(args, value, strict=True))
    return value


def model_config(model):
    """The program's ModelConfig of a configuration file's "model":
    every nested config that ModelConfig declares is built from its
    object, and every list that a tuple field holds is a tuple."""
    from repro.common.config import ModelConfig
    return _from_json(ModelConfig, model)


# ---------------------------------------------------------------------------
# Set-up: the program, the seed's weights and streams, the first rounds.
# ---------------------------------------------------------------------------
class Bench:
    """The program under test for one cell and seed, with the
    benchmark's spans around each call into it."""

    def __init__(self, cell, seed: int, spans):
        import jax
        import jax.numpy as jnp
        from reference.common import abstract, make_params
        from repro.fl.training import MeshTrainerHooks

        self.cell, self.spans = cell, spans
        tr = cell.traffic
        self.clients = [f"client_{i}" for i in range(tr["clients"])]
        cfg = model_config(cell.model)
        self.hooks = MeshTrainerHooks(
            self.clients, cfg=cfg, local_steps=tr["local_steps"],
            batch=tr["batch"], seq=tr["seq"], lr=tr["lr"],
            quantize=tr["quantize"], use_pallas=cfg.use_pallas, seed=0)
        self.specs = cells.reference_module(cell).param_specs(cell.model)
        want = abstract(self.specs, copies=len(self.clients))
        got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                           self.hooks.params_stk)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("the reference's parameter tree differs from "
                             "the program's")
        self.gen = jax.jit(functools.partial(make_params, self.specs,
                                             copies=len(self.clients)),
                           out_shardings=self.hooks.stacked)
        self.zeros = jax.jit(
            lambda t: jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), t),
            out_shardings=self.hooks.stacked)
        self.captured = None
        self.mom = None
        self.deadline = None
        self.stop_after = None
        self.ends = []
        self.attempted = 0
        self._wrap()
        self.reset(seed)

    def reset(self, seed: int) -> None:
        """The weights and streams of `seed`, momentum zero."""
        from harness.data import client_streams
        from reference.common import seed_halves
        h = self.hooks
        self.halves = seed_halves(seed)
        h.params_stk = None
        h.params_stk = self.gen(*self.halves)
        h.mu_stk = self.zeros(h.mu_stk)
        tr = self.cell.traffic
        self.streams = client_streams(
            seed, tr, self.cell.model["vocab_size"],
            keep=SETUP_ROUNDS * tr["local_steps"])
        # the rows come from the benchmark, not from the program
        h._streams = self.streams
        h.losses = []

    def _wrap(self) -> None:
        from harness.check import leaf_norms_jit, to_host
        h, sp = self.hooks, self.spans
        agg, nb, lr_, fa = h.aggregate, h.next_batches, h.local_round, \
            h.fedavg

        def next_batches():
            with sp.span("next_batches"):
                return nb()

        def local_round(batches):
            with sp.span("local_dispatch"):
                out = lr_(batches)
            if self.captured is not None:
                self.captured.append(out[2])
            return out

        def fedavg(*a):
            with sp.span("fedavg_dispatch"):
                return fa(*a)

        def aggregate(participants, round_idx, staleness=None):
            self.attempted += 1
            with sp.span("aggregate"):
                agg(participants, round_idx, staleness)
            now = time.perf_counter()
            self.ends.append(now)
            if self.captured is not None and len(self.ends) == 1:
                self.mom = to_host(leaf_norms_jit(h.mu_stk, True))
            if (self.deadline is not None and now >= self.deadline) \
                    or len(self.ends) == self.stop_after:
                raise WindowClosed

        h.next_batches, h.local_round, h.fedavg, h.aggregate = \
            next_batches, local_round, fedavg, aggregate

    def first_rounds(self) -> dict:
        """The first SETUP_ROUNDS rounds, driven as the window drives
        them (`FLCloudRunner.run()` -> SyncEngine -> `aggregate`, with
        the participants, round indices and staleness the engine
        passes), and the numbers the reference is compared on."""
        import jax
        import numpy as np
        from harness.check import change_norms
        h = self.hooks
        self.captured, self.mom = [], None
        self.ends, self.stop_after = [], SETUP_ROUNDS
        try:
            self.runner().run()
        except WindowClosed:
            pass
        self.stop_after = None
        if len(self.captured) != SETUP_ROUNDS:
            raise RuntimeError(f"set-up ran {len(self.captured)} rounds, "
                               f"not {SETUP_ROUNDS}")
        change = change_norms(h.params_stk, self.gen(*self.halves), True)
        losses = np.stack([np.asarray(l) for l in self.captured])
        self.captured = None
        self.ends, self.attempted = [], 0
        jax.block_until_ready((h.params_stk, h.mu_stk))
        return {"losses": losses.tolist(), "mom": self.mom,
                "change": change}

    def batches(self):
        """The rows of the first rounds: [round][client][step]."""
        k = self.cell.traffic["local_steps"]
        return [[s.kept[r * k:(r + 1) * k] for s in self.streams]
                for r in range(SETUP_ROUNDS)]

    def runner(self):
        from repro.common.config import (ClientProfile, CloudConfig,
                                         FLRunConfig)
        from repro.fl.runner import FLCloudRunner
        tr = self.cell.traffic
        profiles = tuple(ClientProfile(c, mean_epoch_s=tr["sim_epoch_s"],
                                       jitter=0.0) for c in self.clients)
        run_cfg = FLRunConfig(
            dataset=self.cell.name, clients=profiles, n_epochs=10 ** 9,
            policy=tr["policy"], quantize_updates=tr["quantize"],
            seed=SIM_SEED)
        return FLCloudRunner(run_cfg, cloud_cfg=CloudConfig(
            spot_rate_sigma=0.0), hooks=self.hooks)

    def free(self) -> None:
        """Drop every array the program holds."""
        import jax
        h = self.hooks
        for a in jax.tree.leaves((h.params_stk, h.mu_stk)):
            a.delete()
        h.params_stk = h.mu_stk = None
        h._local_fn = h._avg_fn = None
        self.hooks = None
        gc.collect()
        held = sum(a.nbytes for a in jax.live_arrays())
        log(f"after freeing the program: {held} bytes of arrays live")


# ---------------------------------------------------------------------------
# The window.
# ---------------------------------------------------------------------------
def run_window(bench: Bench, seconds: float, trace_dir=None) -> dict:
    import jax
    import numpy as np
    from harness.clock import CompileClock
    runner = bench.runner()
    h = bench.hooks
    n_losses = len(h.losses)
    failed = 0
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    with CompileClock() as clock, bench.spans.span("window"):
        t0 = time.perf_counter()
        bench.ends, bench.attempted = [], 0
        bench.deadline = t0 + seconds
        try:
            runner.run()
        except WindowClosed:
            pass
        except Exception:
            failed += 1
            traceback.print_exc()
        jax.block_until_ready((h.params_stk, h.mu_stk))
        t_close = time.perf_counter()
    bench.deadline = None
    if trace_dir:
        jax.profiler.stop_trace()
    ends = list(bench.ends)
    losses = [r["mean_loss"] for r in h.losses[n_losses:]]
    failed += sum(1 for x in losses if not np.isfinite(x))
    marks = [t0] + ends[:-1] + [t_close]
    return {"t0": t0, "t_close": t_close, "rounds": len(ends),
            "attempted": bench.attempted, "failed": failed,
            "round_times": list(np.diff(marks)) if ends else [],
            "compiles": clock.compiles, "compile_s": clock.seconds}


def program_op_names(hooks, batch: int, seq: int, rows_dtype="int32"
                     ) -> dict:
    """{HLO module name: {instruction name: op_name}} of the hooks' two
    round programs, from their compiled text, lowered from shapes with
    the arguments the window passes (rows of `rows_dtype`). Lowering
    from shapes reads no rows from the streams."""
    import jax
    from harness import trace as T
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,  # noqa: E731
                                          sharding=x.sharding)
    p = jax.tree.map(like, hooks.params_stk)
    mu = jax.tree.map(like, hooks.mu_stk)
    n = len(hooks.clients)
    rows = jax.ShapeDtypeStruct((n, hooks.local_steps, batch, seq),
                                rows_dtype, sharding=hooks.stacked)
    w = jax.ShapeDtypeStruct((n,), "float32", sharding=hooks.stacked)
    lowered = (hooks._local_fn.lower(p, mu, {"tokens": rows,
                                             "labels": rows}),
               hooks._avg_fn.lower(p, p, mu, mu, w))
    return dict(T.op_names(x.compile().as_text()) for x in lowered)


def window_op_names(hooks, batch: int, seq: int, rows_dtype="int32"
                    ) -> dict:
    """`program_op_names`, where its lowering finds the executables the
    window ran in memory; {} where it compiles anything, since a new
    executable's instruction names need not be the trace's: the scope
    metrics then read None."""
    from harness.clock import CompileClock
    t0 = time.perf_counter()
    with CompileClock() as clock:
        programs = program_op_names(hooks, batch, seq, rows_dtype)
    log(f"HLO text of {sorted(programs)} in "
        f"{time.perf_counter() - t0:.3f} s, {clock.compiles} compiles, "
        f"{clock.cache_hits} cache hits")
    if clock.compiles:
        log("the lowered programs are not the window's: no scope is read")
        return {}
    return programs


def memory_peak(n: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n])


# ---------------------------------------------------------------------------
# Per-layer metrics from the trace.
# ---------------------------------------------------------------------------
class Record:
    """What a per-layer reader (bench/metrics/<name>.py) reads: the
    trace record (harness/trace.py) with the traced window [lo, hi] in
    its clock, the benchmark's host spans inside the window, the rounds
    completed in it, the cell's model, traffic and reference module,
    the chip's published peaks, and each program's op_names by
    instruction (`program_op_names`), which `scope_ms` reads."""

    def __init__(self, cell, trace, win, spans, peaks, programs=None):
        from harness import trace as T
        self.cell = cell
        self.model, self.traffic = cell.model, cell.traffic
        self.reference = cells.reference_module(cell)
        self.peaks = peaks
        self.programs = programs or {}
        self.rounds = win["rounds"]
        self.window_s = win["t_close"] - win["t0"]
        t0, t1 = win["t0"] * 1e9, win["t_close"] * 1e9
        self.spans = {k: [(a, b) for a, b in v if a >= t0 and b <= t1]
                      for k, v in spans.spans.items()}
        self.lo, self.hi = T.window(trace)
        self.devices = trace["devices"]

    def span_s(self, name: str) -> float:
        return sum(b - a for a, b in self.spans.get(name, ())) / 1e9

    def scope_ms(self, program: str, scope=None, pass_=None):
        """Device ms per run of `program` (an HLO module name, such as
        "jit_local_train") of its ops whose op_name has `scope` as a
        path component once `jvp(...)` and `transpose(...)` are
        unwrapped, or of all its ops where `scope` is None; `pass_`
        "forward" or "backward" keeps those under `jvp` without, or
        with, `transpose` (harness/trace.py `in_scope`). Averaged over
        the chips; None where no op of the window is selected, or where
        an op in the program's runs is no instruction of its text."""
        from harness import trace as T
        names = self.programs.get(program)
        if names is None or self.unknown_ops(program):
            return None
        picked = {n for n, op in names.items()
                  if scope is None or T.in_scope(op, scope, pass_)}

        def chip(dev):
            runs = T.module_events(dev, program, self.lo, self.hi)
            ns = [d for n, d in T.program_ops(dev, runs)
                  if scope is None or n in picked]
            return sum(ns) / len(runs) / 1e6 if ns else None
        return self.per_chip(chip)

    def unknown_ops(self, program: str) -> int:
        """How many ops in the runs of `program`, over the chips, name no
        instruction of its text."""
        from harness import trace as T
        names = self.programs.get(program, {})
        return sum(n not in names for dev in self.devices
                   for n, _ in T.program_ops(dev, T.module_events(
                       dev, program, self.lo, self.hi)))

    def per_chip(self, fn):
        """Mean over the chips of fn(device), leaving out None; None
        where no chip gives a value."""
        vals = [v for v in (fn(d) for d in self.devices) if v is not None]
        return sum(vals) / len(vals) if vals else None


def reduce_trace(cell, trace_dir, win, spans, peaks, programs):
    import numpy as np
    from harness import trace as T
    rec = T.load_xplane(trace_dir)
    r = Record(cell, rec, win, spans, peaks, programs)
    for prog in sorted(programs):
        log(f"{prog}: its ops take {r.scope_ms(prog)!r} ms a run; "
            f"{r.unknown_ops(prog)} are not in its text")
    busy = [T.busy_ns(d, r.lo, r.hi) for d in rec["devices"]]
    device = {"busy_s": float(np.mean(busy)) / 1e9 if busy else 0.0,
              "window_s": (r.hi - r.lo) / 1e9}
    metrics = {}
    for m in cell.per_layer:
        value = cells.metric_reader(m["name"])(r)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    breakdown = {"device_ops": T.top_ops(rec, r.lo, r.hi),
                 "idle_gaps": T.idle_gaps(rec, r.lo, r.hi)}
    return metrics, device, breakdown


# ---------------------------------------------------------------------------
# Correctness: the reference follows the first rounds.
# ---------------------------------------------------------------------------
def reference_readings(cell, seed, batches, precision="fp32", fault=None):
    import jax
    from harness.check import change_norms_jit, leaf_norms_jit
    from reference.rounds import run_rounds
    return run_rounds(
        cells.reference_module(cell), cell.model, cell.traffic, seed,
        batches, leaf_norms=lambda t: leaf_norms_jit(t, False),
        change_norms=lambda a, b: change_norms_jit(a, b, False),
        rounds=SETUP_ROUNDS, precision=precision, fault=fault,
        devices=jax.devices()[:cell.traffic["clients"]])


def judge(cell, reads: dict):
    """(correct, checks): every number at or under its limit."""
    checks, ok = {}, True
    for name, (value, where) in reads.items():
        limit = (cell.limits.get(name) or {}).get("limit")
        checks[name] = {"value": value, "limit": limit}
        if limit is None or not value <= limit:
            ok = False
    return ok, checks


# ---------------------------------------------------------------------------
def run_cell(cell, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True):
    """One run of `cell`; returns the result object, or None where the
    platform is refused."""
    import jax
    import numpy as np
    from harness.check import readings
    from harness.clock import Spans
    from harness.peaks import chip_peaks

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        log(f"error: JAX's first device is {dev.platform} "
            f"({dev.device_kind}), not a TPU")
        return None
    if len(devices) < cell.chips:
        log(f"error: the cell needs {cell.chips} chips, found "
            f"{len(devices)}")
        return None
    peaks = chip_peaks(dev.device_kind) if require_tpu else None

    spans = Spans(annotate=trace)
    bench = Bench(cell, seed, spans)
    prog = bench.first_rounds()
    setup_s = time.perf_counter() - T_PROCESS
    log(f"setup_s {setup_s:.3f} (process start to window start)")

    seconds = min(seconds, TRACE_SECONDS) if trace else seconds
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    win = run_window(bench, seconds, tmp)
    mem = memory_peak(cell.chips)
    log(f"window: {win['rounds']} rounds in "
        f"{win['t_close'] - win['t0']:.3f} s, {win['compiles']} compiles "
        f"({win['compile_s']:.3f} s) inside it; memory_peak_bytes {mem}")
    if win["round_times"]:
        times = win["round_times"]
        slow = sorted(range(len(times)), key=lambda i: -times[i])[:5]
        log(f"round times: median {float(np.median(times)):.6f} s; "
            f"slowest (round, s) "
            f"{[(i, round(float(times[i]), 6)) for i in slow]}")

    result = {"correct": False, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": {}}
    if trace:
        import shutil
        programs = window_op_names(bench.hooks, cell.traffic["batch"],
                                   cell.traffic["seq"])
        metrics, devinfo, breakdown = reduce_trace(
            cell, tmp, win, spans, peaks, programs)
        shutil.rmtree(tmp, ignore_errors=True)
        result["metrics"] = metrics
    else:
        devinfo = {}
        rounds = max(win["rounds"], 1)
        window_s = win["t_close"] - win["t0"]
        values = {"round_s": window_s / rounds,
                  "round_p90_s": float(np.percentile(win["round_times"], 90))
                  if win["round_times"] else window_s,
                  "setup_s": setup_s}
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in values.items() if k in units}
        tr = cell.traffic
        from harness.flops import round_flops_per_client
        tokens = tr["clients"] * tr["local_steps"] * tr["batch"] * tr["seq"]
        flops = round_flops_per_client(cells.reference_module(cell),
                                       cell.model, tr)
        log(f"client tokens/s {tokens / values['round_s']:.1f}; model "
            f"FLOP per client round {flops:.6g}")
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices), "memory_peak_bytes": mem,
                        **devinfo}
    if trace:
        result["breakdown"] = breakdown

    batches = bench.batches()
    bench.free()
    del bench
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(cell, seed, batches)
    log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    reads = readings(prog, ref)
    correct, checks = judge(cell, reads)
    result["correct"] = bool(correct and win["failed"] == 0
                             and win["rounds"] > 0)
    result["checks"] = checks
    for name, (value, where) in reads.items():
        log(f"check {name}: {value!r} (limit "
            f"{checks[name]['limit']!r}; worst at {where})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    cell = cells.load_cell(args.workload)
    cache = configure_compile_cache()
    log(f"compile cache {cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    if result is None:
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
