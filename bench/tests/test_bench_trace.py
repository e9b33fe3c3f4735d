"""The trace reduction on a recorded excerpt: one round of
phi3.1chip.int8 on one TPU v5 lite chip (bench/tests/trace_excerpt.json,
cut from a chip trace by hand: ops of 50 us or more and every Pallas
call)."""
import json
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (paths)
from harness import cell as cells
from harness import trace as T
from harness.flops import flash_fwd_cost, least_time
from harness.peaks import chip_peaks

EXCERPT = json.loads((Path(__file__).parent / "trace_excerpt.json")
                     .read_text())


@pytest.fixture
def record():
    import run as bench_run
    from harness.clock import Spans
    cell = cells.load_cell("phi3.1chip.int8")
    lo, hi = T.window(EXCERPT)
    spans = Spans()
    for name, s, d in EXCERPT["host"]:
        spans.spans.setdefault(name[len("bench."):], []).append((s, s + d))
    win = {"rounds": 1, "t0": lo / 1e9, "t_close": hi / 1e9}
    return bench_run.Record(cell, EXCERPT, win, spans,
                            chip_peaks("TPU v5 lite"))


def test_interval_arithmetic():
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.clip([(0, 10)], 2, 4) == [(2, 4)]
    assert T.subtract([(0, 10)], [(2, 3), (2.5, 4), (9, 12)]) == 7


def test_modules_by_name(record):
    dev = EXCERPT["devices"][0]
    local = T.module_events(dev, "jit_local_train", record.lo, record.hi)
    fedavg = T.module_events(dev, "jit_fedavg", record.lo, record.hi)
    assert len(local) == len(fedavg) == 1
    read = lambda m: cells.metric_reader(m)(record)
    assert read("local_device_ms") == pytest.approx(277.192512)
    assert read("fedavg_device_ms") == pytest.approx(77.097256)


def test_kernel_rooflines_by_hand(record):
    dev = EXCERPT["devices"][0]
    flash = T.kernel_ops(dev, ("flash_attention",), record.lo, record.hi)
    # 5 layers x 2 local steps, each forward run twice (remat)
    assert len(flash) == 20
    peaks = record.peaks
    t_min, bound = least_time(*flash_fwd_cost(record.model, 1, 2048),
                              peaks.bf16_flops, peaks.hbm_bw)
    assert bound == "compute"
    want = 100 * 20 * t_min / (T.length(flash) / 1e9)
    got = cells.metric_reader("flash_attention_roofline")(record)
    assert got == pytest.approx(want) and 0 < got < 100

    codec = T.kernel_ops(dev, ("vmap_jit_quantize", "vmap_jit_dequantize"),
                         record.lo, record.hi)
    assert len(codec) == 24                  # 12 leaves, there and back
    got = cells.metric_reader("grad_quant_roofline")(record)
    assert 0 < got < 100


def test_idle_and_breakdown(record):
    idle = cells.metric_reader("idle_pct")(record)
    assert 0 <= idle < 100
    ops = T.top_ops(EXCERPT, record.lo, record.hi)
    assert len(ops) == 10 and not {o[0] for o in ops} & set(T.CONTROL_FLOW)
    assert ops[0][1] >= ops[-1][1] > 0
    gaps = T.idle_gaps(EXCERPT, record.lo, record.hi)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert {g[0] for g in gaps} <= {"engine", "next_batches",
                                    "local_dispatch", "fedavg_dispatch",
                                    "aggregate"}


def test_no_kernel_reads_nothing(record):
    """A reader with nothing to read returns None, never 0."""
    record.devices = [{"id": 0, "ops": [], "modules": []}]
    for name in ("flash_attention_roofline", "grad_quant_roofline",
                 "local_device_ms", "fedavg_device_ms",
                 "allreduce_exposed_ms"):
        assert cells.metric_reader(name)(record) is None


MS = 1e6
# an all-reduce (named psum.N, as on four chips) of 10 ms, overlapped for
# 5 ms by a fusion and for 1 ms by a copy, in one FedAvg run
FLAT = [["fusion.1", "fusion", "", 0.0, 10 * MS],
        ["psum.3", "all-reduce", "", 5 * MS, 10 * MS],
        ["copy.2", "copy", "", 12 * MS, 1 * MS]]


def _one_fedavg(record, ops):
    record.lo, record.hi = 0.0, 30 * MS
    record.devices = [{"id": 0, "modules": [["jit_fedavg(1)", 0.0, 20 * MS]],
                       "ops": ops}]
    return cells.metric_reader("allreduce_exposed_ms")(record)


def test_allreduce_exposed_by_hand(record):
    """4 ms of the all-reduce exposed, one round."""
    assert _one_fedavg(record, FLAT) == pytest.approx(5.0 - 1.0)
    assert T.busy_ns(record.devices[0], record.lo, record.hi) == 15 * MS


def test_allreduce_exposed_inside_control_flow(record):
    """A `while` that encloses the all-reduce and the ops beside it
    covers nothing: its time is that of its body."""
    ops = [["while.7", "while", "", 0.0, 20 * MS]] + FLAT
    assert _one_fedavg(record, ops) == pytest.approx(5.0 - 1.0)
