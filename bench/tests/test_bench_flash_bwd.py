"""flash_bwd_ms on the recorded excerpt (bench/tests/trace_excerpt.json,
one round of phi3.1chip.int8 from before the backward kernels): nothing
to read there, and, with the backward's calls added by hand, their time
per round, apart from the forward's."""
import copy
import json
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (paths)
from harness import cell as cells
from harness import trace as T
from harness.peaks import chip_peaks

EXCERPT = json.loads((Path(__file__).parent / "trace_excerpt.json")
                     .read_text())


def _record(excerpt, rounds):
    import run as bench_run
    from harness.clock import Spans
    cell = cells.load_cell("phi3.1chip.int8")
    lo, hi = T.window(excerpt)
    spans = Spans()
    for name, s, d in excerpt["host"]:
        spans.spans.setdefault(name[len("bench."):], []).append((s, s + d))
    win = {"rounds": rounds, "t0": lo / 1e9, "t_close": hi / 1e9}
    return bench_run.Record(cell, excerpt, win, spans,
                            chip_peaks("TPU v5 lite"))


def test_silent_without_the_backward_kernels():
    assert cells.metric_reader("flash_bwd_ms")(_record(EXCERPT, 1)) is None


@pytest.mark.parametrize("rounds", [1, 2])
def test_backward_calls_per_round(rounds):
    rec = copy.deepcopy(EXCERPT)
    lo, hi = T.window(rec)
    dev = rec["devices"][0]
    forward = T.kernel_ops(dev, ("flash_attention",), lo, hi)
    start = lo + 1000
    for i, (name, dur) in enumerate([("fa_bwd_dkv.1", 2.5e6),
                                     ("fa_bwd_dq.1", 1.5e6),
                                     ("fa_bwd_dkv.1", 2.0e6)]):
        dev["ops"].append([name, "custom-call", "tpu_custom_call",
                           start + i * 1e7, dur])
    # a fusion named like a kernel is no Pallas call
    dev["ops"].append(["fa_bwd_dq.2", "fusion", "", start + 5e7, 9e6])
    got = cells.metric_reader("flash_bwd_ms")(_record(rec, rounds))
    assert got == pytest.approx(6.0 / rounds)
    assert T.kernel_ops(dev, ("flash_attention",), lo, hi) == forward
