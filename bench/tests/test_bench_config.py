"""The harness takes any configuration the program runs: the program's
config is built whole from a configuration file's "model", and the work
of a round is counted by the configuration's own reference."""
import dataclasses
import json
import types

import pytest

import bench_tiny
from harness import cell as cells
from harness.flops import round_flops_per_client
from repro import configs

ARCHS = [(a, smoke) for a in configs.ARCH_IDS for smoke in (False, True)]


@pytest.mark.parametrize("arch,smoke", ARCHS,
                         ids=[f"{a}-{'smoke' if s else 'full'}"
                              for a, s in ARCHS])
def test_config_round_trips_through_json(arch, smoke):
    import run as bench_run
    cfg = configs.get_config(arch, smoke=smoke)
    model = json.loads(json.dumps(dataclasses.asdict(cfg)))
    assert bench_run.model_config(model) == cfg


# A configuration the harness has never seen: a layer kind that no
# harness file names, with a "moe" object. Its reference counts a step
# by its own rule (here: 6 N over 10 parameters a token, and 7 FLOPs of
# mixing a token per layer, forward).
NEW_KIND = "mixer_x"
MODEL = {"name": "new", "family": "moe", "num_layers": 2, "d_model": 16,
         "num_heads": 2, "num_kv_heads": 1, "d_ff": 0, "vocab_size": 64,
         "pattern": [NEW_KIND, "attn"],
         "moe": {"num_experts": 8, "top_k": 2, "d_ff": 32,
                 "capacity_factor": 1.5, "group_size": 64,
                 "router_jitter": 0.0},
         "tie_embeddings": True}
STUB = '''
def step_flops(model, batch, seq):
    tokens = batch * seq
    return 6.0 * 10 * tokens + 3.0 * 7 * tokens * model["num_layers"]
'''
TRAFFIC = {"local_steps": 3, "batch": 2, "seq": 4}
PER_ROUND = 3 * (6.0 * 10 * 8 + 3.0 * 7 * 8 * 2)


@pytest.fixture
def stub_cell(tmp_path, monkeypatch):
    """A cell whose configuration names a reference that lives in a
    reference directory of its own."""
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference" / "stub_lm.py").write_text(STUB)
    monkeypatch.setattr(cells, "BENCH_DIR", tmp_path)
    return cells.Cell(name="new.cell", chips=1,
                      config={"reference": "stub_lm", "model": MODEL},
                      traffic=TRAFFIC, limits={}, end_to_end=[],
                      per_layer=[])


def test_new_kind_with_experts_is_parsed(monkeypatch):
    """Once the program knows the kind, the harness needs no edit: the
    "moe" object becomes the program's MoEConfig."""
    import run as bench_run
    from repro.common import config as program_config
    monkeypatch.setattr(program_config, "SUPPORTED_KINDS",
                        program_config.SUPPORTED_KINDS + (NEW_KIND,))
    cfg = bench_run.model_config(MODEL)
    assert cfg.pattern == (NEW_KIND, "attn")
    assert cfg.moe == program_config.MoEConfig(
        num_experts=8, top_k=2, d_ff=32, capacity_factor=1.5,
        group_size=64)
    assert cfg.moe.num_experts == 8


def test_new_kind_is_counted_by_its_reference(stub_cell):
    ref = cells.reference_module(stub_cell)
    assert round_flops_per_client(ref, stub_cell.model,
                                  stub_cell.traffic) == PER_ROUND


def test_new_kind_reaches_round_mfu(stub_cell):
    """round_mfu reads the count from the cell's reference alone."""
    from harness.peaks import chip_peaks
    r = types.SimpleNamespace(
        reference=cells.reference_module(stub_cell), model=MODEL,
        traffic=TRAFFIC, rounds=5, window_s=2.0,
        peaks=chip_peaks("TPU v5 lite"))
    read = cells.load_module(bench_tiny.BENCH / "metrics" / "round_mfu.py",
                             "bench_metric_round_mfu").read
    got = read(r)
    assert got == pytest.approx(100.0 * 5 * PER_ROUND / (2.0 * 197e12))
