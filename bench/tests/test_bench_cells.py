"""Every cell of BENCHMARK.json resolves to its files by name, and the
files agree with what the harness relies on."""
import json
import math
import re

import jax
import pytest

import bench_tiny  # noqa: F401  (paths)
from harness import cell as cells

BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = cells.load_cell(name)
    assert cell.chips in (1, 4)
    assert cell.traffic["clients"] == cell.chips
    ref = cells.reference_module(cell)
    assert callable(ref.param_specs) and callable(ref.loss)
    assert callable(ref.step_flops)
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m["name"]))
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "round_s"}
    assert set(cell.limits) == {"loss_gap", "grad_norm_gap",
                                "change_norm_gap"}


def test_benchmark_names_and_keys():
    assert BENCH["paths"] == ["bench"]
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for e in BENCH["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(cfg):
    data = json.loads((cells.ROOT / cfg["file"]).read_text())
    assert data["name"] == cfg["name"] == data["model"]["name"]
    assert sorted(data["reduced"]) == sorted(cfg["reduced"])
    for key, cut in data["reduced"].items():
        assert data["model"][key] == cut["here"] != cut["published"]


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_reference_tree_matches_program(config):
    """The weights the benchmark makes fit the program's parameter tree
    in every name, shape and dtype (at a small cut)."""
    import run as bench_run
    from reference.common import abstract
    from repro.models import lm
    cell = bench_tiny.tiny_cell(config, "silo1.steps2.fp32")
    want = abstract(cells.reference_module(cell).param_specs(cell.model))
    got = lm.abstract_params(bench_run.model_config(cell.model))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(want)) \
        == lm.param_count(bench_run.model_config(cell.model))
