"""Monte-Carlo sweep harness (repro.sweep): grid construction,
byte-identical reports across repeated and serial-vs-parallel runs,
bootstrap statistics sanity, and the multiprocessing speedup contract
(slow, multi-core only).
"""
import os
import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from repro.sweep import (MARKETS, ScenarioSpec, bootstrap_ci, build_grid,
                         build_report, market_config, run_cell, run_sweep,
                         summarize)
from repro.sweep.report import cell_key, dumps, hash_seed, ranking_table
from repro.sweep.runner import METRICS
from repro.sweep.spec import MARKET_MODELS

SMALL_GRID = dict(policies=("spot", "fedcostaware"),
                  markets=("baseline", "capacity_crunch"),
                  seeds=range(2))


class TestGrid:
    def test_grid_is_full_cross_product(self):
        specs = build_grid(**SMALL_GRID)
        assert len(specs) == 2 * 2 * 2
        assert len(set(specs)) == len(specs)     # frozen + hashable

    def test_grid_order_is_deterministic(self):
        assert build_grid(**SMALL_GRID) == build_grid(**SMALL_GRID)

    def test_default_models_come_from_registry(self):
        specs = build_grid(**SMALL_GRID)
        for s in specs:
            assert s.preemption_model == MARKET_MODELS[s.market]

    def test_explicit_models_cross_every_market(self):
        specs = build_grid(models=("constant", "price_coupled"),
                           **SMALL_GRID)
        assert len(specs) == 2 * 2 * 2 * 2
        assert {s.preemption_model for s in specs} == {
            "constant", "price_coupled"}

    def test_unknown_market_raises(self):
        with pytest.raises(ValueError, match="unknown sweep market"):
            market_config("mars", seed=0)

    def test_engine_axis_crosses_the_grid(self):
        specs = build_grid(engines=("sync", "async_buffered"),
                           **SMALL_GRID)
        assert len(specs) == 2 * 2 * 2 * 2
        assert {s.engine for s in specs} == {"sync", "async_buffered"}
        # default: the policy's own engine, spelled as ""
        assert all(s.engine == "" for s in build_grid(**SMALL_GRID))

    def test_every_registered_market_builds(self):
        for name in MARKETS:
            cfg = market_config(name, seed=1)
            assert len(cfg.providers) == 2
            if name == "baseline":
                assert cfg.scenario is None
            else:
                assert cfg.scenario.name == name
                assert cfg.scenario.seed == 1


class TestStats:
    def test_bootstrap_ci_brackets_the_mean(self):
        rng = np.random.RandomState(0)
        x = rng.normal(10.0, 2.0, size=30)
        lo, hi = bootstrap_ci(x, seed=5)
        assert lo < x.mean() < hi
        assert hi - lo < 4.0                     # not absurdly wide

    def test_bootstrap_ci_is_seeded(self):
        # continuous data: tiny discrete samples can collide across
        # seeds at the percentile grid
        x = np.random.RandomState(3).normal(10.0, 3.0, size=20)
        assert bootstrap_ci(x, seed=7) == bootstrap_ci(x, seed=7)
        assert bootstrap_ci(x, seed=7) != bootstrap_ci(x, seed=8)

    def test_single_value_collapses(self):
        assert bootstrap_ci([3.5]) == (3.5, 3.5)

    def test_empty_raises(self):
        with pytest.raises(ValueError):
            bootstrap_ci([])

    def test_summarize_fields(self):
        s = summarize([1.0, 2.0, 3.0], seed=0)
        assert set(s) == {"mean", "p10", "p50", "p90", "ci_lo",
                          "ci_hi", "n"}
        assert s["mean"] == pytest.approx(2.0)
        assert s["p50"] == pytest.approx(2.0)
        assert s["n"] == 3
        assert s["ci_lo"] <= s["mean"] <= s["ci_hi"]

    def test_hash_seed_is_stable(self):
        # pinned: must not depend on PYTHONHASHSEED or platform
        assert hash_seed("spot|baseline|price_coupled") == hash_seed(
            "spot|baseline|price_coupled")
        assert hash_seed("a") == ord("a")


class TestRunAndReport:
    @pytest.fixture(scope="class")
    def small(self):
        specs = build_grid(**SMALL_GRID)
        return specs, run_sweep(specs, parallel=False)

    def test_cells_return_all_metrics(self, small):
        _, results = small
        for r in results:
            assert set(r) == set(METRICS)
            assert r["cost"] > 0.0
            assert r["makespan_s"] > 0.0

    def test_run_cell_is_deterministic(self, small):
        specs, results = small
        assert run_cell(specs[0]) == results[0]

    def test_report_is_byte_identical_across_runs(self, small):
        specs, results = small
        a = dumps(build_report(specs, results))
        b = dumps(build_report(specs, run_sweep(specs, parallel=False)))
        assert a == b

    def test_report_shape(self, small):
        specs, results = small
        rep = build_report(specs, results)
        assert sorted(rep["grid"]["policies"]) == ["fedcostaware",
                                                   "spot"]
        assert len(rep["cells"]) == 4            # 2 policies x 2 markets
        for key, cell in rep["cells"].items():
            assert key == cell_key(next(s for s in specs
                                        if cell_key(s) == key))
            assert cell["seeds"] == [0, 1]
            for m in METRICS:
                assert cell[m]["n"] == 2

    def test_engine_override_is_deterministic_and_distinct(self):
        """The engine axis reaches the run: the same (policy, market,
        seed) cell under sync vs async_buffered produces different —
        and individually reproducible — metrics, keyed apart in the
        report."""
        specs = build_grid(policies=("fedcostaware",),
                           markets=("baseline",), seeds=range(2),
                           n_epochs=3, engines=("sync", "async_buffered"))
        results = run_sweep(specs, parallel=False)
        assert results == run_sweep(specs, parallel=False)
        rep = build_report(specs, results)
        keys = sorted(rep["cells"])
        assert keys == [
            "fedcostaware|baseline|price_coupled|async_buffered",
            "fedcostaware|baseline|price_coupled|sync"]
        sync_c = rep["cells"][keys[1]]["cost"]["mean"]
        async_c = rep["cells"][keys[0]]["cost"]["mean"]
        assert sync_c != async_c
        assert rep["grid"]["engines"] == ["async_buffered", "sync"]
        # default-engine specs keep the 3-part key (old reports intact)
        assert cell_key(build_grid(**SMALL_GRID)[0]).count("|") == 2

    def test_report_length_mismatch_raises(self, small):
        specs, results = small
        with pytest.raises(ValueError, match="specs vs"):
            build_report(specs, results[:-1])

    def test_ranking_table_lists_every_market(self, small):
        specs, results = small
        table = ranking_table(build_report(specs, results))
        assert "baseline:" in table
        assert "capacity_crunch:" in table
        assert "fedcostaware" in table and "spot" in table

    def test_parallel_equals_serial(self, small):
        """The pool path returns the same results in the same order as
        in-process execution — fan-out must not perturb a single
        bit."""
        specs, serial = small
        par = run_sweep(specs, parallel=True, processes=2)
        assert par == serial

    def test_cells_start_no_jax_backend(self):
        """The pool forks, so the sweep path must stay JAX-free: a cell
        run in a fresh interpreter leaves no JAX backend initialized
        (no device held, no runtime threads to fork)."""
        import subprocess
        code = (
            "from repro.sweep import build_grid, run_cell\n"
            "from jax._src import xla_bridge\n"
            "spec = build_grid(policies=('fedcostaware',), "
            "markets=('baseline',), seeds=range(1), n_clients=3, "
            "n_epochs=2)[0]\n"
            "run_cell(spec)\n"
            "assert not xla_bridge.backends_are_initialized()\n")
        env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
        subprocess.run([sys.executable, "-c", code], env=env, check=True,
                       timeout=120)


@pytest.mark.slow
@pytest.mark.skipif(os.cpu_count() is None or os.cpu_count() < 4,
                    reason="speedup contract needs >= 4 cores")
def test_pool_speedup_on_four_cores():
    """With 4+ cores a 12-cell sweep over 4 workers must beat serial by
    >= 2x (generous: perfect scaling would be ~4x)."""
    specs = build_grid(policies=("spot", "fedcostaware", "on_demand"),
                       markets=("baseline", "capacity_crunch"),
                       seeds=range(2), n_clients=16, n_epochs=10)
    t0 = time.perf_counter()
    serial = run_sweep(specs, parallel=False)
    t_serial = time.perf_counter() - t0
    t0 = time.perf_counter()
    par = run_sweep(specs, parallel=True, processes=4)
    t_par = time.perf_counter() - t0
    assert par == serial
    assert t_serial / t_par >= 2.0, (
        f"pool speedup {t_serial / t_par:.2f}x < 2x "
        f"(serial {t_serial:.2f}s, parallel {t_par:.2f}s)")


class TestBenchmarkCLI:
    def test_smoke_grid_and_crunch_gate(self, tmp_path):
        """The CI smoke invocation end to end: small grid, report on
        disk, ranking printed, crunch-win gate satisfied."""
        sys.path.insert(0, str(REPO / "benchmarks"))
        try:
            import sweep as sweep_cli
        finally:
            sys.path.pop(0)
        out = tmp_path / "BENCH_sweep.json"
        report = sweep_cli.main([
            "--policies", "spot", "fedcostaware",
            "--markets", "baseline", "capacity_crunch",
            "--seeds", "3", "--serial", "--out", str(out),
            "--assert-crunch-win"])
        assert out.exists()
        assert len(report["cells"]) == 4
