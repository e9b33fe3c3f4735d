"""Real-training bridge (repro.fl.training): sharded LM client steps
behind the engine hook protocol, payload-exact egress billing, the
quantized-update accuracy/egress trade, and step-time calibration
against the measured-peak roofline."""
import jax
import numpy as np
import pytest

from repro.common.config import (CloudConfig, ClientProfile, FLRunConfig,
                                 MarketConfig, ProviderConfig)
from repro.comms.payload import UpdatePayload
from repro.fl.runner import FLCloudRunner
from repro.fl.training import (MeshTrainerHooks, StepCalibration,
                               calibrate, calibrated_profiles)

N_CLIENTS = 2
NAMES = tuple(f"client_{i}" for i in range(N_CLIENTS))

# egress priced + uplink modeled, so real runs bill nonzero comm_cost
COMM_MARKET = MarketConfig(providers=(
    ProviderConfig(name="aws", on_demand_rate=1.0, spot_rate_mean=0.4,
                   spot_rate_sigma=0.0,
                   update_egress_usd_per_mb=0.001, uplink_mbps=100.0),))


@pytest.fixture
def needs_devices():
    """One host device per client (tests/conftest.py provides four)."""
    if jax.device_count() < N_CLIENTS:
        pytest.skip(f"needs {N_CLIENTS} devices, found "
                    f"{jax.device_count()}")


def make_hooks(quantize=False, seed=0):
    return MeshTrainerHooks(NAMES, local_steps=1, batch=2, seq=8,
                            quantize=quantize, seed=seed)


def run_real(hooks, rounds=2, quantize=False, seed=0):
    clients = tuple(
        ClientProfile(n, mean_epoch_s=60.0 + 30.0 * i, jitter=0.0)
        for i, n in enumerate(NAMES))
    cfg = FLRunConfig(dataset="t", clients=clients, n_epochs=rounds,
                      policy="fedcostaware", seed=seed,
                      quantize_updates=quantize)
    cloud = CloudConfig(spot_rate_sigma=0.0, market=COMM_MARKET)
    return FLCloudRunner(cfg, cloud_cfg=cloud, hooks=hooks).run()


# ---------------------------------------------------------------------------
# The bridge end to end: real jitted steps inside the simulated loop.
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.usefixtures("needs_devices")
class TestMeshTrainerBridge:
    def test_real_run_trains_and_bills_real_payload(self):
        hooks = make_hooks()
        res = run_real(hooks, rounds=2)
        assert res.rounds_completed == 2
        assert len(hooks.losses) == 2
        assert np.isfinite(hooks.final_loss())
        # egress was billed off the live param pytree, not a modeled MB
        want = UpdatePayload.from_tree(hooks.global_params())
        assert res.comm_cost == pytest.approx(
            0.001 * want.size_mb * N_CLIENTS * 2)

    def test_aggregation_moves_the_global_model(self):
        hooks = make_hooks()
        before = jax.tree.map(np.asarray, hooks.global_params())
        run_real(hooks, rounds=1)
        after = hooks.global_params()
        moved = any(
            not np.allclose(np.asarray(a), b, atol=0)
            for a, b in zip(jax.tree_util.tree_leaves(after),
                            jax.tree_util.tree_leaves(before)))
        assert moved

    def test_quantized_egress_cheaper_at_bounded_loss_delta(self):
        fp_hooks = make_hooks(quantize=False)
        fp = run_real(fp_hooks, rounds=2)
        q_hooks = make_hooks(quantize=True)
        q = run_real(q_hooks, rounds=2, quantize=True)
        assert 0.0 < q.comm_cost < fp.comm_cost
        # the int8 codec must not distort training: the pinned bound
        # the --assert-comm-win benchmark gate enforces too
        delta = abs(q_hooks.final_loss() - fp_hooks.final_loss())
        assert delta <= 0.75


# ---------------------------------------------------------------------------
# Calibration: measured step time -> simulated epoch durations.
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.usefixtures("needs_devices")
class TestCalibrationMeasured:
    def test_calibration_within_3x_of_roofline(self):
        hooks = make_hooks()
        cal = calibrate(hooks)
        assert cal.measured_round_s > 0.0
        assert cal.roofline_round_s > 0.0
        # the ISSUE acceptance band: measured within 3x of the
        # measured-peak roofline estimate (combine="sum" host model)
        assert 1.0 / 3.0 <= cal.ratio <= 3.0, cal

    def test_calibrated_epoch_differs_from_config_default(self):
        hooks = make_hooks()
        cal = calibrate(hooks)
        default = ClientProfile("c", mean_epoch_s=600.0)
        out = calibrated_profiles([default], cal, time_scale=1.0)
        assert out[0].mean_epoch_s != default.mean_epoch_s
        assert out[0].mean_epoch_s == pytest.approx(cal.measured_round_s)


# ---------------------------------------------------------------------------
# Pure profile math (no devices, runs in the fast tier).
# ---------------------------------------------------------------------------
class TestCalibrationMath:
    CAL = StepCalibration(measured_round_s=0.02, roofline_round_s=0.01,
                          flops=1e9, bytes_accessed=1e8,
                          peak_flops=1e11, peak_bw=1e10)

    def test_ratio_and_time_scale(self):
        assert self.CAL.ratio == pytest.approx(2.0)
        assert self.CAL.mean_epoch_s(1000.0) == pytest.approx(20.0)

    def test_profiles_rescale_preserving_heterogeneity(self):
        profiles = [ClientProfile("a", mean_epoch_s=300.0),
                    ClientProfile("b", mean_epoch_s=600.0)]
        out = calibrated_profiles(profiles, self.CAL, time_scale=1000.0)
        # cohort mean lands on the measured anchor...
        assert np.mean([p.mean_epoch_s for p in out]) == \
            pytest.approx(20.0)
        # ...and the 2x client spread survives
        assert out[1].mean_epoch_s == pytest.approx(
            2.0 * out[0].mean_epoch_s)
        # everything else is untouched
        assert out[0].name == "a" and out[0].jitter == profiles[0].jitter
