"""Real-training bridge (repro.fl.training): sharded LM client steps
behind the engine hook protocol, payload-exact egress billing, the
quantized-update accuracy/egress trade, and step-time calibration
against the measured-peak roofline."""
import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.common.config import (CloudConfig, ClientProfile, FLRunConfig,
                                 MarketConfig, ProviderConfig)
from repro.comms.payload import UpdatePayload
from repro.fl.runner import FLCloudRunner
from repro.fl.training import (MeshTrainerHooks, StepCalibration,
                               calibrate, calibrated_profiles,
                               make_round_programs)
from repro.kernels.grad_quant import ops as gq
from repro.models import lm

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
# The FedAvg program's int8 leaf path against the codec, leaf by leaf.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("weights", [[0.6], [0.6, 0.0], [0.25, 0.75]],
                         ids=["1dev", "2dev-weight0", "2dev"])
def test_fedavg_int8_leaf_path(weights, use_pallas):
    """`fedavg` under `quantize=True` gives, bit for bit, old + the sum
    over slots of wn * dequantize(quantize(new - old)) in fp32, each
    leaf's delta through the codec whole as before, stored in the leaf's
    dtype; a weight-0 slot keeps its old momentum."""
    n = len(weights)
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices, found {jax.device_count()}")
    cfg = dataclasses.replace(configs.get_config("phi3-mini-3.8b",
                                                 smoke=True),
                              param_dtype="bfloat16")
    mesh = jax.make_mesh((n,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:n])
    _, fedavg = make_round_programs(cfg, mesh, lr=5e-3, quantize=True,
                                    use_pallas=use_pallas)
    leaves, tree = jax.tree.flatten(lm.init_params(cfg,
                                                   jax.random.PRNGKey(3)))
    normal = lambda seed, i, x: jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(seed), i), (n,) + x.shape)
    old = [jnp.broadcast_to(x[None], (n,) + x.shape) for x in leaves]
    new = [(o.astype(jnp.float32) + 1e-2 * normal(4, i, x)).astype(x.dtype)
           for i, (o, x) in enumerate(zip(old, leaves))]
    old_mu = [normal(5, i, x) for i, x in enumerate(leaves)]
    new_mu = [normal(6, i, x) for i, x in enumerate(leaves)]
    w = np.asarray(weights, np.float32)
    wn = w / np.maximum(np.sum(w, dtype=np.float32), np.float32(1e-12))

    want = []
    for nl, ol in zip(new, old):
        avg = None
        for c in range(n):
            d = nl[c].astype(jnp.float32) - ol[c].astype(jnp.float32)
            term = wn[c] * gq.dequantize(*gq.quantize(d), d.shape)
            avg = term if avg is None else avg + term
        want.append((ol[0].astype(jnp.float32) + avg).astype(ol.dtype))
    mu_want = [np.where(w.reshape((-1,) + (1,) * (m.ndim - 1)) > 0,
                        np.asarray(m), np.asarray(o))
               for m, o in zip(new_mu, old_mu)]

    stk = NamedSharding(mesh, P("pod"))
    put = lambda xs: jax.device_put(tree.unflatten(xs), stk)
    interpret = (pltpu.force_tpu_interpret_mode() if use_pallas
                 else contextlib.nullcontext())
    with interpret:
        got, mu = fedavg(put(new), put(old), put(new_mu), put(old_mu),
                         jax.device_put(w, stk))
    bits = lambda a: np.ascontiguousarray(np.asarray(a)).view(np.uint8)
    for g, want_leaf in zip(jax.tree.leaves(got), want):
        for c in range(n):
            np.testing.assert_array_equal(bits(g[c]), bits(want_leaf))
    for m, m_want in zip(jax.tree.leaves(mu), mu_want):
        np.testing.assert_array_equal(bits(m), bits(m_want))


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
