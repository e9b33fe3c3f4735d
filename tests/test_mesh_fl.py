"""FL-in-the-mesh tests (2 fake pods on CPU): plain vs compressed FedAvg
agreement, sync-barrier invariants, and the FL round step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.fl import mesh_fl
from repro.models import lm
from repro.sharding import rules as R


@pytest.fixture
def mesh():
    """A (pod=2, data=1, model=1) mesh over two of the host devices that
    tests/conftest.py provides."""
    if jax.device_count() < 2:
        pytest.skip(f"needs 2 devices, found {jax.device_count()}")
    return jax.make_mesh((2, 1, 1), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3,
                         devices=jax.devices()[:2])


def tiny_tree(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "w": jnp.asarray(rng.randn(2, 8, 16) * 0.1, jnp.float32),
        "b": jnp.asarray(rng.randn(2, 16) * 0.1, jnp.float32),
    }


class TestFedAvgSync:
    def test_weighted_mean_and_broadcast(self):
        stk = tiny_tree()
        w = jnp.asarray([3.0, 1.0])
        out = mesh_fl.fedavg_sync(stk, w)
        expect = (3 * np.asarray(stk["w"][0]) + np.asarray(stk["w"][1])) / 4
        np.testing.assert_allclose(np.asarray(out["w"][0]), expect,
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out["w"][0]),
                                   np.asarray(out["w"][1]), rtol=0)

    def test_compressed_matches_plain_within_int8(self, mesh):
        stk = tiny_tree(1)
        glob = jax.tree.map(lambda p: p[0] * 0.9, stk)   # deltas ~0.1 scale
        w = jnp.asarray([1.0, 2.0])
        plain = mesh_fl.fedavg_sync(stk, w)
        with jax.set_mesh(mesh):
            comp = jax.jit(
                lambda s, g, ww: mesh_fl.fedavg_sync_compressed(
                    s, g, ww, mesh, 2))(stk, glob, w)
        for k in ("w", "b"):
            delta_amax = float(jnp.max(jnp.abs(
                stk[k] - glob[k][None])))
            err = float(jnp.max(jnp.abs(comp[k] - plain[k])))
            # int8 per-tensor quantization error bound on the delta
            assert err <= 2 * delta_amax / 127 + 1e-6, (k, err)

    def test_round_step_sync_barrier(self, mesh):
        rules = R.make_rules("train")
        shard = R.ShardingCtx(mesh, rules)
        cfg = configs.get_config("phi3-mini-3.8b", smoke=True)
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        stk = mesh_fl.stack_params_for_clients(params, 2)
        mu = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), stk)
        rng = np.random.RandomState(0)
        batch = {
            "tokens": jnp.asarray(
                rng.randint(0, cfg.vocab_size, (2, 2, 2, 16)), jnp.int32),
            "labels": jnp.asarray(
                rng.randint(0, cfg.vocab_size, (2, 2, 2, 16)), jnp.int32),
        }
        weights = jnp.asarray([1.0, 1.0])
        step = mesh_fl.make_fl_round_step(cfg, opt=1e-2, shard=shard,
                                          local_steps=2, mesh=mesh,
                                          n_pods=2)
        with jax.set_mesh(mesh):
            new_stk, new_mu, losses = jax.jit(step)(stk, mu, batch, weights)
        assert losses.shape == (2,)
        assert bool(jnp.all(jnp.isfinite(losses)))
        # after the barrier every client holds the identical model
        for leaf in jax.tree.leaves(new_stk):
            assert float(jnp.max(jnp.abs(
                leaf[0].astype(jnp.float32)
                - leaf[1].astype(jnp.float32)))) < 1e-5
        # and it differs from the initial model (training happened)
        moved = sum(float(jnp.sum(jnp.abs(
            a[0].astype(jnp.float32) - b[0].astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(new_stk),
                            jax.tree.leaves(stk)))
        assert moved > 0
