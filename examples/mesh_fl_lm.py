"""FL in the mesh: federated training of a transformer LM where each
device of a one-axis `pod` mesh hosts one FL client slot.

`MeshTrainerHooks` builds the two round programs the FL runner and the
chip benchmark run: local training, in which no slot talks to another,
and the FedAvg barrier, one weighted `psum` over `pod`. This drives
them by hand for two clients whose data weigh 3:1.

On CPU the two devices come from the host:

    XLA_FLAGS=--xla_force_host_platform_device_count=2 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python examples/mesh_fl_lm.py
"""
import jax
import numpy as np

from repro.fl.training import MeshTrainerHooks

CLIENTS = ("client_0", "client_1")
WEIGHTS = np.asarray([3.0, 1.0], np.float32)   # client 0 has 3x the data
ROUNDS = 4

if jax.device_count() < len(CLIENTS):
    raise SystemExit(f"needs {len(CLIENTS)} devices, found "
                     f"{jax.device_count()}: set "
                     "XLA_FLAGS=--xla_force_host_platform_device_count=2")

# the phi3-mini smoke config, one client slot per device
hooks = MeshTrainerHooks(CLIENTS, local_steps=4, batch=8, seq=32, lr=5e-3)

for r in range(ROUNDS):
    new_p, new_mu, losses = hooks.local_round(hooks.next_batches())
    hooks.params_stk, hooks.mu_stk = hooks.fedavg(new_p, new_mu, WEIGHTS)
    print(f"round {r}: per-client mean loss = "
          + ", ".join(f"{l:.3f}" for l in np.asarray(losses).mean(axis=1)))

# every client slot holds the identical global model after the barrier
drift = max(float(np.max(np.abs(np.asarray(l[0], np.float32)
                                - np.asarray(l[1], np.float32))))
            for l in jax.tree.leaves(hooks.params_stk))
print(f"max cross-client param drift after the FedAvg barrier: {drift:.2e}")
assert drift == 0.0
print("OK: the FedAvg barrier leaves every client with the same model.")
