"""FL-in-the-mesh: federated learning mapped onto the multi-pod mesh.

TPU-idiomatic adaptation of the paper's client/server communication
pattern (DESIGN.md §2): each *pod* of the ``(pod, data, model)`` mesh
hosts one FL client. Client-stacked parameters carry a leading
``fl_clients`` dim sharded on the ``pod`` axis, so

  * local training steps touch only ``data``/``model`` axes (zero
    cross-pod traffic — exactly the paper's "no data leaves the client"),
  * the synchronous FedAvg round boundary is a single weighted reduction
    over the client dim, which GSPMD lowers to a cross-pod all-reduce.

Two aggregation paths:
  fedavg_sync            — plain weighted average (bf16 collective)
  fedavg_sync_compressed — int8-quantized ring aggregation via shard_map
                           + collective_permute (beyond-paper optimization;
                           ~4x less cross-pod traffic, see EXPERIMENTS §Perf)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import lm
from repro.sharding.rules import ShardingCtx


# ---------------------------------------------------------------------------
# Plain FedAvg over the client (pod) axis.
# ---------------------------------------------------------------------------
def fedavg_sync(params_stacked, weights):
    """params_stacked: (C, ...) pytree; weights: (C,). Returns the averaged
    params re-broadcast to every client slot (all clients leave the round
    with the identical global model, as synchronous FL requires)."""
    w = (weights / jnp.sum(weights)).astype(jnp.float32)

    def avg(p):
        # elementwise weighting keeps the sum in fp32 (a dot over the
        # client dim would run at the TPU's bf16 default precision)
        wb = w.reshape((-1,) + (1,) * (p.ndim - 1))
        m = jnp.sum(wb * p.astype(jnp.float32), axis=0)
        return jnp.broadcast_to(m[None].astype(p.dtype), p.shape)

    return jax.tree.map(avg, params_stacked)


# ---------------------------------------------------------------------------
# Compressed FedAvg: int8 ring all-reduce over the pod axis (shard_map).
# ---------------------------------------------------------------------------
def _quantize_int8(x):
    """Symmetric per-tensor int8 quantization; returns (q, scale)."""
    xf = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def fedavg_sync_compressed(params_stacked, global_params, weights,
                           mesh: Mesh, n_pods: int,
                           stacked_specs=None, global_specs=None):
    """Aggregate client *deltas* (client - global) in int8 over a ring of
    pods, then add back to the global model.

    Deltas (not raw weights) are quantized — their dynamic range is ~100x
    smaller after a round of local training, so int8 error is negligible
    (validated in tests against the exact average).

    CRITICAL sharding note (hypothesis->refuted->fixed, EXPERIMENTS §Perf):
    the shard_map specs must PRESERVE each leaf's within-pod (data, model)
    sharding — mapping only the `pod` axis and leaving the rest None makes
    shard_map replicate the full tensor per device (a 16GB all-gather for
    phi3). With shard-preserving specs the ring permutes only the local
    int8 shard (params/chips_per_pod bytes per step).
    """
    wn = (weights / jnp.sum(weights)).astype(jnp.float32)

    def ring_avg(delta_stk, w_all):
        # Executes per-device: delta_stk is this device's local shard of
        # its pod's client delta, client dim sharded to size 1.
        d = delta_stk[0]
        my_w = w_all[0]                    # (1,) local slice of weights
        q, scale = _quantize_int8(d)
        acc = _dequantize_int8(q, scale) * my_w
        perm = [(i, (i + 1) % n_pods) for i in range(n_pods)]
        for _ in range(n_pods - 1):
            q = lax.ppermute(q, "pod", perm)
            scale = lax.ppermute(scale, "pod", perm)
            my_w = lax.ppermute(my_w, "pod", perm)
            acc = acc + _dequantize_int8(q, scale) * my_w
        # every pod now holds the identical weighted average of its shard
        return acc[None].astype(delta_stk.dtype)

    def one_leaf(p_stk, g, spec_stk):
        delta = p_stk.astype(jnp.float32) - g.astype(jnp.float32)[None]
        fn = jax.shard_map(
            ring_avg, mesh=mesh,
            in_specs=(spec_stk, P("pod")),
            out_specs=spec_stk,
            check_vma=False)
        avg_delta = fn(delta, wn)
        return (g.astype(jnp.float32)[None]
                + jnp.broadcast_to(avg_delta, p_stk.shape)
                ).astype(p_stk.dtype)

    if stacked_specs is None:
        stacked_specs = jax.tree.map(
            lambda p: P("pod", *([None] * (p.ndim - 1))), params_stacked)
    return jax.tree.map(one_leaf, params_stacked, global_params,
                        stacked_specs)


# ---------------------------------------------------------------------------
# The full FL round step (lowered in the dry-run as the paper-representative
# program: N local steps then the synchronous aggregation barrier).
# ---------------------------------------------------------------------------
def make_fl_round_step(cfg, opt, shard: ShardingCtx, local_steps: int,
                       compressed: bool = False, mesh: Optional[Mesh] = None,
                       n_pods: int = 1, stacked_specs=None):
    """Returns round_step(params_stacked, opt_mu_stacked, batches, weights).

    params_stacked : (C, ...) model params, client dim on the pod axis
    batches        : dict of (C, local_steps, B_local, S) arrays
    weights        : (C,) FedAvg weights (client sample counts)
    """

    def local_train(params, mu, client_batches):
        def step(carry, batch):
            p, m = carry
            loss, g = jax.value_and_grad(
                lambda pp: lm.loss_fn(pp, cfg, batch, shard=shard))(p)
            # SGD-momentum inline (keeps per-client opt state to one slot)
            m = jax.tree.map(
                lambda mi, gi: 0.9 * mi + gi.astype(jnp.float32), m, g)
            p = jax.tree.map(
                lambda pi, mi: (pi.astype(jnp.float32)
                                - opt * mi).astype(pi.dtype), p, m)
            return (p, m), loss

        (params, mu), losses = lax.scan(step, (params, mu), client_batches)
        return params, mu, jnp.mean(losses)

    def round_step(params_stacked, mu_stacked, batches, weights):
        global_params = jax.tree.map(lambda p: p[0], params_stacked)
        new_p, new_mu, losses = jax.vmap(local_train)(
            params_stacked, mu_stacked, batches)
        if compressed:
            agg = fedavg_sync_compressed(new_p, global_params, weights,
                                         mesh, n_pods,
                                         stacked_specs=stacked_specs)
        else:
            agg = fedavg_sync(new_p, weights)
        return agg, new_mu, losses

    return round_step


def stack_params_for_clients(params, n_clients: int):
    return jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (n_clients,) + p.shape), params)
