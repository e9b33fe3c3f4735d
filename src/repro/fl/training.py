"""Real-training bridge: client LM steps and the FedAvg barrier in the FL loop.

`MeshTrainerHooks` is the `TrainerHooks` implementation that replaces
hand-set epoch times and toy NumPy clients with the repo's real model
stack: `models/lm.py` forward/backward (flash-attention path included)
on a one-axis `pod` mesh with one FL client slot per device. Client
stacks carry a leading client dim placed on `pod`
(`NamedSharding(mesh, P("pod"))`), and both round programs are
`shard_map`s over that axis: each device trains only its own slot, with
no cross-device traffic, and the FedAvg barrier is one `psum` over
`pod`. On a TPU host that is one client per chip; on CPU the devices
come from ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, set by
the caller before jax is imported.

Engine protocol mapping: the simulator calls `run_local(c, r)` at each
client's simulated epoch-completion instant — the hooks only mark the
client as a round participant there — and the actual jitted compute
runs once per round inside `aggregate`, which local-trains every client
slot and folds the *participants'* updates into the global model
(non-participants get weight 0 and keep their previous momentum).
Staleness folds into the FedAvg weights by the FedBuff 1/sqrt(1+s)
discount, so the async engine's reports are honored.

Quantized updates (`quantize=True`) round-trip every participant's
per-leaf delta through the `kernels/grad_quant` int8 block codec before
the weighted average — the int8 payload the comms subsystem bills
(`comms/payload.py` mirrors the codec's exact byte layout) is the same
one the real `aggregate()` consumes.

Calibration (`calibrate` / `calibrated_profiles`) anchors simulated
time to real compute: it wall-clocks the jitted round, cross-checks the
measurement against a roofline estimate built from the compiled HLO's
FLOP/byte counts (`launch.roofline.estimate_step_time`) and the
device's peaks — the published peaks of the chip's `device_kind`, or
peaks measured on the host for CPU devices — and rewrites
`ClientProfile.mean_epoch_s` from the measurement.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.common import tracing
from repro.common.config import ClientProfile, ModelConfig
from repro.comms.payload import UpdatePayload
from repro.data.synthetic import token_stream
from repro.fl.server import JaxTrainerHooks
from repro.fl.types import TrainerHooks
from repro.kernels.grad_quant import ops as gq
from repro.models import lm

_POD = P("pod")

# `jax.named_scope` names of the round programs' parts, as a compiled
# op's `op_name` carries them: the local program's forward ops read
# `jvp(forward)`, its backward ops (remat recompute included)
# `transpose(jvp(forward))`; FedAvg's read `delta`, `codec` or `sum`.
FORWARD, OPTIMIZER = "forward", "optimizer"
DELTA, CODEC, SUM = "delta", "codec", "sum"


def _client_mesh(n_clients: int) -> jax.sharding.Mesh:
    """A one-axis `pod` mesh over the first `n_clients` devices, one
    client slot per device."""
    devices = jax.devices()
    if len(devices) < n_clients:
        raise ValueError(
            f"{n_clients} clients need {n_clients} devices, one client "
            f"slot each; found {len(devices)} {devices[0].platform} "
            f"device(s) ({devices[0].device_kind})")
    return jax.make_mesh((n_clients,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devices[:n_clients])


def make_round_programs(cfg: ModelConfig, mesh: jax.sharding.Mesh, *,
                        lr: float, quantize: bool, use_pallas: bool):
    """The two jitted programs of one FL round on `mesh`'s `pod` axis.

    `local(params, mu, batches) -> (params, mu, losses)` runs
    `batches`' leading-step count of SGD-momentum steps on every client
    slot. `fedavg(new_p, old_p, new_mu, old_mu, w) -> (params, mu)` is
    the barrier: each slot's fp32 delta against its pre-round params
    (every slot holds the global model), optionally round-tripped
    through the int8 codec, weighted by `w` and summed over `pod`; slots
    with weight 0 keep their old momentum. `new_p`/`new_mu` are donated.
    Every argument and result is a client stack placed on `pod`.
    """
    stk = NamedSharding(mesh, _POD)

    def local_train(params, mu, client_batches):
        def forward(pp, batch):
            with jax.named_scope(FORWARD):
                return lm.loss_fn(pp, cfg, batch)

        def step(carry, batch):
            p, m = carry
            loss, g = jax.value_and_grad(forward)(p, batch)
            with jax.named_scope(OPTIMIZER):
                m = jax.tree.map(
                    lambda mi, gi: 0.9 * mi + gi.astype(jnp.float32), m, g)
                p = jax.tree.map(
                    lambda pi, mi: (pi.astype(jnp.float32)
                                    - lr * mi).astype(pi.dtype), p, m)
            return (p, m), loss

        (params, mu), losses = lax.scan(step, (params, mu),
                                        client_batches)
        return params, mu, losses

    def fedavg(new_p, old_p, new_mu, old_mu, w):
        # per device: slot-local arrays with a leading client dim
        wn = w / jnp.maximum(lax.psum(jnp.sum(w), "pod"), 1e-12)
        bcast = lambda x, ref: x.reshape((-1,) + (1,) * (ref.ndim - 1))

        def leaf(n, o):
            with jax.named_scope(DELTA):
                d = n.astype(jnp.float32) - o.astype(jnp.float32)
            # elementwise weighting keeps the sum in fp32 (a dot over
            # the client dim would run at the TPU's bf16 default)
            with jax.named_scope(SUM):
                avg = lax.psum(jnp.sum(bcast(wn, d) * d, axis=0), "pod")
            return (o.astype(jnp.float32) + avg).astype(o.dtype)

        def codec_leaf(n, o):
            # the same sum over the int8 codec's deltas, in three passes:
            # the leaf's row views (a free reshape where the view keeps
            # the leaf's rows), the two kernels (quantize forms the fp32
            # delta in VMEM), then the weighted sum and add-back on the
            # views. The barrier holds the views as built, so that the
            # compiler neither redoes a relayout for the add-back nor
            # moves the add-back off the views.
            with jax.named_scope(DELTA):
                nv, ov = lax.optimization_barrier(
                    (jax.vmap(gq.rows)(n), jax.vmap(gq.rows)(o)))
            with jax.named_scope(CODEC):
                q, s = jax.vmap(functools.partial(
                    gq.quantize_delta, use_pallas=use_pallas))(nv, ov)
                d = jax.vmap(functools.partial(
                    gq.dequantize, shape=nv.shape[1:],
                    use_pallas=use_pallas))(q, s)
            with jax.named_scope(SUM):
                avg = lax.psum(jnp.sum(bcast(wn, d) * d, axis=0), "pod")
                out = (ov.astype(jnp.float32) + avg).astype(o.dtype)
                return jax.vmap(functools.partial(
                    gq.unrows, shape=o.shape[1:]))(out)

        keep = w > 0
        mu = jax.tree.map(lambda n, o: jnp.where(bcast(keep, n), n, o),
                          new_mu, old_mu)
        return jax.tree.map(codec_leaf if quantize else leaf,
                            new_p, old_p), mu

    # check_vma=False: the Pallas kernels' out_shapes carry no
    # varying-mesh-axis annotation, which the check would demand
    local = jax.jit(
        jax.shard_map(jax.vmap(local_train), mesh=mesh,
                      in_specs=(_POD, _POD, _POD),
                      out_specs=(_POD, _POD, _POD), check_vma=False),
        in_shardings=(stk, stk, stk), out_shardings=(stk, stk, stk))
    avg = jax.jit(
        jax.shard_map(fedavg, mesh=mesh, in_specs=(_POD,) * 5,
                      out_specs=(_POD, _POD), check_vma=False),
        in_shardings=(stk,) * 5, out_shardings=(stk, stk),
        donate_argnums=(0, 2))
    return local, avg


class MeshTrainerHooks(TrainerHooks):
    """Real LM training behind the engine hook protocol (see module
    docstring for the round mapping). `cfg` defaults to the phi3-mini
    smoke config."""

    def __init__(self, clients: Sequence[str],
                 cfg: Optional[ModelConfig] = None,
                 local_steps: int = 4, batch: int = 8, seq: int = 32,
                 lr: float = 5e-3, quantize: bool = False,
                 use_pallas: bool = False, seed: int = 0,
                 weights: Optional[Dict[str, float]] = None):
        self.clients = list(clients)
        self.slot = {c: i for i, c in enumerate(self.clients)}
        if len(self.slot) != len(self.clients):
            raise ValueError("duplicate client names")
        self.cfg = cfg or configs.get_config("phi3-mini-3.8b", smoke=True)
        self.local_steps = local_steps
        n = len(self.clients)
        self.mesh = _client_mesh(n)
        self.stacked = NamedSharding(self.mesh, _POD)
        with tracing.span("fl.hooks_init"):
            with tracing.span("fl.build_programs"):
                self._local_fn, self._avg_fn = make_round_programs(
                    self.cfg, self.mesh, lr=lr, quantize=quantize,
                    use_pallas=use_pallas)

            def init(key):
                p = lm.init_params(self.cfg, key)
                stk = jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), p)
                return stk, jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), stk)

            with tracing.span("fl.init_params"):
                self.params_stk, self.mu_stk = jax.jit(
                    init, out_shardings=(self.stacked, self.stacked))(
                        jax.random.PRNGKey(seed))
            with tracing.span("fl.streams"):
                self._streams = [
                    token_stream(self.cfg.vocab_size, batch, seq,
                                 seed=seed + 17 * i) for i in range(n)]
        self._base_w = np.array(
            [float((weights or {}).get(c, 1.0)) for c in self.clients])
        self._participants: Dict[str, int] = {}   # client -> last round
        self.losses: List[dict] = []              # per-aggregation record

    # ------------------------------------------------------------------
    # TrainerHooks protocol.
    # ------------------------------------------------------------------
    def run_local(self, client: str, round_idx: int) -> None:
        """Mark the client's round-`round_idx` update as produced; the
        jitted compute itself batches into `aggregate` (one round
        program per aggregation, every device training in parallel)."""
        if client not in self.slot:
            raise KeyError(f"unknown client {client!r}")
        self._participants[client] = round_idx

    def aggregate(self, participants: List[str], round_idx: int,
                  staleness: Optional[Dict[str, int]] = None) -> None:
        """Run the real round: local training on every slot, then fold
        the participants' (optionally int8-round-tripped) deltas into
        the global model with staleness-discounted FedAvg weights."""
        live = [c for c in participants if c in self._participants]
        if not live:
            return
        stale = staleness or {}
        w = np.zeros(len(self.clients), np.float32)
        for c in set(live):
            w[self.slot[c]] = (
                self._base_w[self.slot[c]]
                * JaxTrainerHooks.staleness_discount(stale.get(c, 0)))
        with tracing.span("fl.next_batches"):
            batches = self.next_batches()
        with tracing.span("fl.local_dispatch"):
            new_p, new_mu, losses = self.local_round(batches)
        with tracing.span("fl.fedavg_dispatch"):
            self.params_stk, self.mu_stk = self.fedavg(new_p, new_mu, w)
        with tracing.span("fl.loss_fetch"):     # waits on the device
            losses = np.asarray(losses)
        self.losses.append({
            "round": round_idx,
            "mean_loss": float(np.mean(
                [losses[self.slot[c]].mean() for c in set(live)]))})
        for c in live:
            self._participants.pop(c, None)

    def update_payload(self, quantized: bool = False) -> UpdatePayload:
        """Byte-exact size of one client's update: the global param
        pytree in the requested wire format (sized from shapes alone)."""
        slot = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape[1:], p.dtype),
            self.params_stk)
        return UpdatePayload.from_tree(slot, quantized=quantized)

    # ------------------------------------------------------------------
    # Round execution + measurement.
    # ------------------------------------------------------------------
    def next_batches(self):
        """The next `local_steps` batches of every client stream,
        stacked `(clients, local_steps, batch, seq)` and placed one
        client per device."""
        stacked = {"tokens": [], "labels": []}
        for s in self._streams:
            rows = [next(s) for _ in range(self.local_steps)]
            stacked["tokens"].append(np.stack([r["tokens"] for r in rows]))
            stacked["labels"].append(np.stack([r["labels"] for r in rows]))
        return jax.device_put({k: np.stack(v) for k, v in stacked.items()},
                              self.stacked)

    def local_round(self, batches):
        """Local training of every slot from the current state, which
        is not advanced: `(params, mu, losses)` client stacks."""
        return self._local_fn(self.params_stk, self.mu_stk, batches)

    def fedavg(self, new_p, new_mu, w):
        """The FedAvg barrier over the round's results (`new_p` and
        `new_mu` are consumed) with per-slot weights `w`: the new
        `(params, mu)` stacks. State is not advanced."""
        return self._avg_fn(new_p, self.params_stk, new_mu, self.mu_stk,
                            jax.device_put(np.asarray(w, np.float32),
                                           self.stacked))

    def global_params(self):
        """The current global model (slot 0 of the stacked params — all
        slots are identical after every aggregation)."""
        return jax.tree.map(lambda p: p[0], self.params_stk)

    def final_loss(self) -> float:
        """Mean participant loss of the last aggregation (inf before
        the first one) — the accuracy side of the egress trade."""
        return self.losses[-1]["mean_loss"] if self.losses \
            else float("inf")

    def measure_round_s(self, warmup: int = 1, iters: int = 2) -> float:
        """Wall-clock one jitted round (local training of every slot)
        on held-out batches, after `warmup` compile/warm runs. State is
        not advanced."""
        batches = self.next_batches()
        for _ in range(max(warmup, 1)):
            jax.block_until_ready(self.local_round(batches))
        t0 = time.perf_counter()
        for _ in range(max(iters, 1)):
            jax.block_until_ready(self.local_round(batches))
        return (time.perf_counter() - t0) / max(iters, 1)


# ---------------------------------------------------------------------------
# Calibration: measured step time -> simulated ClientProfile epoch times,
# cross-checked against a roofline estimate.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StepCalibration:
    """One calibration measurement and its roofline cross-check."""
    measured_round_s: float      # wall-clock of one jitted round
    roofline_round_s: float      # estimate from HLO counts + peaks
    flops: float                 # HLO FLOPs the estimate charges
    bytes_accessed: float        # HLO HBM-proxy bytes it charges
    peak_flops: float            # FLOP/s: published chip / measured host
    peak_bw: float               # bytes/s: published chip / measured host

    @property
    def ratio(self) -> float:
        """measured / roofline — the cross-check the tests bound."""
        return self.measured_round_s / self.roofline_round_s

    def mean_epoch_s(self, time_scale: float = 1.0) -> float:
        """The simulated epoch duration this measurement anchors:
        one local-training round scaled by `time_scale` (the paper's
        scaled-duration simulation knob)."""
        return self.measured_round_s * time_scale


def _measure_host_peaks(dim: int = 256, iters: int = 8):
    """Measured host peaks for the roofline cross-check: achievable
    matmul FLOP/s and memory copy bandwidth at a scale comparable to
    the smoke model's ops, so the estimate carries the same dispatch
    overhead the measured step pays."""
    a = jnp.asarray(np.random.RandomState(0).randn(dim, dim), jnp.float32)
    f = jax.jit(lambda x: x @ x)
    jax.block_until_ready(f(a))
    t0 = time.perf_counter()
    for _ in range(iters):
        a = f(a)
    jax.block_until_ready(a)
    flops_s = iters * 2.0 * dim ** 3 / (time.perf_counter() - t0)

    big = jnp.asarray(np.zeros((1 << 22,), np.float32))  # 16 MB
    g = jax.jit(lambda x: x + 1.0)
    jax.block_until_ready(g(big))
    t0 = time.perf_counter()
    out = big
    for _ in range(iters):
        out = g(out)
    jax.block_until_ready(out)
    bw = iters * 2.0 * big.size * 4 / (time.perf_counter() - t0)
    return flops_s, bw


def calibrate(hooks: MeshTrainerHooks, warmup: int = 1,
              iters: int = 2) -> StepCalibration:
    """Measure one round's wall-clock and cross-check it against the
    roofline estimate built from the compiled per-device module's HLO
    FLOP/byte counts.

    On accelerator chips the clients run in parallel, one per chip, so
    the per-chip counts are charged once against the published peaks
    of the chip's `device_kind` (an unknown kind raises), with the
    classic overlapping bound (`combine="max"`). CPU host devices share
    one physical CPU, so there the counts scale by the client count and
    meet measured host peaks serially (`combine="sum"`)."""
    from repro.launch import hlo_analysis as HA
    from repro.launch.roofline import chip_peaks, estimate_step_time

    measured = hooks.measure_round_s(warmup=warmup, iters=iters)
    compiled = hooks._local_fn.lower(
        hooks.params_stk, hooks.mu_stk, hooks.next_batches()).compile()
    hc = HA.analyze_hlo_text(compiled.as_text())
    device = hooks.mesh.devices.flat[0]
    if device.platform == "cpu":
        n = len(hooks.clients)
        flops, nbytes = hc.flops * n, hc.hbm_bytes * n
        peak_flops, bw = _measure_host_peaks()
        combine = "sum"
    else:
        flops, nbytes = hc.flops, hc.hbm_bytes
        peaks = chip_peaks(device.device_kind)
        peak_flops, bw = peaks.bf16_flops, peaks.hbm_bw
        combine = "max"
    roofline = estimate_step_time(flops, nbytes, peak_flops=peak_flops,
                                  hbm_bw=bw, combine=combine)
    return StepCalibration(measured_round_s=measured,
                           roofline_round_s=roofline, flops=flops,
                           bytes_accessed=nbytes, peak_flops=peak_flops,
                           peak_bw=bw)


def calibrated_profiles(profiles: Sequence[ClientProfile],
                        cal: StepCalibration,
                        time_scale: float = 1.0) -> List[ClientProfile]:
    """Rewrite each profile's `mean_epoch_s` from the measurement —
    simulated durations anchored to real compute instead of config
    guesses. Relative client speed (each profile's epoch time vs the
    cohort mean) is preserved so heterogeneity survives calibration."""
    base = float(np.mean([p.mean_epoch_s for p in profiles]))
    anchor = cal.mean_epoch_s(time_scale)
    return [dataclasses.replace(
        p, mean_epoch_s=anchor * (p.mean_epoch_s / base if base > 0
                                  else 1.0))
            for p in profiles]
