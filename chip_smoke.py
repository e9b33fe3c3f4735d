"""Smoke run of the real-training FL round on TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: the cross-chip barrier

One chip: phi3-mini-3.8b at its published widths, cut to one chip's
share (`repro.configs.phi3_mini_3p8b.ONE_CHIP`: depth, batch and
sequence), trains as one FL client through `FLCloudRunner` (policy
`fedcostaware`) and `MeshTrainerHooks` for 3 rounds of 2 local steps,
with the Pallas flash-attention kernel in the model and the Pallas int8
codec on the update. Phases, each of which exits non-zero on failure:

  kernels  flash_attention, ssd and rglru at published widths against
           their references
  round    the FL round: compile seconds, steady round seconds, peak
           bytes, per-round losses (all finite); then `calibrate`
  codec    the Pallas int8 (grad_quant) payload of one real delta
           against the reference codec, within 1 LSB
  parity   the kernels-on first-round loss against a kernels-off rerun
           on the same seed, within a bf16 tolerance

Four chips (`--four-chips`) runs only the cross-silo round: 4 clients,
one per chip, fp32 and int8 arms, one round each. The aggregated global
model is checked against a plain fp32 jax.numpy FedAvg of the same
per-client deltas on the host's CPU device, and every client stack must
hold exactly one slot per chip.

Without a TPU as JAX's first device it exits non-zero and prints no
result. Its last line is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
ROUNDS = 3
FOUR_CHIPS = 4
# bf16 carries 8 significant bits: two units in the last place of the
# loss, relative, bound kernels-on vs kernels-off
PARITY_RTOL = 2.0 ** -7
KERNEL_RTOL = {"flash_attention": 2e-2, "ssd": 1e-3, "rglru": 1e-3}


class SmokeFailure(Exception):
    """A phase's check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Phase: kernels at published widths against their references.
# ---------------------------------------------------------------------------
def kernel_phase(seq: int = 512) -> None:
    """flash_attention at phi3-mini width (32 heads x 96), ssd at
    mamba2-1.3b width (64 heads x 64, d_state 128, chunk 256), rglru at
    recurrentgemma-2b width (2560, chunk 128, block 128), each on a
    `seq`-token sequence; grad_quant runs in the codec phase."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import reference_attention
    from repro.kernels.rglru.ops import rglru_scan
    from repro.kernels.rglru.ref import rglru_scan_ref
    from repro.kernels.ssd.ops import ssd
    from repro.kernels.ssd.ref import ssd_reference

    rng = np.random.RandomState(SEED)

    def rel_err(out, ref):
        out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
        check(bool(np.all(np.isfinite(out))), "non-finite kernel output")
        return float(np.max(np.abs(out - ref)) / (np.max(np.abs(ref))
                                                  + 1e-30))

    errs = {}
    with jax.default_matmul_precision("highest"):
        B, N, H = 1, 32, 96
        q, k, v = (jnp.asarray(rng.randn(B, seq, N, H), jnp.bfloat16)
                   for _ in range(3))
        out = flash_attention(q, k, v)
        fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * N, seq, H)
        ref = reference_attention(fold(q), fold(k), fold(v))
        errs["flash_attention"] = rel_err(
            out, ref.reshape(B, N, seq, H).transpose(0, 2, 1, 3))

        h, p, n = 64, 64, 128
        xbar = jnp.asarray(rng.randn(1, seq, h, p) * 0.5, jnp.float32)
        log_a = jnp.asarray(-np.abs(rng.randn(1, seq, h)) * 0.1,
                            jnp.float32)
        Bm, Cm = (jnp.asarray(rng.randn(1, seq, h, n) * 0.3, jnp.float32)
                  for _ in range(2))
        chunk = min(256, seq)
        y, _ = ssd(xbar, log_a, Bm, Cm, chunk=chunk)
        y_ref, _ = ssd_reference(xbar, log_a, Bm, Cm, chunk=chunk)
        errs["ssd"] = rel_err(y, y_ref)

        w = 2560
        la = jnp.asarray(-np.abs(rng.randn(1, seq, w)) * 0.2, jnp.float32)
        b = jnp.asarray(rng.randn(1, seq, w) * 0.5, jnp.float32)
        hk = rglru_scan(la, b, chunk=min(128, seq), block_w=128)
        errs["rglru"] = rel_err(hk, rglru_scan_ref(la, b))

    for name, e in errs.items():
        log(f"kernel {name}: max rel err {e:.3e} "
            f"(bound {KERNEL_RTOL[name]:g})")
        check(e <= KERNEL_RTOL[name], f"{name} kernel disagrees with "
              f"its reference: {e:.3e} > {KERNEL_RTOL[name]:g}")


# ---------------------------------------------------------------------------
# Phases on one chip: the FL round, the codec, kernels-on/off parity.
# ---------------------------------------------------------------------------
def make_hooks(cfg, n_clients, *, batch, seq, local_steps, quantize,
               use_pallas):
    from repro.fl.training import MeshTrainerHooks
    names = [f"client_{i}" for i in range(n_clients)]
    return MeshTrainerHooks(names, cfg=cfg, local_steps=local_steps,
                            batch=batch, seq=seq, quantize=quantize,
                            use_pallas=use_pallas, seed=SEED)


def round_phase(cfg, *, batch, seq, local_steps):
    """3 FL rounds through FLCloudRunner on one client slot, kernels on.
    Returns the first-round loss; runs the codec phase on the delta the
    rounds made to the largest leaf."""
    import jax
    import numpy as np
    from repro.common import tracing
    from repro.common.config import CloudConfig, ClientProfile, FLRunConfig
    from repro.fl.runner import FLCloudRunner
    from repro.fl.training import calibrate

    run_cfg = FLRunConfig(
        dataset="chip-smoke", n_epochs=ROUNDS, policy="fedcostaware",
        seed=SEED, quantize_updates=True,
        clients=(ClientProfile("client_0", mean_epoch_s=600.0,
                               jitter=0.0),))
    rec = tracing.start()
    try:
        hooks = make_hooks(cfg, 1, batch=batch, seq=seq,
                           local_steps=local_steps, quantize=True,
                           use_pallas=True)
        leaves = jax.tree.leaves(hooks.global_params())
        big = int(np.argmax([x.size for x in leaves]))
        before = leaves[big]
        del leaves
        res = FLCloudRunner(run_cfg,
                            cloud_cfg=CloudConfig(spot_rate_sigma=0.0),
                            hooks=hooks).run()
    finally:
        tracing.stop()
    # a round ends when its losses reach the host
    ends = [s.end_ns / 1e9 for s in rec.named("fl.aggregate")]
    round_s = np.diff(ends)
    losses = [r["mean_loss"] for r in hooks.losses]
    dev = jax.devices()[0]
    log(f"round: rounds_completed {res.rounds_completed}, "
        f"total_cost ${res.total_cost:.4f}, comm_cost ${res.comm_cost:.6f}")
    log(f"round: compile_s {rec.counters.get('compile_s', 0):.1f} "
        f"({rec.counters.get('compiles', 0)} compiles or cache loads; "
        f"persistent-cache hits {rec.counters.get('cache_hits', 0)})")
    log(f"round: round_s {[round(float(t), 4) for t in round_s]}; steady "
        f"round_s {float(np.median(round_s)):.4f} (median of rounds "
        f"2..{len(ends)}, end to end of the fl.aggregate spans)")
    log(f"round: per-round losses {losses}")
    check(res.rounds_completed == ROUNDS and len(losses) == ROUNDS,
          f"expected {ROUNDS} aggregated rounds, got {len(losses)}")
    check(all(np.isfinite(losses)), f"non-finite round loss: {losses}")

    cal = calibrate(hooks)
    log(f"round: calibrate measured_round_s {cal.measured_round_s:.4f} "
        f"roofline_round_s {cal.roofline_round_s:.4f} ratio "
        f"{cal.ratio:.2f} (peaks {cal.peak_flops:.3g} FLOP/s, "
        f"{cal.peak_bw:.3g} B/s)")
    check(np.isfinite(cal.ratio) and cal.ratio > 0, "calibration failed")
    stats = dev.memory_stats() or {}
    log(f"round: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")

    after = jax.tree.leaves(hooks.global_params())[big]
    codec_phase(after.astype("float32") - before.astype("float32"))
    return losses[0]


def codec_phase(delta) -> None:
    """The Pallas int8 payload of `delta` against the reference codec:
    codes within 1 LSB, equal scales, and exactly the bytes billed."""
    import jax.numpy as jnp
    import numpy as np
    from repro.comms.payload import quantized_leaf_bytes
    from repro.kernels.grad_quant import ops as gq

    check(float(jnp.max(jnp.abs(delta))) > 0, "the rounds left the leaf "
          "unchanged: no real delta to encode")
    q_p, s_p = gq.quantize(delta, use_pallas=True)
    q_r, s_r = gq.quantize(delta, use_pallas=False)
    lsb = int(jnp.max(jnp.abs(q_p.astype(jnp.int32)
                              - q_r.astype(jnp.int32))))
    scale_err = float(jnp.max(jnp.abs(s_p - s_r) / s_r))
    wire = q_p.size * q_p.dtype.itemsize + s_p.size * s_p.dtype.itemsize
    log(f"codec: delta {tuple(delta.shape)}, {q_p.shape[0]} blocks, max "
        f"code diff {lsb} LSB, max scale rel diff {scale_err:.2e}, "
        f"{wire} wire bytes")
    check(lsb <= 1, f"int8 codes differ by {lsb} LSB")
    check(scale_err <= 1e-6, f"scales differ by {scale_err:.2e}")
    check(wire == quantized_leaf_bytes(delta.size),
          "payload bytes differ from what comms/payload.py bills")


def parity_phase(cfg, first_loss, *, batch, seq, local_steps) -> None:
    """Kernels-off rerun of the first round's local training."""
    import numpy as np
    hooks = make_hooks(dataclasses.replace(cfg, use_pallas=False), 1,
                       batch=batch, seq=seq, local_steps=local_steps,
                       quantize=False, use_pallas=False)
    _, _, losses = hooks.local_round(hooks.next_batches())
    off = float(np.mean(np.asarray(losses)[0]))
    diff = abs(first_loss - off)
    log(f"parity: first-round loss kernels on {first_loss:.6f}, off "
        f"{off:.6f}, |diff| {diff:.3e} (bound {PARITY_RTOL:g} x |off|)")
    check(diff <= PARITY_RTOL * abs(off), "kernels-on first-round loss "
          "disagrees with the kernels-off rerun")


# ---------------------------------------------------------------------------
# Four chips: one client per chip, the FedAvg barrier across chips.
# ---------------------------------------------------------------------------
def one_slot_per_device(tree, n) -> bool:
    """Every leaf is split over `n` devices, one client slot on each."""
    import jax
    for leaf in jax.tree.leaves(tree):
        shards = leaf.addressable_shards
        if (len({s.device for s in shards}) != n
                or any(s.data.shape[0] != 1 for s in shards)):
            return False
    return True


def four_chip_phase(cfg, *, batch, seq, local_steps) -> None:
    """One round on FOUR_CHIPS clients, fp32 then int8, against a plain
    fp32 FedAvg of the same deltas on the host's CPU device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cpu = jax.devices("cpu")[0]
    n_clients = FOUR_CHIPS
    w = np.arange(1, n_clients + 1, dtype=np.float32)
    for quantize in (False, True):
        arm = "int8" if quantize else "fp32"
        hooks = make_hooks(cfg, n_clients, batch=batch, seq=seq,
                           local_steps=local_steps, quantize=quantize,
                           use_pallas=True)
        check(one_slot_per_device((hooks.params_stk, hooks.mu_stk),
                                  n_clients),
              f"{arm}: a device holds other than one client slot")
        new_p, new_mu, losses = hooks.local_round(hooks.next_batches())
        losses = np.asarray(losses)
        check(bool(np.all(np.isfinite(losses))),
              f"{arm}: non-finite losses {losses}")
        check(one_slot_per_device((new_p, new_mu), n_clients),
              f"{arm}: round outputs not one slot per device")

        # plain fp32 FedAvg of the same per-client deltas, on one device
        wn = w / w.sum()
        refs, amaxes = [], []
        for n_leaf, o_leaf in zip(jax.tree.leaves(new_p),
                                  jax.tree.leaves(hooks.params_stk)):
            n32 = jax.device_put(n_leaf, cpu).astype(jnp.float32)
            o32 = jax.device_put(o_leaf, cpu).astype(jnp.float32)
            d = n32 - o32
            avg = sum(wn[c] * d[c] for c in range(n_clients))
            refs.append((o32[0] + avg).astype(o_leaf.dtype))
            amaxes.append(float(jnp.max(jnp.abs(d))))
        check(max(amaxes) > 0, f"{arm}: local training moved nothing")

        params, mu = hooks.fedavg(new_p, new_mu, w)
        check(one_slot_per_device((params, mu), n_clients),
              f"{arm}: aggregated stacks not one slot per device")
        worst = 0.0
        for got, ref, amax in zip(jax.tree.leaves(params), refs, amaxes):
            got = np.asarray(jax.device_put(got, cpu), np.float32)
            ref = np.asarray(ref, np.float32)
            # one bf16 unit in the last place, plus the int8 bound
            tol = np.abs(ref) * 2.0 ** -7 + (amax / 127 if quantize else 0)
            worst = max(worst, float(np.max(np.abs(got - ref[None])
                                            / (tol + 1e-30))))
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()[:n_clients]]
        log(f"four-chip {arm}: losses {losses.mean(axis=1).tolist()}, "
            f"max |agg - ref| / tol {worst:.3f}, per-chip "
            f"peak_bytes_in_use {peaks}")
        check(worst <= 1.0, f"{arm}: aggregated model disagrees with the "
              "fp32 FedAvg reference")
        del hooks, new_p, new_mu, params, mu, refs
        gc.collect()


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-client, one-per-chip round")
    args = ap.parse_args(argv)

    from repro.common.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    from repro.configs.phi3_mini_3p8b import (ONE_CHIP, ONE_CHIP_BATCH,
                                              ONE_CHIP_LOCAL_STEPS,
                                              ONE_CHIP_SEQ)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"error: JAX's first device is {dev.platform} "
              f"({dev.device_kind}), not a TPU", file=sys.stderr)
        return 1
    n_need = FOUR_CHIPS if args.four_chips else 1
    if len(jax.devices()) < n_need:
        print(f"error: needs {n_need} chips, found {len(jax.devices())}",
              file=sys.stderr)
        return 1
    log(f"device_kind {dev.device_kind!r}, {len(jax.devices())} chip(s); "
        f"compile cache {cache}")
    log(f"config {ONE_CHIP.name}: {ONE_CHIP.num_layers} layers, d_model "
        f"{ONE_CHIP.d_model}, vocab {ONE_CHIP.vocab_size}, batch "
        f"{ONE_CHIP_BATCH} x seq {ONE_CHIP_SEQ}, {ONE_CHIP_LOCAL_STEPS} "
        f"local steps")
    sizes = dict(batch=ONE_CHIP_BATCH, seq=ONE_CHIP_SEQ,
                 local_steps=ONE_CHIP_LOCAL_STEPS)
    try:
        if args.four_chips:
            four_chip_phase(ONE_CHIP, **sizes)
        else:
            kernel_phase()
            first = round_phase(ONE_CHIP, **sizes)
            gc.collect()
            parity_phase(ONE_CHIP, first, **sizes)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
