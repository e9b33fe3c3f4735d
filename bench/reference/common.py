"""Plain pieces shared by the references: parameter specs, weights made
from the seed, matmul precision policies, norms, and the FL round.

Nothing here imports the program. Parameter trees use the program's
key names so that one set of weights, made here from the seed, can be
handed to both; the benchmark checks that the two trees agree in every
name, shape and dtype before a run.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Parameter specs and weights from the seed.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Spec:
    """One parameter: shape, stored dtype, and how it is initialised.

    init: "normal" (std given), "ones", "zeros", "a_log" (log of
    U(lo, hi)), "dt_bias" (inverse softplus of a log-uniform dt in
    [lo, hi])."""
    shape: tuple
    dtype: str
    init: str = "normal"
    std: float = 0.0
    lo: float = 0.0
    hi: float = 0.0


def is_spec(x) -> bool:
    return isinstance(x, Spec)


def seed_halves(seed: int):
    """The low and high 32 bits of a seed of up to 64 bits."""
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def seed_key(lo, hi):
    """A PRNG key from both halves of the seed (traced or not)."""
    return jax.random.fold_in(jax.random.key(lo), hi)


def _make(spec: Spec, key):
    f32 = jnp.float32
    if spec.init == "normal":
        x = jax.random.normal(key, spec.shape, f32) * spec.std
    elif spec.init == "ones":
        x = jnp.ones(spec.shape, f32)
    elif spec.init == "zeros":
        x = jnp.zeros(spec.shape, f32)
    elif spec.init == "a_log":
        x = jnp.log(jax.random.uniform(key, spec.shape, f32, spec.lo,
                                       spec.hi))
    elif spec.init == "dt_bias":
        u = jax.random.uniform(key, spec.shape, f32)
        dt = jnp.exp(u * (math.log(spec.hi) - math.log(spec.lo))
                     + math.log(spec.lo))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown init {spec.init!r}")
    return x.astype(spec.dtype)


def make_params(specs, lo, hi, copies: int = 0):
    """The weights of the seed whose halves are `lo` and `hi` (see
    `seed_halves`; pass them as arguments of a jitted call, so that one
    program serves every seed). With `copies` > 0 every leaf gets a
    leading dim of that many identical copies (one per FL client slot)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(seed_key(lo, hi), len(leaves))
    vals = []
    for i, s in enumerate(leaves):
        x = _make(s, keys[i])
        if copies:
            x = jnp.broadcast_to(x[None], (copies,) + x.shape)
        vals.append(x)
    return jax.tree.unflatten(treedef, vals)


def spec_size(specs) -> int:
    """Elements of every parameter in `specs`."""
    return sum(math.prod(s.shape)
               for s in jax.tree.leaves(specs, is_leaf=is_spec))


def matmul_params(specs, tie_embeddings: bool) -> int:
    """Parameters that multiply activations, from the specs: all but
    the embedding table where the head is separate (its lookup is free);
    all of them where the head is the tied table. Norm scales count, as
    6 N counts them."""
    lookup = 0 if tie_embeddings else spec_size(specs["embed"])
    return spec_size(specs) - lookup


def abstract(specs, copies: int = 0):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            ((copies,) if copies else ()) + s.shape, jnp.dtype(s.dtype)),
        specs, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# Matmul precision: the reference, and the control one step below the
# precision the configuration states.
# ---------------------------------------------------------------------------
def mm_fp32(spec: str, a, b):
    """fp32 operands at full fp32 precision."""
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _fp8(x):
    """x rounded to per-tensor scaled float8_e4m3fn, the scale mapping
    |x|'s max to the format's largest finite value (448); gradients pass
    straight through the rounding."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm_fp8(spec: str, a, b):
    """Both operands rounded to scaled fp8 (e4m3), products accumulated
    in fp32: the precision a step below bfloat16."""
    return jnp.einsum(spec, _fp8(a), _fp8(b),
                      precision=jax.lax.Precision.HIGHEST)


PRECISIONS: Dict[str, Callable] = {"fp32": mm_fp32, "fp8": mm_fp8}


# ---------------------------------------------------------------------------
# Layers every reference uses.
# ---------------------------------------------------------------------------
def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(jnp.float32)


def cross_entropy(logits, labels):
    """Mean token cross-entropy in fp32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# The FL round: local SGD with momentum on each client, then FedAvg.
# ---------------------------------------------------------------------------
MOMENTUM = 0.9


def make_local_step(loss: Callable, lr: float):
    """One SGD-momentum step: m = 0.9 m + g; p = stored(p - lr m), with
    the update in fp32 and p rounded to its stored dtype."""
    def step(params, mu, tokens, labels):
        val, g = jax.value_and_grad(loss)(params, tokens, labels)
        mu = jax.tree.map(lambda m, gi: MOMENTUM * m
                          + gi.astype(jnp.float32), mu, g)
        params = jax.tree.map(
            lambda p, m: (p.astype(jnp.float32) - lr * m).astype(p.dtype),
            params, mu)
        return params, mu, val
    return jax.jit(step, donate_argnums=(0, 1))


def codec_roundtrip(d, block: int = 2048):
    """The int8 block codec: the flattened delta in blocks of `block`
    (zero-padded), each scaled by its max |value| / 127, rounded and
    clipped to [-127, 127], and multiplied back."""
    flat = d.reshape(-1)
    n = flat.shape[0]
    nb = max(-(-n // block), 1)
    rows = jnp.pad(flat, (0, nb * block - n)).reshape(nb, block)
    scale = jnp.maximum(jnp.max(jnp.abs(rows), axis=1, keepdims=True),
                        1e-12) / 127.0
    q = jnp.clip(jnp.round(rows / scale), -127, 127)
    return (q * scale).reshape(-1)[:n].reshape(d.shape)


@jax.jit
def _delta(new, old):
    return new.astype(jnp.float32) - old.astype(jnp.float32)


_codec = jax.jit(codec_roundtrip)


@jax.jit
def _apply(old, avg):
    return (old.astype(jnp.float32) + avg).astype(old.dtype)


def fedavg(old, news, weights, quantize: bool, device):
    """The global model after one FedAvg barrier: old + sum_c w_c d_c
    with w normalised to sum 1, d_c the fp32 delta of client c
    (optionally through the int8 codec), leaf by leaf on `device`."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    out = []
    old_leaves, treedef = jax.tree.flatten(old)
    new_leaves = [jax.tree.leaves(n) for n in news]
    for i, o in enumerate(old_leaves):
        o = jax.device_put(o, device)
        acc = jnp.zeros(o.shape, jnp.float32, device=device)
        for c, nl in enumerate(new_leaves):
            d = _delta(jax.device_put(nl[i], device), o)
            if quantize:
                d = _codec(d)
            acc = acc + jnp.float32(w[c]) * d
        out.append(_apply(o, acc))
    return jax.tree.unflatten(treedef, out)


def tree_to(tree: Any, device):
    return jax.tree.map(lambda x: jax.device_put(x, device), tree)
