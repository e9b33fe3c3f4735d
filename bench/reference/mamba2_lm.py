"""Plain reference of an attention-free Mamba-2 LM (arXiv:2405.21060):
pre-norm RMSNorm, one projection each for z, x, B, C and dt, a causal
depthwise convolution with bias and SiLU over (x, B, C), dt =
softplus(dt + dt_bias), A = -exp(A_log), the SSD recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t,    y_t = C_t h_t + D x_t,

gated RMSNorm of y * silu(z), output projection, tied head, mean token
cross-entropy.

The recurrence is computed in its dual, quadratic form (the paper's
masked-attention view, §3): y = (L o C B^T) (dt x) with L_ij =
exp(sum_{k=j+1..i} dt_k A) for i >= j, a few heads at a time. It shares
nothing with the chunked scan the program runs. Matmuls go through `mm`
(reference fp32, or the control's lower precision). Each layer is
rematerialised so that the backward pass fits on one chip.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from harness.flops import ssd_fwd_flops, train_step_flops
from reference.common import Spec, cross_entropy, matmul_params, rms_norm

HEAD_BLOCK = 8      # heads per block of the quadratic form


def _dims(model):
    s = model["ssm"]
    d_in = s["expand"] * model["d_model"]
    nh = d_in // s["head_dim"]
    gn = s["n_groups"] * s["d_state"]
    return s, d_in, nh, gn, d_in + 2 * gn


def param_specs(model):
    d, v = model["d_model"], model["vocab_size"]
    s, d_in, nh, gn, conv_dim = _dims(model)
    n = model["num_layers"]
    pd = model["param_dtype"]
    w = lambda *shape: Spec((n,) + shape, pd, "normal",
                            1.0 / math.sqrt(shape[0]))
    f32 = "float32"
    mix = {
        "wz": w(d, d_in), "wx": w(d, d_in), "wB": w(d, gn), "wC": w(d, gn),
        "wdt": w(d, nh),
        "conv_w": w(s["conv_width"], conv_dim),
        "conv_b": Spec((n, conv_dim), pd, "zeros"),
        "A_log": Spec((n, nh), f32, "a_log", lo=s["a_init_range"][0],
                      hi=s["a_init_range"][1]),
        "dt_bias": Spec((n, nh), f32, "dt_bias", lo=s["dt_min"],
                        hi=s["dt_max"]),
        "D": Spec((n, nh), f32, "ones"),
        "norm": Spec((n, d_in), f32, "ones"),
        "wo": w(d_in, d),
    }
    specs = {
        "embed": {"table": Spec((v, d), pd, "normal", 0.02)},
        "final_norm": {"scale": Spec((d,), f32, "ones")},
        "blocks": {"00_mamba2": {"norm1": {"scale": Spec((n, d), f32,
                                                         "ones")},
                                 "mix": mix}},
    }
    if not model["tie_embeddings"]:
        specs["lm_head"] = {"table": Spec((d, v), pd, "normal",
                                          1.0 / math.sqrt(d))}
    return specs


def step_flops(model, batch: int, seq: int) -> float:
    """Model FLOPs of one training step (harness/flops.py's rules): 6 N
    per token over the matmul parameters of `param_specs`, plus three
    times every layer's SSD chunked scan forward."""
    s, _, nh, _, _ = _dims(model)
    mix = model["num_layers"] * ssd_fwd_flops(
        batch, seq, chunk=s["chunk_size"], d_state=s["d_state"],
        n_groups=s["n_groups"], num_heads=nh, head_dim=s["head_dim"])
    return train_step_flops(
        matmul_params(param_specs(model), model["tie_embeddings"]),
        batch * seq, mix)


def _conv(u, w, b):
    """Causal depthwise convolution: out_t = sum_i w_i u_{t-k+1+i} + b."""
    k = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(pad[:, i:i + u.shape[1]] * w[i].astype(jnp.float32)
               for i in range(k)) + b.astype(jnp.float32)


def _ssd(mm, xbar, la, B, C, heads_per_group):
    """y_i = sum_{j<=i} (C_i . B_j) exp(cs_i - cs_j) xbar_j per head.

    xbar (b,s,h,p), la (b,s,h), B and C (b,s,g,n)."""
    b, s, nh, p = xbar.shape
    cs = jnp.cumsum(la, axis=1)                          # (b,s,h)
    causal = jnp.tril(jnp.ones((s, s), bool))
    group = jnp.arange(nh) // heads_per_group
    cb = mm("bign,bjgn->bgij", C, B)                     # (b,g,s,s)
    nb = max(nh // HEAD_BLOCK, 1)
    hb = nh // nb

    def block(i):
        sl = lambda x, ax: jax.lax.dynamic_slice_in_dim(x, i * hb, hb, ax)
        c = sl(cs, 2).transpose(0, 2, 1)                 # (b,hb,s)
        seg = jnp.where(causal, c[..., :, None] - c[..., None, :], -jnp.inf)
        m = jnp.take(cb, sl(group, 0), axis=1) * jnp.exp(seg)
        return mm("bhij,bjhp->bihp", m, sl(xbar, 2))     # (b,s,hb,p)

    # each block is recomputed in the backward pass, not stored
    ys = jax.lax.map(jax.checkpoint(block), jnp.arange(nb))  # (nb,b,s,hb,p)
    return ys.transpose(1, 2, 0, 3, 4).reshape(b, s, nh, p)


def _mixer(model, mm, x, p):
    s, d_in, nh, gn, _ = _dims(model)
    b, t, _ = x.shape
    g, n = s["n_groups"], s["d_state"]
    z = mm("bsd,di->bsi", x, p["wz"])
    xbc = jnp.concatenate([mm("bsd,di->bsi", x, p["wx"]),
                           mm("bsd,dn->bsn", x, p["wB"]),
                           mm("bsd,dn->bsn", x, p["wC"])], axis=-1)
    xbc = jax.nn.silu(_conv(xbc, p["conv_w"], p["conv_b"]))
    xi = xbc[..., :d_in].reshape(b, t, nh, s["head_dim"])
    B = xbc[..., d_in:d_in + gn].reshape(b, t, g, n)
    C = xbc[..., d_in + gn:].reshape(b, t, g, n)
    dt = jax.nn.softplus(mm("bsd,dh->bsh", x, p["wdt"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = _ssd(mm, xi * dt[..., None], dt * A, B, C, nh // g)
    y = (y + xi * p["D"][:, None]).reshape(b, t, d_in)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], model["norm_eps"])
    return mm("bsi,id->bsd", y, p["wo"])


def _layer(model, mm, x, p):
    return x + _mixer(model, mm,
                      rms_norm(x, p["norm1"]["scale"], model["norm_eps"]),
                      p["mix"])


def loss(model, mm, params, tokens, labels):
    x = params["embed"]["table"].astype(jnp.float32)[tokens]
    layer = jax.checkpoint(lambda x, p: (_layer(model, mm, x, p), None))
    x, _ = jax.lax.scan(layer, x, params["blocks"]["00_mamba2"])
    x = rms_norm(x, params["final_norm"]["scale"], model["norm_eps"])
    head = (params["embed"]["table"].T if model["tie_embeddings"]
            else params["lm_head"]["table"])
    return cross_entropy(mm("bsd,dv->bsv", x, head), labels)
