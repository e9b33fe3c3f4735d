"""Plain reference of a Nemotron-H hybrid LM, as Nemotron 3 Nano 30B-A3B
(huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16) builds it:
single-mixer blocks x <- x + Mixer(RMSNorm(x)), the mixer of each block
given by the model's pattern, then a final RMSNorm, the untied head and
mean token cross-entropy. No projection has a bias.

  mamba2  z, x, B, C and dt projected from the normed input; a causal
          depthwise convolution with bias, then SiLU, over (x, B, C);
          dt = softplus(dt + dt_bias), A = -exp(A_log); the SSD
          recurrence per head, head h reading B and C of group
          h // (heads / groups), plus D x; RMSNorm of y * silu(z) over
          each group's share of the inner width, then the scale; the
          output projection
  attn    causal softmax(q k^T / sqrt(head_dim)) v, each key-value head
          shared by its group of query heads; no position embedding
  moe     s = sigmoid(x W_r) over all experts; the top k of s + b
          chosen, b a selection bias with no gradient; w_e = scaling *
          s_e / (sum of s over the k chosen); y = sum over the chosen
          experts held here of w_e W2_e relu(W1_e x)^2, plus the shared
          expert W2 relu(W1 x)^2 on every token; experts 0 .. held - 1
          are held here

Straightforward jax.numpy in fp32, every matmul through `mm`: the
recurrence in its quadratic dual form (mamba2_lm._ssd), attention as a
masked softmax over the whole square, and each held expert computed
densely on every token and weighted by w_e, 0 where the token did not
choose it: no dispatch. Each layer is rematerialised so that the
backward pass fits on one chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness.flops import (causal_attention_fwd_flops, ssd_fwd_flops,
                           train_step_flops)
from reference.common import Spec, cross_entropy, matmul_params, rms_norm
from reference.mamba2_lm import _conv, _ssd

F32 = "float32"


def _dims(model):
    s = model["ssm"]
    d_in = (s["num_heads"] * s["head_dim"] if s.get("num_heads")
            else s["expand"] * model["d_model"])
    gn = s["n_groups"] * s["d_state"]
    return s, d_in, d_in // s["head_dim"], gn


def _held(model):
    m = model["moe"]
    return m.get("held") or m["num_experts"]


def _layers(model):
    """(pattern of the scanned blocks and their count, tail pattern)."""
    pattern, n = list(model["pattern"]), model["num_layers"]
    return pattern, n // len(pattern), pattern[:n % len(pattern)]


def _mixer_specs(model, kind, lead):
    d, pd = model["d_model"], model["param_dtype"]
    w = lambda *shape: Spec(lead + shape, pd, "normal",
                            1.0 / math.sqrt(shape[0]))
    if kind == "mamba2":
        s, d_in, nh, gn = _dims(model)
        conv = d_in + 2 * gn
        return {
            "wz": w(d, d_in), "wx": w(d, d_in), "wB": w(d, gn),
            "wC": w(d, gn), "wdt": w(d, nh),
            "conv_w": w(s["conv_width"], conv),
            "conv_b": Spec(lead + (conv,), pd, "zeros"),
            "A_log": Spec(lead + (nh,), F32, "a_log",
                          lo=s["a_init_range"][0], hi=s["a_init_range"][1]),
            "dt_bias": Spec(lead + (nh,), F32, "dt_bias", lo=s["dt_min"],
                            hi=s["dt_max"]),
            "D": Spec(lead + (nh,), F32, "ones"),
            "norm": Spec(lead + (d_in,), F32, "ones"),
            "wo": w(d_in, d),
        }
    if kind == "attn":
        nq, nk, h = model["num_heads"], model["num_kv_heads"], \
            model["head_dim"]
        return {"wq": w(d, nq, h), "wk": w(d, nk, h), "wv": w(d, nk, h),
                "wo": Spec(lead + (nq, h, d), pd, "normal",
                           1.0 / math.sqrt(nq * h))}
    if kind == "moe":
        m = model["moe"]
        e, f, held = m["num_experts"], m["d_ff"], _held(model)
        std = lambda n: 1.0 / math.sqrt(n)
        out = {
            "router": Spec(lead + (d, e), F32, "normal", std(d)),
            # zero, as the published initialisation of the correction bias
            "router_bias": Spec(lead + (e,), F32, "zeros"),
            "wi": Spec(lead + (held, d, f), pd, "normal", std(d)),
            "wo": Spec(lead + (held, f, d), pd, "normal", std(f)),
        }
        if m.get("shared_d_ff"):
            fs = m["shared_d_ff"]
            out["shared"] = {"wi": w(d, fs), "wo": w(fs, d)}
        return out
    raise ValueError(f"no reference of layer kind {kind!r}")


def param_specs(model):
    """The program's parameter tree: scanned blocks of the pattern with a
    leading layer dim, then the tail's blocks unstacked."""
    d, v, pd = model["d_model"], model["vocab_size"], model["param_dtype"]
    pattern, n_super, tail = _layers(model)

    def block(kinds, lead):
        return {f"{i:02d}_{k}": {"norm1": {"scale": Spec(lead + (d,), F32,
                                                         "ones")},
                                 "mix": _mixer_specs(model, k, lead)}
                for i, k in enumerate(kinds)}

    specs = {
        "embed": {"table": Spec((v, d), pd, "normal", 0.02)},
        "final_norm": {"scale": Spec((d,), F32, "ones")},
        "lm_head": {"table": Spec((d, v), pd, "normal", 1.0 / math.sqrt(d))},
    }
    if n_super:
        specs["blocks"] = block(pattern, (n_super,))
    if tail:
        specs["tail"] = block(tail, ())
    return specs


# ---------------------------------------------------------------------------
# Work of a training step (harness/flops.py's rules).
# ---------------------------------------------------------------------------
def _kinds(model):
    pattern, n_super, tail = _layers(model)
    return pattern * n_super + tail


def _expert_params(model):
    """(routed, shared): parameters of the held experts' and of the
    shared expert's MLPs over all expert layers."""
    n = _kinds(model).count("moe")
    m = model["moe"]
    d = model["d_model"]
    return (n * _held(model) * 2 * d * m["d_ff"],
            n * 2 * d * m.get("shared_d_ff", 0))


def _per_token(model, routed):
    """Routed expert parameters a token meets: each token is sent to
    top_k of num_experts, so held experts see top_k x held / num_experts
    of the tokens."""
    m = model["moe"]
    return routed * m["top_k"] / m["num_experts"]


def step_flops(model, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: 6 N per token over the matmul
    parameters of `param_specs` (the embedding lookup left out), the
    routed experts at their expected share of the tokens, plus three
    times each Mamba-2 layer's SSD chunked scan and each attention
    layer's causal products, forward."""
    s, _, nh, _ = _dims(model)
    kinds = _kinds(model)
    mix = (kinds.count("mamba2") * ssd_fwd_flops(
        batch, seq, chunk=s["chunk_size"], d_state=s["d_state"],
        n_groups=s["n_groups"], num_heads=nh, head_dim=s["head_dim"])
        + kinds.count("attn") * causal_attention_fwd_flops(
            batch, seq, model["num_heads"], model["head_dim"]))
    routed, _ = _expert_params(model)
    n = matmul_params(param_specs(model), False)
    return train_step_flops(n - routed + _per_token(model, routed),
                            batch * seq, mix)


def expert_step_flops(model, batch: int, seq: int) -> float:
    """FLOPs of one training step's expert matmuls, by the same rule:
    the held routed experts at their share of the tokens and the shared
    expert at every token (the router left out)."""
    routed, shared = _expert_params(model)
    return train_step_flops(_per_token(model, routed) + shared,
                            batch * seq, 0.0)


# ---------------------------------------------------------------------------
# The loss.
# ---------------------------------------------------------------------------
def _mamba2(model, mm, x, p):
    s, d_in, nh, gn = _dims(model)
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
    # the gated norm, per group of d_in / g channels
    y = (y * jax.nn.silu(z)).reshape(b, t, g, d_in // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + model["norm_eps"])
    y = y.reshape(b, t, d_in) * p["norm"]
    return mm("bsi,id->bsd", y, p["wo"])


def _attention(model, mm, x, p):
    nq, nk, h = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    b, s, _ = x.shape
    q = mm("bsd,dnh->bsnh", x, p["wq"]).reshape(b, s, nk, nq // nk, h)
    k = mm("bsd,dkh->bskh", x, p["wk"])
    v = mm("bsd,dkh->bskh", x, p["wv"])
    scores = mm("bqkgh,btkh->bkgqt", q, k) / math.sqrt(h)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    o = mm("bkgqt,btkh->bqkgh", jax.nn.softmax(scores, axis=-1), v)
    return mm("bsnh,nhd->bsd", o.reshape(b, s, nq, h), p["wo"])


def _experts(model, mm, x, p):
    m = model["moe"]
    relu2 = lambda u: jnp.square(jax.nn.relu(u))
    s = jax.nn.sigmoid(mm("bsd,de->bse", x, p["router"]))
    _, chosen = jax.lax.top_k(s + p["router_bias"], m["top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = m["routed_scaling"] * w / jnp.sum(w, axis=-1, keepdims=True)
    # each expert's weight for each token, 0 where it was not chosen
    dense = jnp.sum(jax.nn.one_hot(chosen, m["num_experts"]) * w[..., None],
                    axis=-2)
    dense = dense[..., :_held(model)]
    y_e = mm("bsef,efd->bsed", relu2(mm("bsd,edf->bsef", x, p["wi"])),
             p["wo"])
    y = jnp.sum(dense[..., None] * y_e, axis=-2)
    if m.get("shared_d_ff"):
        sh = p["shared"]
        y = y + mm("bsf,fd->bsd", relu2(mm("bsd,df->bsf", x, sh["wi"])),
                   sh["wo"])
    return y


MIXERS = {"mamba2": _mamba2, "attn": _attention, "moe": _experts}


def _layer(model, mm, kind, x, p):
    h = rms_norm(x, p["norm1"]["scale"], model["norm_eps"])
    return x + MIXERS[kind](model, mm, h, p["mix"])


def _blocks(model, mm, kinds, x, p_blk):
    for i, kind in enumerate(kinds):
        layer = jax.checkpoint(functools.partial(_layer, model, mm, kind))
        x = layer(x, p_blk[f"{i:02d}_{kind}"])
    return x


def loss(model, mm, params, tokens, labels):
    """Mean next-token cross-entropy of `tokens` (b, s) against
    `labels` (b, s)."""
    pattern, n_super, tail = _layers(model)
    x = params["embed"]["table"].astype(jnp.float32)[tokens]
    if n_super:
        x, _ = jax.lax.scan(
            lambda x, p: (_blocks(model, mm, pattern, x, p), None), x,
            params["blocks"])
    if tail:
        x = _blocks(model, mm, tail, x, params["tail"])
    x = rms_norm(x, params["final_norm"]["scale"], model["norm_eps"])
    return cross_entropy(mm("bsd,dv->bsv", x, params["lm_head"]["table"]),
                         labels)
