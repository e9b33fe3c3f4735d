"""Plain reference of a dense decoder LM: pre-norm RMSNorm, multi-head
attention with rotary positions (rotate-half, as in Llama and Phi-3),
causal softmax, SwiGLU MLP, untied or tied output head, mean token
cross-entropy. Phi-3 (arXiv:2404.14219) is this architecture.

Straightforward jax.numpy in fp32; matmuls go through `mm`, so that the
same code runs at full fp32 (the reference) or a lower precision (the
control). Each layer is rematerialised so that the backward pass of a
full-width model fits on one chip after the program has been freed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from harness.flops import causal_attention_fwd_flops, train_step_flops
from reference.common import Spec, cross_entropy, matmul_params, rms_norm


def param_specs(model):
    d, v = model["d_model"], model["vocab_size"]
    nq, nk = model["num_heads"], model["num_kv_heads"]
    h = model.get("head_dim") or d // nq
    f = model["d_ff"]
    n = model["num_layers"]
    pd = model["param_dtype"]
    w = lambda *shape: Spec((n,) + shape, pd, "normal",
                            1.0 / math.sqrt(shape[0]))
    ones = Spec((n, d), "float32", "ones")
    layer = {
        "norm1": {"scale": ones},
        "mix": {"wq": w(d, nq, h), "wk": w(d, nk, h), "wv": w(d, nk, h),
                "wo": Spec((n, nq, h, d), pd, "normal",
                           1.0 / math.sqrt(nq * h))},
        "norm2": {"scale": ones},
        "mlp": {"wi_gate": w(d, f), "wi_up": w(d, f), "wo": w(f, d)},
    }
    specs = {
        "embed": {"table": Spec((v, d), pd, "normal", 0.02)},
        "final_norm": {"scale": Spec((d,), "float32", "ones")},
        "blocks": {"00_attn": layer},
    }
    if not model["tie_embeddings"]:
        specs["lm_head"] = {"table": Spec((d, v), pd, "normal",
                                          1.0 / math.sqrt(d))}
    return specs


def step_flops(model, batch: int, seq: int) -> float:
    """Model FLOPs of one training step (harness/flops.py's rules): 6 N
    per token over the matmul parameters of `param_specs`, plus three
    times every layer's causal attention forward."""
    nq = model["num_heads"]
    h = model.get("head_dim") or model["d_model"] // nq
    mix = model["num_layers"] * causal_attention_fwd_flops(batch, seq, nq, h)
    return train_step_flops(
        matmul_params(param_specs(model), model["tie_embeddings"]),
        batch * seq, mix)


def _rope(x, theta):
    """x: (b, s, n, h), rotated by position with the two halves paired."""
    s, h = x.shape[1], x.shape[-1]
    half = h // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(model, mm, x, p):
    eps = model["norm_eps"]
    nq, nk = model["num_heads"], model["num_kv_heads"]
    h = rms_norm(x, p["norm1"]["scale"], eps)
    a = p["mix"]
    q = _rope(mm("bsd,dnh->bsnh", h, a["wq"]), model["rope_theta"])
    k = _rope(mm("bsd,dnh->bsnh", h, a["wk"]), model["rope_theta"])
    v = mm("bsd,dnh->bsnh", h, a["wv"])
    if nq != nk:
        k = jnp.repeat(k, nq // nk, axis=2)
        v = jnp.repeat(v, nq // nk, axis=2)
    s = x.shape[1]
    scores = mm("bqnh,bknh->bnqk", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    o = mm("bnqk,bknh->bqnh", jax.nn.softmax(scores, axis=-1), v)
    x = x + mm("bsnh,nhd->bsd", o, a["wo"])
    h = rms_norm(x, p["norm2"]["scale"], eps)
    m = p["mlp"]
    g = jax.nn.silu(mm("bsd,df->bsf", h, m["wi_gate"]))
    x = x + mm("bsf,fd->bsd", g * mm("bsd,df->bsf", h, m["wi_up"]),
               m["wo"])
    return x


def loss(model, mm, params, tokens, labels):
    """Mean next-token cross-entropy of `tokens` (b, s) against
    `labels` (b, s)."""
    x = params["embed"]["table"].astype(jnp.float32)[tokens]
    layer = jax.checkpoint(lambda x, p: (_layer(model, mm, x, p), None))
    x, _ = jax.lax.scan(layer, x, params["blocks"]["00_attn"])
    x = rms_norm(x, params["final_norm"]["scale"], model["norm_eps"])
    head = (params["embed"]["table"].T if model["tie_embeddings"]
            else params["lm_head"]["table"])
    return cross_entropy(mm("bsd,dv->bsv", x, head), labels)
