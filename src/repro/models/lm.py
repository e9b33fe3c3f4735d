"""Unified decoder LM covering all assigned architecture families.

A model is ``num_layers`` layers tiled by ``cfg.pattern`` (e.g. dense =
("attn",), recurrentgemma = ("rglru","rglru","attn_local"), Nemotron-H
= ("mamba2","moe",...,"attn",...)); the repeated super-block is scanned
(`lax.scan`) with stacked params so HLO size is O(1) in depth, and each
of its layers optionally rematerialized. A layer is pre-norm + mixer,
plus an MLP sublayer where ``cfg.has_mlp`` (never when the pattern names
the standalone expert layer "moe").

Public API:
  param_schema / init_params / abstract_params / logical_axes
  forward(params, cfg, tokens, cond=None)           -> logits, aux
  loss_fn(params, cfg, batch)                       -> scalar loss
  init_cache / abstract_cache / cache_logical_axes
  decode_step(params, cfg, tokens, pos, cache)      -> logits, cache
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.common import config as C
from repro.models import layers as L
from repro.models import ssm as S
from repro.sharding.rules import ShardingCtx, INERT


# ---------------------------------------------------------------------------
# Schemas.
# ---------------------------------------------------------------------------
def _sublayer_schema(cfg, kind):
    sub = {"norm1": L.rms_norm_schema(cfg.d_model)}
    if kind in (C.ATTN, C.LOCAL_ATTN, C.CROSS_ATTN):
        sub["mix"] = L.attention_schema(cfg, cross=(kind == C.CROSS_ATTN))
    elif kind == C.MAMBA2:
        sub["mix"] = S.mamba2_schema(cfg)
    elif kind == C.RGLRU:
        sub["mix"] = S.rglru_schema(cfg)
    elif kind == C.MOE:
        sub["mix"] = L.moe_schema(cfg)
    if cfg.has_mlp:
        sub["norm2"] = L.rms_norm_schema(cfg.d_model)
        sub["mlp"] = L.moe_schema(cfg) if cfg.moe else L.mlp_schema(cfg)
    return sub


def _block_schema(cfg, pattern):
    return {f"{i:02d}_{k}": _sublayer_schema(cfg, k)
            for i, k in enumerate(pattern)}


def param_schema(cfg):
    d, v = cfg.d_model, cfg.vocab_size
    schema = {
        "embed": {"table": L.ParamSpec((v, d), ("vocab", "embed"), "embed")},
        "final_norm": L.rms_norm_schema(d),
    }
    if not cfg.tie_embeddings:
        schema["lm_head"] = {
            "table": L.ParamSpec((d, v), ("embed", "vocab"))}
    if cfg.n_super > 0:
        schema["blocks"] = L.stack_specs(
            _block_schema(cfg, cfg.pattern), cfg.n_super)
    if cfg.tail_pattern:
        schema["tail"] = _block_schema(cfg, cfg.tail_pattern)
    return schema


def init_params(cfg, key):
    return L.materialize_tree(param_schema(cfg), key,
                              jnp.dtype(cfg.param_dtype))


def abstract_params(cfg):
    return L.abstract_tree(param_schema(cfg), jnp.dtype(cfg.param_dtype))


def logical_axes(cfg):
    return L.axes_tree(param_schema(cfg))


def param_count(cfg) -> int:
    import numpy as np
    return int(sum(np.prod(l.shape)
                   for l in jax.tree.leaves(abstract_params(cfg))))


# ---------------------------------------------------------------------------
# Forward (train / prefill).
# ---------------------------------------------------------------------------
def _apply_sublayer(kind, p, x, *, cfg, shard, cond):
    h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
    # SP boundary: gather the bf16 normed tensor (NOT the fp32 norm
    # intermediate, which GSPMD otherwise picks — 2x collective bytes).
    h = shard(h, "batch", None, "embed_act")
    aux = jnp.zeros((), jnp.float32)
    if kind in (C.ATTN, C.LOCAL_ATTN, C.CROSS_ATTN):
        h = L.attention(p["mix"], h, cfg, kind=kind, shard=shard, cond=cond)
    elif kind == C.MAMBA2:
        h = S.mamba2_mix(p["mix"], h, cfg, shard=shard)
    elif kind == C.RGLRU:
        h = S.rglru_mix(p["mix"], h, cfg, shard=shard)
    elif kind == C.MOE:
        h, aux = L.moe(p["mix"], h, cfg, shard=shard)
    x = x + h
    if cfg.has_mlp:
        h = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        h = shard(h, "batch", None, "embed_act")
        if cfg.moe:
            h, aux = L.moe(p["mlp"], h, cfg, shard=shard)
        else:
            h = L.mlp(p["mlp"], h, cfg, shard=shard)
        x = x + h
    return x, aux


def _apply_block(pattern, p_blk, x, cfg, shard, cond, remat=False):
    """(x, aux) of one block. With `remat`, each layer is rematerialised
    in the backward pass: the block keeps only its layers' inputs, and
    one layer's intermediates live at a time."""
    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(pattern):
        layer = functools.partial(_apply_sublayer, kind, cfg=cfg,
                                  shard=shard, cond=cond)
        if remat:
            layer = jax.checkpoint(
                layer, policy=jax.checkpoint_policies.nothing_saveable)
        x, a = layer(p_blk[f"{i:02d}_{kind}"], x)
        aux = aux + a
    return x, aux


def forward(params, cfg, tokens, cond=None, shard: ShardingCtx = INERT):
    """tokens: (B,S) int32 (or (B,S,D) pre-embedded frames for [audio]).

    Returns (logits (B,S,V), aux_loss scalar).
    """
    if tokens.ndim == 2:
        x = jnp.take(params["embed"]["table"], tokens, axis=0)
    else:
        x = tokens.astype(cfg.activation_dtype)
    x = x.astype(cfg.activation_dtype)
    x = shard(x, "batch", "seq", "embed_act")
    if cond is not None:
        cond = cond.astype(cfg.activation_dtype)

    aux_total = jnp.zeros((), jnp.float32)
    if cfg.n_super > 0:
        def block(carry, p_blk):
            h, aux = carry
            h, a = _apply_block(cfg.pattern, p_blk, h, cfg, shard, cond,
                                remat=cfg.remat)
            return (h, aux + a), None

        (x, aux_total), _ = lax.scan(block, (x, aux_total), params["blocks"])
    if cfg.tail_pattern:
        x, a = _apply_block(cfg.tail_pattern, params["tail"], x, cfg,
                            shard, cond)
        aux_total = aux_total + a

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = (params["embed"]["table"].T if cfg.tie_embeddings
             else params["lm_head"]["table"])
    logits = jnp.einsum("bsd,dv->bsv", x, table)
    logits = shard(logits, "batch", "seq", "vocab")
    return logits, aux_total


def loss_fn(params, cfg, batch, shard: ShardingCtx = INERT,
            aux_weight: float = 0.01):
    """batch: dict(tokens (B,S), labels (B,S), [cond]). Mean token CE."""
    logits, aux = forward(params, cfg, batch["tokens"],
                          cond=batch.get("cond"), shard=shard)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, batch["labels"][..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    return ce + aux_weight * aux


# ---------------------------------------------------------------------------
# Decode path.
# ---------------------------------------------------------------------------
def _sublayer_cache_shapes(cfg, kind, batch, max_len, dtype):
    h = cfg.resolved_head_dim
    nk = cfg.num_kv_heads
    if kind == C.ATTN:
        return {
            "k": ((batch, max_len, nk, h), dtype,
                  ("batch", "cache_len", "kv_heads", "head_dim")),
            "v": ((batch, max_len, nk, h), dtype,
                  ("batch", "cache_len", "kv_heads", "head_dim")),
        }
    if kind == C.LOCAL_ATTN:
        wl = min(cfg.window_size, max_len)
        return {
            "k": ((batch, wl, nk, h), dtype,
                  ("batch", None, "kv_heads", "head_dim")),
            "v": ((batch, wl, nk, h), dtype,
                  ("batch", None, "kv_heads", "head_dim")),
        }
    if kind == C.CROSS_ATTN:
        t = cfg.n_cond_tokens
        return {
            "cond_k": ((batch, t, nk, h), dtype,
                       ("batch", "cond", "kv_heads", "head_dim")),
            "cond_v": ((batch, t, nk, h), dtype,
                       ("batch", "cond", "kv_heads", "head_dim")),
        }
    if kind == C.MAMBA2:
        s = cfg.ssm
        d_in, nh, conv_dim = S.mamba2_dims(cfg)
        return {
            "conv": ((batch, s.conv_width - 1, conv_dim), dtype,
                     ("batch", None, "ssm_inner")),
            "ssm": ((batch, nh, s.head_dim, s.d_state), jnp.float32,
                    ("batch", "ssm_heads", None, "ssm_state")),
        }
    if kind == C.RGLRU:
        w = cfg.rglru.lru_width or cfg.d_model
        k = cfg.rglru.conv_width
        return {
            "conv": ((batch, k - 1, w), dtype, ("batch", None, "lru_width")),
            "h": ((batch, w), jnp.float32, ("batch", "lru_width")),
        }
    if kind == C.MOE:
        return {}
    raise ValueError(kind)


def _cache_tree(cfg, batch, max_len, dtype, mode):
    """mode: 'zeros' | 'abstract' | 'axes'."""
    def blk(pattern, stack):
        out = {}
        for i, kind in enumerate(pattern):
            sub = {}
            for name, (shape, dt, ax) in _sublayer_cache_shapes(
                    cfg, kind, batch, max_len, dtype).items():
                if stack:
                    shape = (cfg.n_super,) + shape
                    ax = ("layers",) + ax
                if mode == "zeros":
                    sub[name] = jnp.zeros(shape, dt)
                elif mode == "abstract":
                    sub[name] = jax.ShapeDtypeStruct(shape, dt)
                else:
                    sub[name] = ax
            out[f"{i:02d}_{kind}"] = sub
        return out

    cache = {}
    if cfg.n_super > 0:
        cache["blocks"] = blk(cfg.pattern, stack=True)
    if cfg.tail_pattern:
        cache["tail"] = blk(cfg.tail_pattern, stack=False)
    return cache


def init_cache(cfg, batch, max_len, dtype=None):
    return _cache_tree(cfg, batch, max_len, dtype or cfg.activation_dtype,
                       "zeros")


def abstract_cache(cfg, batch, max_len, dtype=None):
    return _cache_tree(cfg, batch, max_len, dtype or cfg.activation_dtype,
                       "abstract")


def cache_logical_axes(cfg, batch=0, max_len=0):
    return _cache_tree(cfg, 1, 2, jnp.float32, "axes")


def _apply_sublayer_decode(kind, p, x, cfg, cache, pos, shard):
    h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind in (C.ATTN, C.LOCAL_ATTN):
        h, new = L.decode_attention(p["mix"], h, cfg, kind=kind,
                                    cache=cache, pos=pos, shard=shard)
    elif kind == C.CROSS_ATTN:
        h, _ = L.decode_attention(
            p["mix"], h, cfg, kind=kind, cache=None, pos=pos, shard=shard,
            cond_kv={"k": cache["cond_k"], "v": cache["cond_v"]})
        new = cache
    elif kind == C.MAMBA2:
        h, new = S.mamba2_decode(p["mix"], h, cfg, cache, shard=shard)
    elif kind == C.RGLRU:
        h, new = S.rglru_decode(p["mix"], h, cfg, cache, shard=shard)
    elif kind == C.MOE:
        h, _ = L.moe(p["mix"], h, cfg, shard=shard)
        new = cache
    x = x + h
    if cfg.has_mlp:
        h = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        if cfg.moe:
            h, _ = L.moe(p["mlp"], h, cfg, shard=shard)
        else:
            h = L.mlp(p["mlp"], h, cfg, shard=shard)
        x = x + h
    return x, new


def _apply_block_decode(pattern, p_blk, x, cfg, cache_blk, pos, shard):
    new_cache = {}
    for i, kind in enumerate(pattern):
        key = f"{i:02d}_{kind}"
        x, new_cache[key] = _apply_sublayer_decode(
            kind, p_blk[key], x, cfg, cache_blk[key], pos, shard)
    return x, new_cache


def decode_step(params, cfg, tokens, pos, cache, shard: ShardingCtx = INERT):
    """tokens: (B,1) int32 (or (B,1,D) frames); pos: (B,) int32.

    Returns (logits (B,1,V), new_cache).
    """
    if tokens.ndim == 2:
        x = jnp.take(params["embed"]["table"], tokens, axis=0)
    else:
        x = tokens.astype(cfg.activation_dtype)
    x = x.astype(cfg.activation_dtype)
    x = shard(x, "batch", None, "embed_act")

    new_cache = {}
    if cfg.n_super > 0:
        def body(h, inp):
            p_blk, c_blk = inp
            h, nc = _apply_block_decode(cfg.pattern, p_blk, h, cfg, c_blk,
                                        pos, shard)
            return h, nc

        x, new_cache["blocks"] = lax.scan(
            body, x, (params["blocks"], cache["blocks"]))
    if cfg.tail_pattern:
        x, new_cache["tail"] = _apply_block_decode(
            cfg.tail_pattern, params["tail"], x, cfg, cache["tail"], pos,
            shard)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = (params["embed"]["table"].T if cfg.tie_embeddings
             else params["lm_head"]["table"])
    logits = jnp.einsum("bsd,dv->bsv", x, table)
    return shard(logits, "batch", None, "vocab"), new_cache
