"""Core transformer layers: norms, RoPE, attention (GQA / sliding-window /
cross), MLPs, GShard capacity-routed MoE, and the held share of a
sigmoid-routed expert layer that drops no token.

All layers are pure functions over param pytrees. Shapes use
B=batch, S=query seq, T=kv seq, D=d_model, N=q heads, K=kv heads,
G=N//K (GQA group), H=head_dim, F=d_ff, E=experts, C=capacity.

Attention is computed in query chunks with the softmax row kept full —
O(chunk * T) live memory instead of O(S * T) — which is what lets the
32k-prefill cells fit during the dry-run. The Pallas flash-attention
kernel (repro.kernels.flash_attention) replaces the inner chunk loop on
TPU when ``cfg.use_pallas`` is set.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.sharding.rules import ShardingCtx, INERT


# ---------------------------------------------------------------------------
# Param schema plumbing.
# ---------------------------------------------------------------------------
class ParamSpec:
    """Declarative parameter: shape + logical axes + initializer."""

    __slots__ = ("shape", "axes", "init", "dtype")

    def __init__(self, shape, axes, init="normal", dtype=None):
        assert len(shape) == len(axes), (shape, axes)
        self.shape = tuple(shape)
        self.axes = tuple(axes)
        self.init = init
        self.dtype = dtype

    def materialize(self, key, dtype):
        dtype = self.dtype or dtype
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        if self.init == "normal":
            fan_in = self.shape[0] if self.shape else 1
            scale = 1.0 / math.sqrt(max(fan_in, 1))
            return (jax.random.normal(key, self.shape, jnp.float32)
                    * scale).astype(dtype)
        if self.init == "embed":
            return (jax.random.normal(key, self.shape, jnp.float32)
                    * 0.02).astype(dtype)
        if callable(self.init):
            return self.init(key, self.shape).astype(dtype)
        raise ValueError(self.init)


def is_spec(x):
    return isinstance(x, ParamSpec)


def materialize_tree(schema, key, dtype):
    leaves, treedef = jax.tree.flatten(schema, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = [l.materialize(k, dtype) for l, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_tree(schema, dtype):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or dtype),
        schema, is_leaf=is_spec)


def axes_tree(schema):
    return jax.tree.map(lambda s: s.axes, schema, is_leaf=is_spec)


def stack_specs(schema, n, axis_name="layers"):
    """Prefix every spec with a stacked leading dim (for scan-over-layers)."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, (axis_name,) + s.axes, s.init,
                            s.dtype),
        schema, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# Norms.
# ---------------------------------------------------------------------------
def rms_norm_schema(d):
    return {"scale": ParamSpec((d,), ("norm",), "ones", dtype=jnp.float32)}


def rms_norm(x, p, eps):
    """RMSNorm with fp32 statistics but no materialized fp32 activation:
    the fp32 square fuses into the variance reduce, and the normalization
    multiply stays in the input dtype. (A full fp32 intermediate on the
    residual path doubles the SP-boundary all-gather bytes — GSPMD
    gathers whatever tensor feeds the projections.)"""
    dt = x.dtype
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv = lax.rsqrt(var + eps).astype(dt)
    return x * inv * p["scale"].astype(dt)


# ---------------------------------------------------------------------------
# RoPE.
# ---------------------------------------------------------------------------
def rope(x, positions, theta):
    """x: (..., S, n, H) rotated in (S) by `positions` (..., S)."""
    h = x.shape[-1]
    half = h // 2
    freq = jnp.arange(0, half, dtype=jnp.float32)
    inv = theta ** (-freq / half)                      # (half,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., None, :]                             # (..., S, 1, half)
    cos = cos[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention.
# ---------------------------------------------------------------------------
def attention_schema(cfg, cross=False):
    d, h = cfg.d_model, cfg.resolved_head_dim
    nq, nk = cfg.num_heads, cfg.num_kv_heads
    s = {
        "wq": ParamSpec((d, nq, h), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, nk, h), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, nk, h), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((nq, h, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((nq, h), ("heads", "head_dim"), "zeros")
        s["bk"] = ParamSpec((nk, h), ("kv_heads", "head_dim"), "zeros")
        s["bv"] = ParamSpec((nk, h), ("kv_heads", "head_dim"), "zeros")
    return s


def _soft_cap(scores, cap):
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _chunked_attn(q, k, v, q_positions, kv_positions, *, causal, window,
                  softcap, chunk, shard: ShardingCtx):
    """q, k, v: (B,S|T,N,H) with kv already expanded to N heads.

    Query-chunked (full softmax row per chunk): O(chunk*T) live memory —
    what lets prefill_32k compile within HBM without the Pallas kernel.
    Flat head layout (no (K,G) split) keeps GSPMD on the standard
    attention partitioning path (heads on `model`).
    """
    B, S, N, H = q.shape
    T = k.shape[1]
    scale = 1.0 / math.sqrt(H)
    nc = max(S // chunk, 1)
    chunk = S // nc
    qr = q.reshape(B, nc, chunk, N, H).transpose(1, 0, 2, 3, 4)
    qp = q_positions.reshape(nc, chunk)

    def body(_, qi_pi):
        qi, pi = qi_pi                              # (B,c,N,H), (c,)
        s = jnp.einsum("bqnh,btnh->bnqt", qi, k,
                       preferred_element_type=jnp.float32) * scale
        s = _soft_cap(s, softcap)
        # additive bias (chunk,T) — small, fuses into the softmax; a
        # boolean select at full score shape gets hoisted out of the layer
        # scan by XLA as a ~0.5GB loop-invariant carry.
        bias = jnp.zeros((chunk, T), jnp.float32)
        if causal:
            bias = jnp.where(kv_positions[None, :] <= pi[:, None],
                             bias, -1e30)
        if window is not None:
            bias = jnp.where(kv_positions[None, :] > pi[:, None] - window,
                             bias, -1e30)
        s = s + bias[None, None]
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bnqt,btnh->bqnh", p, v)
        return None, o

    _, out = lax.scan(body, None, (qr, qp))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, N, H)


def attention(p, x, cfg, *, kind, shard: ShardingCtx = INERT,
              cond=None, positions=None):
    """Self / sliding-window / cross attention. x: (B,S,D) -> (B,S,D)."""
    from repro.common import config as C
    B, S, D = x.shape
    nq, nk, h = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = nq // nk
    cross = kind == C.CROSS_ATTN
    src = cond if cross else x
    T = src.shape[1]

    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.einsum("btd,dnh->btnh", src, p["wk"])
    v = jnp.einsum("btd,dnh->btnh", src, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    # Inner tensors claim `model` for heads (TP) with priority over seq
    # (SP): when the head count divides the axis this is plain TP with the
    # residual stream sequence-sharded at block boundaries (Megatron-SP);
    # when it does not (24 heads on a 16-way axis), `resolve_spec` frees
    # the axis and seq claims it — attention runs sequence-parallel
    # instead of replicated.
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", None, "kv_heads", "head_dim")
    v = shard(v, "batch", None, "kv_heads", "head_dim")

    if positions is None:
        positions = jnp.arange(S)
    kv_positions = jnp.arange(T)
    if not cross and cfg.rope_theta is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, kv_positions, cfg.rope_theta)

    # GQA: expand kv to the full head count. The expansion keeps GSPMD on
    # the plain-attention partitioning path and makes the head dim
    # shardable even when num_kv_heads < mesh model-axis (e.g. kv=8 on a
    # 16-way axis); the repeat of a replicated kv shard is local.
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    k = shard(k, "batch", None, "heads", "head_dim")
    v = shard(v, "batch", None, "heads", "head_dim")
    window = cfg.window_size if kind == C.LOCAL_ATTN else None
    if cfg.use_pallas and not cross:
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(
            q, k, v, causal=True, window=window,
            softcap=cfg.logit_softcap)
    else:
        out = _chunked_attn(
            q, k, v, positions, kv_positions,
            causal=not cross, window=window, softcap=cfg.logit_softcap,
            chunk=min(cfg.attn_chunk, S), shard=shard)
    y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"])
    return shard(y, "batch", "seq", "embed_act")


def decode_attention(p, x, cfg, *, kind, cache, pos, shard: ShardingCtx = INERT,
                     cond_kv=None):
    """One-token decode. x: (B,1,D); cache: dict(k,v: (B,L,K,H)).

    Returns (y, new_cache). `pos`: (B,) current position per sequence.
    """
    from repro.common import config as C
    B, _, D = x.shape
    nq, nk, h = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = nq // nk
    cross = kind == C.CROSS_ATTN

    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]

    if cross:
        # static cross KV, precomputed at prefill time
        k, v = cond_kv["k"], cond_kv["v"]
        L = k.shape[1]
        valid = jnp.ones((B, L), bool)
        new_cache = cache
    else:
        knew = jnp.einsum("btd,dnh->btnh", x, p["wk"])
        vnew = jnp.einsum("btd,dnh->btnh", x, p["wv"])
        if cfg.qkv_bias:
            knew = knew + p["bk"]
            vnew = vnew + p["bv"]
        if cfg.rope_theta is not None:
            q = rope(q, pos[:, None], cfg.rope_theta)
            knew = rope(knew, pos[:, None], cfg.rope_theta)
        L = cache["k"].shape[1]
        if kind == C.LOCAL_ATTN:
            # ring buffer of size window
            slot = (pos % L)
        else:
            slot = pos
        bidx = jnp.arange(B)
        k = cache["k"].at[bidx, slot].set(knew[:, 0].astype(cache["k"].dtype))
        v = cache["v"].at[bidx, slot].set(vnew[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": k, "v": v}
        idx = jnp.arange(L)
        if kind == C.LOCAL_ATTN:
            valid = (idx[None] <= slot[:, None]) | (pos[:, None] >= L)
        else:
            valid = idx[None] <= pos[:, None]

    qf = q.reshape(B, nk, g, h).astype(jnp.float32)
    s = jnp.einsum("bkgh,btkh->bkgt", qf, k.astype(jnp.float32))
    s = s / math.sqrt(h)
    s = _soft_cap(s, cfg.logit_softcap)
    s = jnp.where(valid[:, None, None], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkh->bkgh", pr, v.astype(jnp.float32))
    o = o.reshape(B, 1, nq, h).astype(x.dtype)
    y = jnp.einsum("bsnh,nhd->bsd", o, p["wo"])
    return shard(y, "batch", None, "embed_act"), new_cache


# ---------------------------------------------------------------------------
# Dense MLP.
# ---------------------------------------------------------------------------
def mlp_schema(cfg):
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_kind == "swiglu":
        return {
            "wi_gate": ParamSpec((d, f), ("embed", "mlp")),
            "wi_up": ParamSpec((d, f), ("embed", "mlp")),
            "wo": ParamSpec((f, d), ("mlp", "embed")),
        }
    return {
        "wi": ParamSpec((d, f), ("embed", "mlp")),
        "wo": ParamSpec((f, d), ("mlp", "embed")),
    }


def _act(kind, h):
    """The ungated MLP activation of `mlp_kind`: gelu, or relu2 (the
    squared ReLU of Nemotron-H and Primer)."""
    return jnp.square(jax.nn.relu(h)) if kind == "relu2" else jax.nn.gelu(h)


def mlp(p, x, cfg, shard: ShardingCtx = INERT):
    if cfg.mlp_kind == "swiglu":
        gate = jnp.einsum("bsd,df->bsf", x, p["wi_gate"])
        up = jnp.einsum("bsd,df->bsf", x, p["wi_up"])
        h = jax.nn.silu(gate) * up
    else:
        h = _act(cfg.mlp_kind, jnp.einsum("bsd,df->bsf", x, p["wi"]))
    h = shard(h, "batch", "seq", "mlp")
    y = jnp.einsum("bsf,fd->bsd", h, p["wo"])
    return shard(y, "batch", "seq", "embed_act")


# ---------------------------------------------------------------------------
# Mixture of Experts (GShard capacity routing, top-k).
# ---------------------------------------------------------------------------
def moe_schema(cfg):
    if cfg.moe.router == "sigmoid":
        return held_experts_schema(cfg)
    d = cfg.d_model
    e, f = cfg.moe.num_experts, cfg.moe.d_ff
    s = {"router": ParamSpec((d, e), ("embed", None))}
    if cfg.mlp_kind == "swiglu":
        s["wi_gate"] = ParamSpec((e, d, f), ("experts", "embed", "mlp"))
        s["wi_up"] = ParamSpec((e, d, f), ("experts", "embed", "mlp"))
        s["wo"] = ParamSpec((e, f, d), ("experts", "mlp", "embed"))
    else:
        s["wi"] = ParamSpec((e, d, f), ("experts", "embed", "mlp"))
        s["wo"] = ParamSpec((e, f, d), ("experts", "mlp", "embed"))
    return s


def moe_capacity(cfg, group_tokens: int) -> int:
    m = cfg.moe
    cap = int(math.ceil(group_tokens * m.top_k * m.capacity_factor
                        / m.num_experts))
    return max(cap, m.top_k)


def moe(p, x, cfg, shard: ShardingCtx = INERT):
    """x: (B,S,D). GShard one-hot dispatch with per-group capacity; a
    sigmoid router is the held share of `held_experts`, with no
    auxiliary loss. Returns (y, aux loss)."""
    m = cfg.moe
    if m.router == "sigmoid":
        return (held_experts(p, x, cfg, shard=shard),
                jnp.zeros((), jnp.float32))
    B, S, D = x.shape
    gs = min(m.group_size, B * S)
    assert (B * S) % gs == 0, (B, S, gs)
    ng = B * S // gs
    E, K = m.num_experts, m.top_k
    C = moe_capacity(cfg, gs)

    xg = x.reshape(ng, gs, D)
    xg = shard(xg, "batch", None, "embed_act")
    logits = jnp.einsum("gsd,de->gse", xg, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = lax.top_k(probs, K)       # (ng, gs, K)
    gate_vals = gate_vals / jnp.clip(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (ng,gs,K,E)
    # position of each (token, slot) within its expert queue, priority by
    # (slot-major, token) order as in GShard.
    flat = onehot.transpose(0, 2, 1, 3).reshape(ng, gs * K, E)
    pos = jnp.cumsum(flat, axis=1) - flat              # (ng, gs*K, E)
    pos = pos.reshape(ng, K, gs, E).transpose(0, 2, 1, 3)  # (ng,gs,K,E)
    pos = jnp.sum(pos * onehot, axis=-1)               # (ng, gs, K)
    within = (pos < C).astype(jnp.float32)

    pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * within[..., None]
    # dispatch: (ng, gs, E, C)
    dispatch = jnp.einsum("gske,gskc->gsec", onehot, pos_oh)
    combine = jnp.einsum("gsk,gske,gskc->gsec", gate_vals, onehot, pos_oh)

    dispatch = dispatch.astype(x.dtype)
    xe = jnp.einsum("gsec,gsd->gecd", dispatch, xg)    # (ng,E,C,D)
    xe = shard(xe, "batch", "experts", None, "embed_act")
    if cfg.mlp_kind == "swiglu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, p["wi_gate"])) \
            * jnp.einsum("gecd,edf->gecf", xe, p["wi_up"])
    else:
        h = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", xe, p["wi"]))
    h = shard(h, "batch", "experts", None, "mlp")
    ye = jnp.einsum("gecf,efd->gecd", h, p["wo"])      # (ng,E,C,D)
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), ye)
    y = y.reshape(B, S, D)
    return shard(y, "batch", "seq", "embed_act"), _aux_loss(probs, onehot)


def _aux_loss(probs, onehot):
    """Load-balancing auxiliary loss (Switch-style)."""
    # probs: (ng, gs, E); onehot: (ng, gs, K, E)
    E = probs.shape[-1]
    frac_tokens = jnp.mean(jnp.sum(onehot, axis=2), axis=1)   # (ng, E)
    frac_probs = jnp.mean(probs, axis=1)                       # (ng, E)
    return jnp.mean(jnp.sum(frac_tokens * frac_probs, -1)) * E


# ---------------------------------------------------------------------------
# The held share of a sigmoid-routed expert layer (DeepSeek-V3 /
# Nemotron-H routing), with no token dropped.
# ---------------------------------------------------------------------------
# `jax.named_scope` names of the layer and its parts, as a compiled op's
# `op_name` carries them.
EXPERT_LAYER, ROUTER, EXPERTS, SHARED_EXPERT = (
    "expert_layer", "router", "experts", "shared_expert")


def _normal_over(axis):
    """Normal init with std 1/sqrt(shape[axis]): the fan-in of a stacked
    weight is not its leading dim."""
    def init(key, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(shape[axis]))
    return init


def held_experts_schema(cfg):
    """Router (fp32) and selection bias over all experts; the held
    experts' ungated MLP weights; the shared expert's."""
    d, m = cfg.d_model, cfg.moe
    if cfg.mlp_kind == "swiglu":
        raise ValueError("the sigmoid-routed expert layer takes an "
                         "ungated mlp_kind (gelu, relu2)")
    h, f = m.num_held, m.d_ff
    s = {
        "router": ParamSpec((d, m.num_experts), ("embed", None),
                            dtype=jnp.float32),
        "router_bias": ParamSpec((m.num_experts,), (None,),
                                 dtype=jnp.float32),
        "wi": ParamSpec((h, d, f), ("experts", "embed", "mlp"),
                        _normal_over(1)),
        "wo": ParamSpec((h, f, d), ("experts", "mlp", "embed"),
                        _normal_over(1)),
    }
    if m.shared_d_ff:
        fs = m.shared_d_ff
        s["shared"] = {"wi": ParamSpec((d, fs), ("embed", "mlp")),
                       "wo": ParamSpec((fs, d), ("mlp", "embed"))}
    return s


def route(p, x, m):
    """Sigmoid routing of tokens x (T, D) over all `m.num_experts`:
    scores s = sigmoid(x W_r) in fp32, the top k of s + b chosen (b
    selects and gets no gradient), each weight `routed_scaling` x s over
    the sum of s over the k chosen. Returns (chosen (T, k) expert ids,
    weights (T, k) fp32)."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"],
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(scores + lax.stop_gradient(p["router_bias"]),
                          m.top_k)
    w = jnp.take_along_axis(scores, chosen, axis=1)
    return chosen, m.routed_scaling * w / jnp.sum(w, -1, keepdims=True)


def held_slots(chosen, m):
    """The routed slots (token, k) in the order of the held expert they
    go to (experts 0 .. held - 1), those to experts not held here last.
    Returns (order (T*k,) slot indices, sizes (held,) int32 slots per
    held expert)."""
    h = m.num_held
    key = jnp.minimum(chosen.reshape(-1), h)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(h), axis=0,
                    dtype=jnp.int32)
    return order, sizes


def _filled(rows, sizes):
    """(rows, 1) mask of the rows inside the groups of `sizes`."""
    return (jnp.arange(rows) < jnp.sum(sizes))[:, None]


# One client slot at a time under vmap: neither the TPU compiler's
# grouped matmul nor the megablox kernels' grid, sized by the groups,
# take a batch dim. Rows past the groups are left unwritten by the TPU's
# `lax.ragged_dot`, in its result and in its transpose's; they are
# zeroed, so that memory reaches neither values nor gradients.
@jax.custom_batching.sequential_vmap
def _ragged(x, w, sizes):
    return jnp.where(_filled(x.shape[0], sizes),
                     lax.ragged_dot(x, w, sizes), 0)


@jax.custom_batching.sequential_vmap
def _ragged_vjp(x, w, sizes, g):
    _, vjp = jax.vjp(lambda a, b: lax.ragged_dot(a, b, sizes), x, w)
    dx, dw = vjp(g)
    return jnp.where(_filled(x.shape[0], sizes), dx, 0), dw


def _block(dim, cap):
    """The largest multiple of 128 that divides `dim` and is at most
    `cap`, else all of `dim`: a block edge the TPU takes."""
    for b in range(cap - cap % 128, 0, -128):
        if dim % b == 0:
            return b
    return dim


def _gmm_tiles(m, k, n):
    """Megablox `gmm` tiles (rows, contraction, columns): 256 rows, under
    16 MiB of VMEM at 2688 x 1856 bf16 with double buffering."""
    return math.gcd(m, 256), _block(k, 1024), _block(n, 1024)


def _tgmm_tiles(m, k, n):
    """Megablox `tgmm` tiles: each expert's (k, n) block of its weight
    gradient, and the block's fp32 accumulator, at most 720K elements."""
    tk = _block(k, 512)
    return math.gcd(m, 256), tk, _block(n, max(128, 720_000 // tk))


def _with_rest(x, sizes):
    """The group sizes with the rows past the held groups as one more
    group, which megablox leaves out of the work and zeroes."""
    return jnp.append(sizes, x.shape[0] - jnp.sum(sizes)).astype(jnp.int32)


@jax.custom_batching.sequential_vmap
def _megablox(x, w, sizes):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    return gmm(x, w, _with_rest(x, sizes), x.dtype, _gmm_tiles)


@jax.custom_batching.sequential_vmap
def _megablox_vjp(x, w, sizes, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    every = _with_rest(x, sizes)
    dx = gmm(g, w, every, x.dtype, _gmm_tiles, transpose_rhs=True)
    dw = tgmm(x.T, g, every, w.dtype, _tgmm_tiles,
                 num_actual_groups=w.shape[0])
    return dx, dw


_GROUPED = {False: (_ragged, _ragged_vjp), True: (_megablox, _megablox_vjp)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(x, w, sizes, pallas=False):
    """Rows of x (M, K), sorted by group, each times its group's w
    (G, K, N), with the rows past sum(sizes) zero: `lax.ragged_dot`, or
    with `pallas` the megablox kernels, whose work follows the rows in
    the groups in tiles of 256."""
    return _GROUPED[pallas][0](x, w, sizes)


def _grouped_fwd(x, w, sizes, pallas):
    return _GROUPED[pallas][0](x, w, sizes), (x, w, sizes)


def _grouped_bwd(pallas, res, g):
    x, w, sizes = res
    dx, dw = _GROUPED[pallas][1](x, w, sizes, g)
    return dx, dw, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def held_experts(p, x, cfg, shard: ShardingCtx = INERT):
    """This chip's part of a sigmoid-routed expert layer. x: (B,S,D).

    Each token is routed over all experts (`route`); every slot routed
    to a held expert is computed, however uneven the routing: the
    slots are sorted by held expert and the experts' MLPs run as grouped
    matmuls (`grouped_matmul`) over rows bounded by all B*S*k slots.
    Slots to experts held elsewhere weigh 0; their weights still count
    in the normalisation. The shared expert sees every token. No
    auxiliary loss."""
    m = cfg.moe
    B, S, D = x.shape
    n, k = B * S, m.top_k
    xf = x.reshape(n, D)
    act = functools.partial(_act, cfg.mlp_kind)
    with jax.named_scope(EXPERT_LAYER):
        with jax.named_scope(ROUTER):
            chosen, w = route(p, xf, m)
            order, sizes = held_slots(chosen, m)
            w = jnp.where(chosen < m.num_held, w, 0.0)
        with jax.named_scope(EXPERTS):
            gmm = functools.partial(grouped_matmul, sizes=sizes,
                                    pallas=cfg.use_pallas)
            h = act(gmm(xf[order // k], p["wi"]))
            ys = gmm(h, p["wo"])
            ys = ys[jnp.argsort(order)].reshape(n, k, D)
            y = jnp.sum(ys.astype(jnp.float32) * w[..., None], axis=1)
            y = y.astype(x.dtype)
        if m.shared_d_ff:
            with jax.named_scope(SHARED_EXPERT):
                sh = p["shared"]
                y = y + act(xf @ sh["wi"]) @ sh["wo"]
    return shard(y.reshape(B, S, D), "batch", "seq", "embed_act")
