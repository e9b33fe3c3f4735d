"""State-space layers: Mamba2 (SSD, chunked scan) and RG-LRU (Griffin).

Shapes: b=batch, s=seq, d=d_model, i=d_inner, h=ssm heads, p=head_dim,
n=d_state, g=B/C groups, w=lru width, c=chunks, q=chunk len.

The model trains through the chunked SSD here. The Pallas kernel in
repro.kernels.ssd implements the identical chunk decomposition with VMEM
tiling, forward only, and is validated against `ssd_reference` below;
having no backward, it is not called from the model, whose `use_pallas`
turns on the flash-attention and RG-LRU kernels.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import ParamSpec, rms_norm


# ===========================================================================
# Mamba2 (SSD).
# ===========================================================================
# `jax.named_scope` name of the Mamba-2 mixer, as a compiled op's
# `op_name` carries it.
SSM_MIXER = "ssm_mixer"


def mamba2_dims(cfg):
    s = cfg.ssm
    d_in = s.num_heads * s.head_dim if s.num_heads else s.expand * cfg.d_model
    nheads = d_in // s.head_dim
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    return d_in, nheads, conv_dim


def mamba2_schema(cfg):
    s = cfg.ssm
    d = cfg.d_model
    d_in, nh, conv_dim = mamba2_dims(cfg)
    gn = s.n_groups * s.d_state

    def a_init(key, shape):
        lo, hi = s.a_init_range
        u = jax.random.uniform(key, shape, jnp.float32, lo, hi)
        return jnp.log(u)

    def dt_bias_init(key, shape):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(s.dt_max) - math.log(s.dt_min))
                     + math.log(s.dt_min))
        # inverse softplus
        return dt + jnp.log(-jnp.expm1(-dt))

    return {
        "wz": ParamSpec((d, d_in), ("embed", "ssm_inner")),
        "wx": ParamSpec((d, d_in), ("embed", "ssm_inner")),
        "wB": ParamSpec((d, gn), ("embed", None)),
        "wC": ParamSpec((d, gn), ("embed", None)),
        "wdt": ParamSpec((d, nh), ("embed", "ssm_heads")),
        "conv_w": ParamSpec((s.conv_width, conv_dim), (None, None)),
        "conv_b": ParamSpec((conv_dim,), (None,), "zeros"),
        "A_log": ParamSpec((nh,), ("ssm_heads",), a_init, dtype=jnp.float32),
        "dt_bias": ParamSpec((nh,), ("ssm_heads",), dt_bias_init,
                             dtype=jnp.float32),
        "D": ParamSpec((nh,), ("ssm_heads",), "ones", dtype=jnp.float32),
        "norm": ParamSpec((d_in,), ("ssm_inner",), "ones", dtype=jnp.float32),
        "wo": ParamSpec((d_in, d), ("ssm_inner", "embed")),
    }


def _causal_conv(u, w, b):
    """Depthwise causal conv. u: (b,s,c); w: (k,c); b: (c,)."""
    k = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + u.shape[1]] * w[i] for i in range(k))
    return out + b


def _segsum(a):
    """a: (..., q) -> (..., q, q) with out[i,j]=sum_{k=j+1..i} a_k, i>=j."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, out, -jnp.inf)


def ssd_reference(xbar, log_a, Bm, Cm, chunk, initial_state=None):
    """Chunked state-space-duality scan (Mamba2 §6 minimal algorithm).

    xbar: (b,s,h,p)  inputs already scaled by dt
    log_a: (b,s,h)   dt * A  (negative)
    Bm, Cm: (b,s,h,n) input/output projections (already group-broadcast)
    Returns y: (b,s,h,p), final_state: (b,h,p,n)
    """
    b, s, h, p = xbar.shape
    n = Bm.shape[-1]
    nc = max(s // chunk, 1)
    q = s // nc
    xb = xbar.reshape(b, nc, q, h, p).astype(jnp.float32)
    la = log_a.reshape(b, nc, q, h).astype(jnp.float32)
    Bc = Bm.reshape(b, nc, q, h, n).astype(jnp.float32)
    Cc = Cm.reshape(b, nc, q, h, n).astype(jnp.float32)

    la_cs = jnp.cumsum(la, axis=2)                     # (b,c,q,h) inclusive
    # 1. intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(la.transpose(0, 1, 3, 2)))     # (b,c,h,q,q)
    att = jnp.einsum("bcihn,bcjhn->bchij", Cc, Bc)
    y_diag = jnp.einsum("bchij,bchij,bcjhp->bcihp", att, L, xb)
    # 2. per-chunk end states
    decay_end = jnp.exp(la_cs[:, :, -1:, :] - la_cs)   # (b,c,q,h)
    states = jnp.einsum("bcjhn,bcjh,bcjhp->bchpn", Bc, decay_end, xb)
    # 3. inter-chunk recurrence (scan over chunks)
    chunk_decay = jnp.exp(la_cs[:, :, -1, :])          # (b,c,h)
    s0 = (jnp.zeros((b, h, p, n), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(carry, inp):
        st, dec = inp                                  # (b,h,p,n), (b,h)
        new = carry * dec[:, :, None, None] + st
        return new, carry                              # emit state BEFORE chunk

    final, prev_states = lax.scan(
        step, s0,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # (b,c,h,p,n)
    # 4. contribution of carried state to each position
    state_decay = jnp.exp(la_cs)                        # (b,c,q,h)
    y_off = jnp.einsum("bcihn,bchpn,bcih->bcihp", Cc, prev_states,
                       state_decay)
    y = (y_diag + y_off).reshape(b, s, h, p)
    return y.astype(xbar.dtype), final


def gated_norm(y, z, scale, n_groups, eps):
    """RMSNorm of y * silu(z) over each of `n_groups` equal groups of the
    last dim (gate first, then norm, then the scale), with fp32
    statistics as `rms_norm` keeps them; one group is the whole width."""
    g = y * jax.nn.silu(z)
    if n_groups == 1:      # one group: rms_norm, as the mamba2 slot runs it
        return rms_norm(g, {"scale": scale}, eps)
    shape = g.shape
    g = g.reshape(shape[:-1] + (n_groups, shape[-1] // n_groups))
    var = jnp.mean(jnp.square(g.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    g = (g * lax.rsqrt(var + eps).astype(g.dtype)).reshape(shape)
    return g * scale.astype(g.dtype)


def mamba2_mix(p, x, cfg):
    """Full Mamba2 mixing layer. x: (b,s,d) -> (b,s,d)."""
    with jax.named_scope(SSM_MIXER):
        return _mamba2_mix(p, x, cfg)


def _mamba2_mix(p, x, cfg):
    s_cfg = cfg.ssm
    b, s, d = x.shape
    d_in, nh, conv_dim = mamba2_dims(cfg)
    gn = s_cfg.n_groups * s_cfg.d_state

    z = jnp.einsum("bsd,di->bsi", x, p["wz"])
    xi = jnp.einsum("bsd,di->bsi", x, p["wx"])
    Bm = jnp.einsum("bsd,dn->bsn", x, p["wB"])
    Cm = jnp.einsum("bsd,dn->bsn", x, p["wC"])
    dt = jnp.einsum("bsd,dh->bsh", x, p["wdt"])

    conv_in = jnp.concatenate([xi, Bm, Cm], axis=-1)
    conv_out = jax.nn.silu(_causal_conv(conv_in, p["conv_w"], p["conv_b"]))
    xi = conv_out[..., :d_in]
    Bm = conv_out[..., d_in:d_in + gn]
    Cm = conv_out[..., d_in + gn:]

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])                           # (nh,)
    xh = xi.reshape(b, s, nh, s_cfg.head_dim)
    hpg = nh // s_cfg.n_groups
    Bh = jnp.repeat(Bm.reshape(b, s, s_cfg.n_groups, s_cfg.d_state),
                    hpg, axis=2)
    Ch = jnp.repeat(Cm.reshape(b, s, s_cfg.n_groups, s_cfg.d_state),
                    hpg, axis=2)

    xbar = xh * dt[..., None].astype(xh.dtype)
    log_a = dt * A
    y, _ = ssd_reference(xbar, log_a, Bh, Ch, chunk=min(s_cfg.chunk_size, s))
    y = y + xh * p["D"][:, None].astype(y.dtype)
    y = y.reshape(b, s, d_in)
    y = gated_norm(y, z, p["norm"], s_cfg.n_groups, cfg.norm_eps)
    return jnp.einsum("bsi,id->bsd", y, p["wo"])


def mamba2_decode(p, x, cfg, state):
    """One-token decode. x: (b,1,d)."""
    s_cfg = cfg.ssm
    b = x.shape[0]
    d_in, nh, _ = mamba2_dims(cfg)
    gn = s_cfg.n_groups * s_cfg.d_state

    z = jnp.einsum("bsd,di->bsi", x, p["wz"])[:, 0]
    xi = jnp.einsum("bsd,di->bsi", x, p["wx"])[:, 0]
    Bm = jnp.einsum("bsd,dn->bsn", x, p["wB"])[:, 0]
    Cm = jnp.einsum("bsd,dn->bsn", x, p["wC"])[:, 0]
    dt = jnp.einsum("bsd,dh->bsh", x, p["wdt"])[:, 0]

    conv_in = jnp.concatenate([xi, Bm, Cm], axis=-1)   # (b, conv_dim)
    window = jnp.concatenate([state["conv"], conv_in[:, None]], axis=1)
    conv_out = jnp.einsum("bkc,kc->bc", window, p["conv_w"]) + p["conv_b"]
    conv_out = jax.nn.silu(conv_out)
    new_conv = window[:, 1:]

    xi = conv_out[:, :d_in]
    Bm = conv_out[:, d_in:d_in + gn]
    Cm = conv_out[:, d_in + gn:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    a = jnp.exp(dt * A)                                # (b, nh)
    xh = xi.reshape(b, nh, s_cfg.head_dim).astype(jnp.float32)
    hpg = nh // s_cfg.n_groups
    Bh = jnp.repeat(Bm.reshape(b, s_cfg.n_groups, s_cfg.d_state), hpg, 1)
    Ch = jnp.repeat(Cm.reshape(b, s_cfg.n_groups, s_cfg.d_state), hpg, 1)
    Bh = Bh.astype(jnp.float32)

    xbar = xh * dt[..., None]
    new_ssm = (state["ssm"] * a[..., None, None]
               + jnp.einsum("bhp,bhn->bhpn", xbar, Bh))
    y = jnp.einsum("bhpn,bhn->bhp", new_ssm, Ch.astype(jnp.float32))
    y = y + xh * p["D"][:, None]
    y = y.reshape(b, 1, d_in).astype(x.dtype)
    y = gated_norm(y, z[:, None], p["norm"], s_cfg.n_groups, cfg.norm_eps)
    out = jnp.einsum("bsi,id->bsd", y, p["wo"])
    return out, {"conv": new_conv, "ssm": new_ssm}


# ===========================================================================
# RG-LRU (Griffin / RecurrentGemma recurrent block).
# ===========================================================================
def rglru_schema(cfg):
    d = cfg.d_model
    w = cfg.rglru.lru_width or d
    k = cfg.rglru.conv_width

    def lam_init(key, shape):
        # a = sigmoid(lam) ~ U(0.9, 0.999) as in Griffin
        u = jax.random.uniform(key, shape, jnp.float32, 0.9, 0.999)
        return jnp.log(u) - jnp.log1p(-u)

    return {
        "w_gate": ParamSpec((d, w), ("embed", "lru_width")),
        "w_in": ParamSpec((d, w), ("embed", "lru_width")),
        "conv_w": ParamSpec((k, w), (None, "lru_width")),
        "conv_b": ParamSpec((w,), ("lru_width",), "zeros"),
        "ra_w": ParamSpec((w,), ("lru_width",), "normal", dtype=jnp.float32),
        "ra_b": ParamSpec((w,), ("lru_width",), "zeros", dtype=jnp.float32),
        "ix_w": ParamSpec((w,), ("lru_width",), "normal", dtype=jnp.float32),
        "ix_b": ParamSpec((w,), ("lru_width",), "zeros", dtype=jnp.float32),
        "lam": ParamSpec((w,), ("lru_width",), lam_init, dtype=jnp.float32),
        "wo": ParamSpec((w, d), ("lru_width", "embed")),
    }


def _rglru_coeffs(p, u, cfg):
    """u: (..., w) fp32 -> (a, b) recurrence coefficients."""
    c = cfg.rglru.c_constant
    r = jax.nn.sigmoid(u * p["ra_w"] + p["ra_b"])
    i = jax.nn.sigmoid(u * p["ix_w"] + p["ix_b"])
    log_a = -c * r * jax.nn.softplus(p["lam"])
    a = jnp.exp(log_a)
    # sqrt(1 - a^2) input normalization (Griffin eq. 4)
    beta = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    return a, beta * (i * u)


def rglru_mix(p, x, cfg):
    """Griffin recurrent block. x: (b,s,d) -> (b,s,d)."""
    gate = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, p["w_gate"]))
    u = jnp.einsum("bsd,dw->bsw", x, p["w_in"])
    u = _causal_conv(u, p["conv_w"], p["conv_b"])
    a, bvec = _rglru_coeffs(p, u.astype(jnp.float32), cfg)

    if cfg.use_pallas:
        from repro.kernels.rglru import ops as rglru_ops
        h = rglru_ops.rglru_scan(jnp.log(jnp.maximum(a, 1e-37)), bvec,
                                 chunk=min(128, u.shape[1]),
                                 block_w=min(128, u.shape[2]))
    else:
        def combine(l, r):
            al, bl = l
            ar, br = r
            return al * ar, bl * ar + br

        _, h = lax.associative_scan(combine, (a, bvec), axis=1)
    h = h.astype(x.dtype)
    return jnp.einsum("bsw,wd->bsd", gate * h, p["wo"])


def rglru_decode(p, x, cfg, state):
    """One-token decode. x: (b,1,d)."""
    gate = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, p["w_gate"]))[:, 0]
    u = jnp.einsum("bsd,dw->bsw", x, p["w_in"])[:, 0]   # (b,w)
    window = jnp.concatenate([state["conv"], u[:, None]], axis=1)
    u = jnp.einsum("bkw,kw->bw", window, p["conv_w"]) + p["conv_b"]
    a, bvec = _rglru_coeffs(p, u.astype(jnp.float32), cfg)
    h = state["h"] * a + bvec
    out = jnp.einsum("bw,wd->bd", gate * h.astype(x.dtype), p["wo"])[:, None]
    return out, {"conv": window[:, 1:], "h": h}
