"""Pallas TPU flash attention (causal / sliding-window, online softmax),
forward and backward.

Forward grid: (batch*heads, q_blocks, k_blocks), k innermost — TPU
executes the grid sequentially per core, so the running max / denominator
/ output accumulator live in VMEM scratch across k-block steps and the
HBM footprint is O(seq * head_dim), never O(seq^2). Besides the output
it writes each row's log-sum-exp (m + log l), the one residual the
backward needs beyond q, k, v and the output.

BlockSpec tiling (per grid step, all VMEM):
  q   : (1, block_q, head_dim)
  k,v : (1, block_k, head_dim)
  out : (1, block_q, head_dim)        written at the last k block
  lse : (1, 1, block_q)               a row, written with out
With block_q = block_k = 512 and head_dim<=256 the working set is
<= 4 * 512*256*4B = 2MB — comfortably inside a v5e core's VMEM, and the
512x512 f32 score tile keeps the MXU shape-aligned (multiples of 128).

Backward (FA2): two kernels on the same fold, each recomputing the
probabilities tile by tile in VMEM as P = exp(s - lse), with
delta = rowsum(dO * o):
  dk/dv: grid (batch*heads, k_blocks, q_blocks), q innermost; works on
         transposed tiles (k rows, q columns), so lse and delta enter
         as rows: dv += P^T dO, dk += dS^T q.
  dq:    grid (batch*heads, q_blocks, k_blocks), k innermost; dq += dS k,
         with lse and delta turned into columns once per q block.
Tiles that the causal or window mask fills entirely do no work, and
their grid steps point at the block of a live neighbour so that nothing
is copied in for them. P and dS stay fp32 and enter their products as
fp32; nothing of size S x T reaches HBM.

Validated against ref.reference_attention (values) and its jax.vjp
(gradients) in interpret mode (tests sweep shapes, dtypes, block
sizes, causal/window/softcap).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _mask(qpos, kpos, causal, window):
    mask = jnp.ones(qpos.shape, jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask


def _row_to_column(row):
    """(1, n) -> (n, 1), through a lane-aligned 2-D transpose."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, row.shape[1])))[:, :1]


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr,
                  *, scale, causal, window, softcap,
                  block_q, block_k, n_k_blocks):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                    # (bq, h)
    k = k_ref[0].astype(jnp.float32)                    # (bk, h)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # (bq, bk)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    s = jnp.where(_mask(qpos, kpos, causal, window), s, NEG_INF)

    m_old = m_scr[...]                                   # (bq, 1)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new)                               # (bq, bk)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(denom)                # (bq, 1)
        lse_ref[0] = jnp.transpose(
            jnp.broadcast_to(lse, (block_q, LANES)))[:1, :]


def flash_attention_bnh(q, k, v, *, causal=True, window=None, softcap=None,
                        block_q=512, block_k=512, interpret=False):
    """q: (BN, S, H); k, v: (BN, T, H) — heads pre-folded into batch.

    Returns (out (BN, S, H) in q's dtype, lse (BN, 1, S) fp32)."""
    BN, S, H = q.shape
    T = k.shape[1]
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    n_q = S // block_q
    n_k = T // block_k
    scale = 1.0 / math.sqrt(H)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, n_k_blocks=n_k)

    return pl.pallas_call(
        kernel,
        grid=(BN, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, H), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, H), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((BN, S, H), q.dtype),
                   jax.ShapeDtypeStruct((BN, 1, S), jnp.float32)],
        scratch_shapes=[
            # running max, denominator, accumulator — persist across the
            # innermost (k) grid dimension
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, H), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward.
# ---------------------------------------------------------------------------
def _q_blocks(j, *, causal, window, block_q, block_k, n_q):
    """First and last q block that k block `j` is live in (the first may
    lie past the last where none is)."""
    lo = (j * block_k) // block_q if causal else 0
    hi = n_q - 1
    if window is not None:
        hi = jnp.minimum(hi, (j * block_k + block_k + window - 2) // block_q)
    return lo, hi


def _k_blocks(i, *, causal, window, block_q, block_k, n_k):
    """First and last k block that q block `i` is live in."""
    lo = 0
    if window is not None:
        lo = jnp.maximum(i * block_q - window + 1, 0) // block_k
    hi = n_k - 1
    if causal:
        hi = jnp.minimum(hi, (i * block_q + block_q - 1) // block_k)
    return lo, hi


def _clamp(x, lo, hi):
    """`x` held to [lo, hi], and to hi where lo > hi: a dead step points
    at a live step's block (or the last block), so none is copied in."""
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _scores(a, b, scale, softcap):
    """(s, tanh) of a b^T * scale, capped: `tanh` feeds the cap's
    derivative (None without a cap)."""
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is None:
        return s, None
    t = jnp.tanh(s / softcap)
    return softcap * t, t


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, window, softcap, block_q, block_k, n_q):
    j = pl.program_id(1)
    i = pl.program_id(2)
    lo, hi = _q_blocks(j, causal=causal, window=window, block_q=block_q,
                       block_k=block_k, n_q=n_q)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when((i >= lo) & (i <= hi))
    def _run():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s, t = _scores(k, q, scale, softcap)             # (bk, bq)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0)
        qpos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1)
        s = jnp.where(_mask(qpos, kpos, causal, window), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])                      # P^T
        dv_scr[...] += jax.lax.dot_general(
            p, do.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])                     # dS^T
        if t is not None:
            ds = ds * (1.0 - t * t)
        dk_scr[...] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == n_q - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, lse_scr, delta_scr,
               *, scale, causal, window, softcap, block_q, block_k, n_k):
    i = pl.program_id(1)
    j = pl.program_id(2)
    lo, hi = _k_blocks(i, causal=causal, window=window, block_q=block_q,
                       block_k=block_k, n_k=n_k)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        lse_scr[...] = _row_to_column(lse_ref[0])
        delta_scr[...] = _row_to_column(delta_ref[0])

    @pl.when((j >= lo) & (j <= hi))
    def _run():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s, t = _scores(q, k, scale, softcap)             # (bq, bk)
        qpos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(_mask(qpos, kpos, causal, window), s, NEG_INF)
        p = jnp.exp(s - lse_scr[...])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_scr[...])
        if t is not None:
            ds = ds * (1.0 - t * t)
        dq_scr[...] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_k - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_shapes(q, k, block_q, block_k):
    BN, S, H = q.shape
    T = k.shape[1]
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    return BN, S, T, H, block_q, block_k, 1.0 / math.sqrt(H)


def flash_bwd_dkv_bnh(q, k, v, do, lse, delta, *, causal=True, window=None,
                      softcap=None, block_q=512, block_k=512,
                      interpret=False):
    """(dk, dv), each (BN, T, H) in k's dtype. q, do: (BN, S, H); k, v:
    (BN, T, H); lse, delta: (BN, 1, S) fp32."""
    BN, S, T, H, block_q, block_k, scale = _bwd_shapes(q, k, block_q,
                                                       block_k)
    n_q, n_k = S // block_q, T // block_k
    blocks = functools.partial(_q_blocks, causal=causal, window=window,
                               block_q=block_q, block_k=block_k, n_q=n_q)

    def q_block(j, i):
        return _clamp(i, *blocks(j))

    kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, n_q=n_q)
    q_spec = pl.BlockSpec((1, block_q, H),
                          lambda b, j, i: (b, q_block(j, i), 0))
    k_spec = pl.BlockSpec((1, block_k, H), lambda b, j, i: (b, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q),
                            lambda b, j, i: (b, 0, q_block(j, i)))
    return pl.pallas_call(
        kernel,
        grid=(BN, n_k, n_q),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct((BN, T, H), k.dtype),
                   jax.ShapeDtypeStruct((BN, T, H), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, H), jnp.float32),
                        pltpu.VMEM((block_k, H), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def flash_bwd_dq_bnh(q, k, v, do, lse, delta, *, causal=True, window=None,
                     softcap=None, block_q=512, block_k=512,
                     interpret=False):
    """dq (BN, S, H) in q's dtype; arguments as `flash_bwd_dkv_bnh`."""
    BN, S, T, H, block_q, block_k, scale = _bwd_shapes(q, k, block_q,
                                                       block_k)
    n_q, n_k = S // block_q, T // block_k
    blocks = functools.partial(_k_blocks, causal=causal, window=window,
                               block_q=block_q, block_k=block_k, n_k=n_k)

    def k_block(i, j):
        return _clamp(j, *blocks(i))

    kernel = functools.partial(
        _dq_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, n_k=n_k)
    q_spec = pl.BlockSpec((1, block_q, H), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, block_k, H),
                          lambda b, i, j: (b, k_block(i, j), 0))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
    return pl.pallas_call(
        kernel,
        grid=(BN, n_q, n_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((BN, S, H), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, H), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
