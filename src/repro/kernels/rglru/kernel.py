"""Pallas TPU kernel for the RG-LRU linear recurrence (Griffin /
RecurrentGemma): h_t = a_t * h_{t-1} + b_t, per channel.

Same TPU-native structure as the SSD kernel: the sequence is chunked and
the inter-chunk carry lives in VMEM scratch across the sequential chunk
grid dimension. Inside a chunk the recurrence is a Hillis-Steele scan
over the affine maps h -> a h + b: log2(Q) steps, each composing every
row with the row 2^k above it (a sublane rotate), so the chunk is
computed in registers with no cumsum and no (Q, Q, W) decay tensor.
After the scan row t holds (prod a, accumulated b) over rows <= t, and

  h_t = A_t * h_in + B_t.

Grid: (batch, w_blocks, n_chunks), chunks innermost.
BlockSpec tiles (VMEM): a, b, h: (1, Q, WB); carry scratch (1, WB).
Q=128, WB=128: each operand is 16 vregs of f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(loga_ref, b_ref, h_ref, carry_scr, *, chunk):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        carry_scr[...] = jnp.zeros_like(carry_scr)

    a = jnp.exp(loga_ref[0].astype(jnp.float32))     # (Q, WB)
    b = b_ref[0].astype(jnp.float32)                 # (Q, WB)
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    k = 1
    while k < chunk:
        has_prev = row >= k
        a_prev = jnp.where(has_prev, pltpu.roll(a, k, 0), 1.0)
        b_prev = jnp.where(has_prev, pltpu.roll(b, k, 0), 0.0)
        b = a * b_prev + b
        a = a * a_prev
        k *= 2

    h = a * carry_scr[...] + b
    h_ref[0] = h.astype(h_ref.dtype)
    carry_scr[...] = h[chunk - 1:chunk, :]


def rglru_scan_b(log_a, b, *, chunk=128, block_w=128, interpret=False):
    """log_a, b: (B, S, W) -> h: (B, S, W) with h_t = e^{log_a_t} h_{t-1} + b_t."""
    B, S, W = log_a.shape
    chunk = min(chunk, S)
    block_w = min(block_w, W)
    assert S % chunk == 0 and W % block_w == 0, (S, W, chunk, block_w)
    nc = S // chunk
    nw = W // block_w

    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, nw, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda bi, wi, ci: (bi, ci, wi)),
            pl.BlockSpec((1, chunk, block_w), lambda bi, wi, ci: (bi, ci, wi)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_w),
                               lambda bi, wi, ci: (bi, ci, wi)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), b.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
    )(log_a, b)
