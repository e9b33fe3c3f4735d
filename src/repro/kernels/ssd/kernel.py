"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Implements the state-space-duality decomposition (intra-chunk quadratic
block + inter-chunk linear recurrence) with the recurrent state carried in
VMEM scratch across the sequential chunk grid dimension:

Grid: (batch*heads, n_chunks) — chunks innermost, executed in order on a
TPU core, so the (head_dim, d_state) state tile never leaves VMEM between
chunks (the GPU formulation materializes all chunk states in HBM and runs
a separate scan kernel; on TPU the sequential grid makes that round trip
unnecessary).

BlockSpec tiling per grid step (VMEM):
  x      : (1, Q, P)      inputs (already dt-scaled)
  la_row : (1, 1, Q)      dt * A (log decay), lanes
  la_col : (1, Q, 1)      the same values, sublanes
  B, C   : (1, Q, N)      input/output projections
  y      : (1, Q, P)      output
  state  : (P, N) f32     scratch, persists across chunks
Q=chunk (256), P=head_dim (64), N=d_state (128): ~0.5MB — VMEM-friendly,
and the (Q,Q) intra-chunk score tile is 256x256 (MXU-aligned). The log
decay comes in both orientations because the chunk's inclusive cumulative
sum is needed as a row and as a column; each is a triangular masked sum
of the other orientation (Mosaic has no cumsum), exact in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _ssd_kernel(x_ref, lar_ref, lac_ref, b_ref, c_ref, y_ref, state_scr, *,
                chunk):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    la_row = lar_ref[0].astype(jnp.float32)   # (1, Q)
    la_col = lac_ref[0].astype(jnp.float32)   # (Q, 1)
    Bm = b_ref[0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)         # (Q, N)

    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    qj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = qi >= qj
    # inclusive cumulative sums: cs_col[i] = sum_{j<=i} la[j], as a
    # column (from the lane layout) and as a row (from the sublane one)
    cs_col = jnp.sum(jnp.where(lower, la_row, 0.0), axis=1, keepdims=True)
    cs_row = jnp.sum(jnp.where(qi <= qj, la_col, 0.0), axis=0,
                     keepdims=True)
    total = jnp.sum(la_row, axis=1, keepdims=True)        # (1, 1)

    # intra-chunk: L[i,j] = exp(cs[i] - cs[j]) for i >= j
    L = jnp.exp(jnp.where(lower, cs_col - cs_row, -jnp.inf))
    att = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                              precision=_HI,
                              preferred_element_type=jnp.float32)  # (Q,Q)
    y = jax.lax.dot_general(att * L, x, (((1,), (0,)), ((), ())),
                            precision=_HI,
                            preferred_element_type=jnp.float32)    # (Q,P)
    # contribution of the carried state: C_i . state * exp(cs_i)
    state = state_scr[...]                     # (P, N)
    y += jnp.exp(cs_col) * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: state' = a_chunk * state + sum_j decay_j * x_j B_j^T
    xw = x * jnp.exp(total - cs_col)           # (Q, P)
    new_state = jax.lax.dot_general(
        xw, Bm, (((0,), (0,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)    # (P, N)
    state_scr[...] = jnp.exp(total) * state + new_state


def ssd_bh(x, la, Bm, Cm, *, chunk=256, interpret=False):
    """x: (BH, S, P); la: (BH, S); Bm, Cm: (BH, S, N) -> y (BH, S, P)."""
    BH, S, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, la[:, None, :], la[:, :, None], Bm, Cm)
