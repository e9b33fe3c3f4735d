"""Pallas TPU kernels: per-block int8 symmetric (de)quantization.

Used by the compressed cross-pod FedAvg collective (repro.fl.mesh_fl):
client deltas are quantized to int8 + one f32 scale per block before the
ring collective-permute, cutting cross-pod ICI traffic ~4x vs f32 (2x vs
bf16) — the beyond-paper distributed-optimization trick.

Wire layout (what `comms/payload.py` bills): int8 values `(nb, BLOCK)`
plus one f32 scale per block row, `(nb, 1)`.

Grid: one program per tile of `r = min(nb, ROWS)` block rows. Each step
loads an `(r, BLOCK)` tile into VMEM, reduces |max| along each row,
scales and rounds. Every block spec obeys the TPU (8, 128) tiling rule:
`r` is ROWS, a multiple of 32 (the int8 sublane tile), or the whole
array; BLOCK=2048 is 16 x 128 lanes; and the scale column `(r, 1)` spans
the array's full last dim. A final partial tile is padded by Pallas; its
padded rows are independent rows whose writes are dropped.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 256          # block rows per grid step: 2 MiB of f32 input


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                   # (r, BLOCK)
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True), 1e-12)
    scale = amax / 127.0                                 # (r, 1)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int32).astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    q = q_ref[...].astype(jnp.int32).astype(jnp.float32)
    x_ref[...] = (q * s_ref[...]).astype(x_ref.dtype)


def _tiling(nb: int):
    r = min(nb, ROWS)
    return r, (pl.cdiv(nb, r),)


def quantize_blocks(x2d, *, interpret=False):
    """x2d: (nb, BLOCK) -> (int8 (nb, BLOCK), f32 scales (nb, 1))."""
    nb, block = x2d.shape
    r, grid = _tiling(nb)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((r, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((r, block), lambda i: (i, 0)),
            pl.BlockSpec((r, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2d)


def dequantize_blocks(q2d, scales, out_dtype=jnp.float32, *,
                      interpret=False):
    """Inverse of `quantize_blocks`: (nb, BLOCK) int8 x (nb, 1) scales."""
    nb, block = q2d.shape
    r, grid = _tiling(nb)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((r, block), lambda i: (i, 0)),
            pl.BlockSpec((r, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((r, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), out_dtype),
        interpret=interpret,
    )(q2d, scales)
