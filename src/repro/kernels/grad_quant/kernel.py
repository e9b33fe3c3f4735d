"""Pallas TPU kernels: per-block int8 symmetric (de)quantization.

Used by the FedAvg barrier of `repro.fl.training` (`fedavg` under
`quantize=True`): each participant's per-leaf delta is quantized to int8
+ one f32 scale per block of BLOCK consecutive elements and dequantized
before the weighted average, so the global model takes the same update
the int8 upload (`comms/payload.py`) carries.

The kernels work on a row view `(..., R, C)` of the data, matrices of
whole blocks: C is a multiple of SEG (half a block), and the blocks are
the row-major flattening's consecutive runs of BLOCK elements. With C a
multiple of BLOCK, each row holds C / BLOCK whole blocks; otherwise C is
an odd number of SEGs and each pair of rows holds C / SEG blocks, one of
which spans the end of the even row and the start of the odd one.
`(nb, BLOCK)` is the view with one block per row. Scales come as
`(..., R, K)`, K = ceil(C / BLOCK): the blocks that start or end in each
row, left to right (in the odd case the spanning block appears in both
rows of its pair).

Quantize takes the view of x, or the views of `new` and `old` and
quantizes their difference in fp32, so that a delta is formed in VMEM
and never written to HBM. Per block: scale = max(max|x|, 1e-12) / 127,
codes = clip(round(x / scale), -127, 127); the block's max is taken
over its SEG-wide halves, which is exact.

Grid: one program per tile of r rows of one matrix, r a multiple of 32
(the int8 sublane tile) holding about ROWS * BLOCK elements, or all R
rows. Each block spec obeys the TPU (8, 128) tiling rule: every SEG
slice is 8 x 128 lanes and the scale block `(r, K)` spans the array's
full last dim. A final partial tile is padded by Pallas; its padded rows
(an even number, so pairs stay whole) are written nowhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 2048        # elements per scale
SEG = BLOCK // 2    # a row view's width is a multiple of SEG
ROWS = 256          # rows per grid step at width BLOCK: 2 MiB of f32 input


def _seg_scales(scales, segs, rows):
    """The scale of each SEG slice of a tile's rows: `scales` holds the
    tile's K scale columns, each (rows, 1)."""
    if segs % 2 == 0:
        return [scales[s // 2] for s in range(segs)]
    odd = lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % 2 == 1
    return [scales[s // 2] if s // 2 == (s + 1) // 2 else
            jnp.where(odd, scales[(s + 1) // 2], scales[s // 2])
            for s in range(segs)]


def _quant_kernel(*refs):
    *x_refs, q_ref, s_ref = refs
    rows, width = q_ref.shape
    segs = width // SEG

    def x(s):
        cols = pl.ds(s * SEG, SEG)
        v = x_refs[0][:, cols].astype(jnp.float32)
        return v - x_refs[1][:, cols].astype(jnp.float32) \
            if len(x_refs) == 2 else v

    m = [jnp.max(jnp.abs(x(s)), axis=1, keepdims=True) for s in range(segs)]
    if segs % 2 == 0:
        amax = [jnp.maximum(m[2 * j], m[2 * j + 1])
                for j in range(segs // 2)]
    else:
        # the spanning block: the even row's last SEG and the odd row's
        # first (rows wrapped round the tile's ends are never selected)
        span_even = jnp.maximum(m[-1], pltpu.roll(m[0], rows - 1, 0))
        span_odd = jnp.maximum(pltpu.roll(m[-1], 1, 0), m[0])
        odd = lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % 2 == 1
        k = (segs + 1) // 2
        even_cols = [jnp.maximum(m[2 * j], m[2 * j + 1])
                     for j in range(k - 1)] + [span_even]
        odd_cols = [span_odd] + [jnp.maximum(m[2 * j - 1], m[2 * j])
                                 for j in range(1, k)]
        amax = [jnp.where(odd, a, b) for b, a in zip(even_cols, odd_cols)]
    scales = [jnp.maximum(a, 1e-12) / 127.0 for a in amax]
    for j, scale in enumerate(scales):
        s_ref[:, pl.ds(j, 1)] = scale
    for s, scale in enumerate(_seg_scales(scales, segs, rows)):
        q = jnp.clip(jnp.round(x(s) / scale), -127.0, 127.0)
        q_ref[:, pl.ds(s * SEG, SEG)] = q.astype(jnp.int32).astype(jnp.int8)


def _dequant_kernel(q_ref, s_ref, x_ref):
    rows, width = q_ref.shape
    scales = [s_ref[:, pl.ds(j, 1)] for j in range(s_ref.shape[1])]
    for s, scale in enumerate(_seg_scales(scales, width // SEG, rows)):
        cols = pl.ds(s * SEG, SEG)
        q = q_ref[:, cols].astype(jnp.int32).astype(jnp.float32)
        x_ref[:, cols] = (q * scale).astype(x_ref.dtype)


def _scale_cols(width: int) -> int:
    """K: scale columns of a row view `width` wide."""
    if width % SEG:
        raise ValueError(f"row width {width} is not a multiple of {SEG}")
    return -(-width // BLOCK)


def _tiling(shape):
    """Block shape, grid and index map of a row view `(..., R, C)`: one
    program per tile of r rows of one `(R, C)` matrix."""
    *lead, rows, width = shape
    r = min(rows, max(32, ROWS * BLOCK // width // 32 * 32))
    return ((None,) * len(lead) + (r,), (*lead, pl.cdiv(rows, r)),
            lambda *i: (*i, 0))


def quantize_blocks(x, old=None, *, interpret=False):
    """x: a row view (..., R, C) -> (int8 codes (..., R, C), f32 scales
    (..., R, K)). With `old` (same shape, any float dtype) the codes are
    those of `x - old` in fp32. `(nb, BLOCK)` gives `(nb, 1)` scales."""
    *lead, rows, width = x.shape
    k = _scale_cols(width)
    tile, grid, at = _tiling(x.shape)
    xs = (x,) if old is None else (x, old)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(tile + (width,), at)] * len(xs),
        out_specs=[
            pl.BlockSpec(tile + (width,), at),
            pl.BlockSpec(tile + (k,), at),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, jnp.int8),
            jax.ShapeDtypeStruct((*lead, rows, k), jnp.float32),
        ],
        interpret=interpret,
    )(*xs)


def dequantize_blocks(q, scales, out_dtype=jnp.float32, *,
                      interpret=False):
    """Inverse of `quantize_blocks`: (..., R, C) int8 x (..., R, K)
    scales."""
    k = _scale_cols(q.shape[-1])
    tile, grid, at = _tiling(q.shape)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(tile + (q.shape[-1],), at),
            pl.BlockSpec(tile + (k,), at),
        ],
        out_specs=pl.BlockSpec(tile + (q.shape[-1],), at),
        out_shape=jax.ShapeDtypeStruct(q.shape, out_dtype),
        interpret=interpret,
    )(q, scales)
