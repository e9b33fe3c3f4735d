"""Jitted wrappers: quantize/dequantize arbitrary-shaped tensors.

`quantize` flattens a tensor to padded `(nb, BLOCK)` rows, one block per
row: the wire layout `comms/payload.py` bills.

`quantize_delta` runs the same codec on a leaf's row view (`rows`), for
the FedAvg barrier: the codes and scales of `new - old`, the fp32 delta
formed inside the kernel. `dequantize` takes either layout. The view is
chosen from the leaf's shape alone: where the leaf's size is a whole
number of blocks and a trailing run of its dims multiplies to a multiple
of SEG (at most MAX_WIDTH), the view keeps the leaf's dims before that
run, then the dim just before it as rows and the run's product as the
row width C (a free reshape of a row-major leaf); else it is
`(nb, BLOCK)`, zero-padded. `blocks` turns row-view codes and scales
into `quantize`'s layout: they are the same numbers.

`use_pallas` selects the Pallas kernel, compiled for the TPU unless
`interpret=True` asks for the interpreter (CPU tests); otherwise the
pure-jnp reference codec runs."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.grad_quant import kernel as K
from repro.kernels.grad_quant import ref as R

BLOCK = K.BLOCK
MAX_WIDTH = 16 * K.SEG     # widest row view: 32 rows of it fill a tile


def _flat_view(n: int):
    """`(nb, BLOCK)`: one block per row."""
    return (max(-(-n // BLOCK), 1), BLOCK)


def _fill(x, view):
    """x's row-major elements in `view`, zero-padded at the end."""
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, math.prod(view) - flat.shape[0])).reshape(view)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def quantize(x, use_pallas=False, interpret=False):
    """x: any shape -> (q int8 (nb, BLOCK), scales f32 (nb, 1))."""
    x2d = _fill(x, _flat_view(x.size))
    if use_pallas:
        return K.quantize_blocks(x2d, interpret=interpret)
    return R.quantize_blocks_ref(x2d)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "use_pallas",
                                             "interpret"))
def dequantize(q, scales, shape, dtype=jnp.float32, use_pallas=False,
               interpret=False):
    """(q, scales) from `quantize`, or a row view's from `quantize_delta`
    -> an array of `shape` and `dtype` (the row view's own shape keeps
    the view)."""
    if use_pallas:
        x = K.dequantize_blocks(q, scales, dtype, interpret=interpret)
    else:
        x = R.dequantize_blocks_ref(*blocks(q, scales), dtype)
    return unrows(x, shape)


def row_view(shape):
    """The shape of a leaf's row view (see the module docstring)."""
    n = math.prod(shape)
    if n and n % BLOCK == 0:
        width = 1
        for k in range(len(shape) - 1, 0, -1):
            width *= shape[k]
            if width > MAX_WIDTH:
                break
            if width % K.SEG == 0:
                if shape[k - 1] * width % BLOCK:
                    break
                return (*shape[:k], width)
    return _flat_view(n)


def rows(x):
    """A leaf's row view `(..., R, C)`, zero-padded to whole blocks."""
    return _fill(x, row_view(x.shape))


def unrows(x, shape):
    """Inverse of `rows`: the leaf of `shape` in a row view."""
    return x.reshape(-1)[:math.prod(shape)].reshape(shape)


def _row_scales(scales, view):
    """(nb, 1) block scales -> the (..., R, K) scales of a row view."""
    *lead, n_rows, width = view
    segs = width // K.SEG
    if segs % 2 == 0:
        return scales.reshape(*lead, n_rows, segs // 2)
    k = (segs + 1) // 2
    pairs = scales.reshape(*lead, n_rows // 2, segs)
    return jnp.stack([pairs[..., :k], pairs[..., k - 1:]], -2).reshape(
        *lead, n_rows, k)


def blocks(q, scales):
    """Row-view codes and scales -> `quantize`'s (nb, BLOCK) codes and
    (nb, 1) scales."""
    *lead, n_rows, width = q.shape
    if (width // K.SEG) % 2:
        pairs = scales.reshape(*lead, n_rows // 2, 2, scales.shape[-1])
        scales = jnp.concatenate([pairs[..., 0, :], pairs[..., 1, 1:]], -1)
    return q.reshape(-1, BLOCK), scales.reshape(-1, 1)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def quantize_delta(new, old, use_pallas=False, interpret=False):
    """Row views of a leaf's new and old values -> the int8 codes
    (..., R, C) and f32 scales (..., R, K) of `new - old` in fp32;
    `blocks` of them equal `quantize(new.astype(f32) - old.astype(f32))`."""
    if use_pallas:
        return K.quantize_blocks(new, old, interpret=interpret)
    d = new.astype(jnp.float32) - old.astype(jnp.float32)
    q, scales = R.quantize_blocks_ref(d.reshape(-1, BLOCK))
    return q.reshape(new.shape), _row_scales(scales, new.shape)
