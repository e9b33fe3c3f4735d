"""Jitted wrappers: quantize/dequantize arbitrary-shaped tensors by
flattening to padded (nb, BLOCK) rows.

`use_pallas` selects the Pallas kernel, compiled for the TPU unless
`interpret=True` asks for the interpreter (CPU tests); otherwise the
pure-jnp reference codec runs."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.grad_quant import kernel as K
from repro.kernels.grad_quant import ref as R

BLOCK = 2048


def _pad_rows(x):
    flat = x.reshape(-1)
    n = flat.shape[0]
    nb = max((n + BLOCK - 1) // BLOCK, 1)
    flat = jnp.pad(flat, (0, nb * BLOCK - n))
    return flat.reshape(nb, BLOCK), n


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def quantize(x, use_pallas=False, interpret=False):
    """x: any shape -> (q int8 (nb, BLOCK), scales f32 (nb, 1))."""
    x2d, _ = _pad_rows(x)
    if use_pallas:
        return K.quantize_blocks(x2d, interpret=interpret)
    return R.quantize_blocks_ref(x2d)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "use_pallas",
                                             "interpret"))
def dequantize(q, scales, shape, dtype=jnp.float32, use_pallas=False,
               interpret=False):
    """(q, scales) from `quantize` -> an array of `shape` and `dtype`."""
    if use_pallas:
        x2d = K.dequantize_blocks(q, scales, dtype, interpret=interpret)
    else:
        x2d = R.dequantize_blocks_ref(q, scales, dtype)
    n = 1
    for d in shape:
        n *= d
    return x2d.reshape(-1)[:n].reshape(shape)
