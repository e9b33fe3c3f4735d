"""Jitted wrapper for the RG-LRU scan kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.rglru.kernel import rglru_scan_b


@functools.partial(jax.jit, static_argnames=("chunk", "block_w",
                                             "interpret"))
def rglru_scan(log_a, b, *, chunk=128, block_w=128, interpret=False):
    """h_t = exp(log_a_t) h_{t-1} + b_t over axis 1 of (B, S, W) inputs;
    compiled for the TPU unless `interpret=True` (CPU tests)."""
    return rglru_scan_b(log_a, b, chunk=chunk, block_w=block_w,
                        interpret=interpret)
