"""Jitted public wrapper for the SSD kernel (model layout (b,s,h,p))."""
from __future__ import annotations

import functools

import jax

from repro.kernels.ssd.kernel import ssd_bh


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(xbar, log_a, Bm, Cm, *, chunk=256, interpret=False):
    """xbar: (b,s,h,p); log_a: (b,s,h); Bm, Cm: (b,s,h,n).

    Returns (y (b,s,h,p), final_state=None) matching ssd_reference's
    calling convention (the kernel keeps state in VMEM; decode uses the
    O(1) recurrence in repro.models.ssm instead). Compiled for the TPU
    unless `interpret=True` (CPU tests).
    """
    b, s, h, p = xbar.shape
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, t.shape[-1])
    la = log_a.transpose(0, 2, 1).reshape(b * h, s)
    y = ssd_bh(fold(xbar), la, fold(Bm), fold(Cm), chunk=chunk,
               interpret=interpret)
    return y.reshape(b, h, s, p).transpose(0, 2, 1, 3), None
