"""Jitted public wrapper for the flash-attention kernel.

Accepts model-layout tensors (B, S, N, H) (kv pre-expanded to N heads by
the attention layer) and runs the Pallas kernel, compiled for the TPU
unless `interpret=True` asks for the interpreter (CPU tests).

Differentiable: forward runs the Pallas kernel; the VJP recomputes
attention with the reference path (flash-backward kernels are a logged
follow-up — forward is where the O(S^2) memory win lives; the backward
recompute is remat-equivalent and numerically validated in
tests/test_kernels.py::TestFlashAttentionGrad).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bnh
from repro.kernels.flash_attention.ref import reference_attention


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fa(q, k, v, causal, window, softcap, block_q, block_k, interpret):
    B, S, N, H = q.shape
    T = k.shape[1]
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * N, x.shape[1], H)
    out = flash_attention_bnh(
        fold(q), fold(k), fold(v), causal=causal, window=window,
        softcap=softcap, block_q=min(block_q, S), block_k=min(block_k, T),
        interpret=interpret)
    return out.reshape(B, N, S, H).transpose(0, 2, 1, 3)


def _fa_fwd(q, k, v, causal, window, softcap, block_q, block_k, interpret):
    return _fa(q, k, v, causal, window, softcap, block_q, block_k,
               interpret), (q, k, v)


def _fa_bwd(causal, window, softcap, block_q, block_k, interpret,
            res, g):
    q, k, v = res
    B, S, N, H = q.shape
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * N, x.shape[1], H)

    def ref(qf, kf, vf):
        return reference_attention(qf, kf, vf, causal=causal,
                                   window=window, softcap=softcap)

    _, vjp = jax.vjp(ref, fold(q), fold(k), fold(v))
    dq, dk, dv = vjp(fold(g))
    unfold = lambda x: x.reshape(B, N, x.shape[1], H).transpose(0, 2, 1, 3)
    return unfold(dq), unfold(dk), unfold(dv)


_fa.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    block_q=512, block_k=512, interpret=False):
    """q, k, v: (B, S|T, N, H) -> (B, S, N, H)."""
    return _fa(q, k, v, causal, window, softcap, block_q, block_k,
               interpret)
