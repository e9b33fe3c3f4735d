"""Jitted public wrapper for the flash-attention kernels.

Accepts model-layout tensors (B, S, N, H) (kv pre-expanded to N heads by
the attention layer) and runs the Pallas kernels, compiled for the TPU
unless `interpret=True` asks for the interpreter (CPU tests).

Differentiable: the forward kernel saves the folded q, k, v, its output
and each row's log-sum-exp; the VJP is a kernel pair on the same fold
(kernel.py: dk/dv over k blocks, dq over q blocks), which recomputes the
probabilities tile by tile in VMEM and skips the tiles the mask fills.
Both take the forward's block sizes. They are called through the jitted
wrappers `fa_bwd_dkv` and `fa_bwd_dq`, whose names the Pallas calls
take: a name starting with `flash_attention` marks a forward call.
Gradients are validated against jax.vjp of the reference in
tests/test_kernels.py::TestFlashAttentionGrad.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (flash_attention_bnh,
                                                  flash_bwd_dkv_bnh,
                                                  flash_bwd_dq_bnh)

_STATIC = ("causal", "window", "softcap", "block_q", "block_k", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def fa_bwd_dkv(q, k, v, do, lse, delta, **kw):
    """(dk, dv) of the folded attention (kernel.py `flash_bwd_dkv_bnh`)."""
    return flash_bwd_dkv_bnh(q, k, v, do, lse, delta, **kw)


@functools.partial(jax.jit, static_argnames=_STATIC)
def fa_bwd_dq(q, k, v, do, lse, delta, **kw):
    """dq of the folded attention (kernel.py `flash_bwd_dq_bnh`)."""
    return flash_bwd_dq_bnh(q, k, v, do, lse, delta, **kw)


def _fold(x):
    B, L, N, H = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * N, L, H)


def _unfold(x, B):
    BN, L, H = x.shape
    return x.reshape(B, BN // B, L, H).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fa(q, k, v, causal, window, softcap, block_q, block_k, interpret):
    return _fa_fwd(q, k, v, causal, window, softcap, block_q, block_k,
                   interpret)[0]


def _fa_fwd(q, k, v, causal, window, softcap, block_q, block_k, interpret):
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    out, lse = flash_attention_bnh(
        qf, kf, vf, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return _unfold(out, q.shape[0]), (qf, kf, vf, out, lse)


def _fa_bwd(causal, window, softcap, block_q, block_k, interpret,
            res, g):
    qf, kf, vf, out, lse = res
    B = g.shape[0]
    do = _fold(g)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]                 # (BN, 1, S)
    kw = dict(causal=causal, window=window, softcap=softcap,
              block_q=block_q, block_k=block_k, interpret=interpret)
    dk, dv = fa_bwd_dkv(qf, kf, vf, do, lse, delta, **kw)
    dq = fa_bwd_dq(qf, kf, vf, do, lse, delta, **kw)
    return _unfold(dq, B), _unfold(dk, B), _unfold(dv, B)


_fa.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.jit, static_argnames=_STATIC)
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    block_q=512, block_k=512, interpret=False):
    """q, k, v: (B, S|T, N, H) -> (B, S, N, H)."""
    return _fa(q, k, v, causal, window, softcap, block_q, block_k,
               interpret)
