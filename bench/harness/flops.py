"""Operations and bytes the algorithms need, counted from shapes.

These counts are the benchmark's, not the program's: MFU and every
roofline share divide them by measured time, so a kernel that skips work
raises its share and one that counts work twice cannot pass 100 %.
Counts follow the work the algorithm needs whatever implements it:
causal attention counts half the square, recomputation counts nothing,
and the embedding lookup is not a matmul.

A configuration's reference (bench/reference/<ref>.py) counts one
training step of its own model with `step_flops(model, batch, seq)`,
from its own parameter specs and the helpers here; nothing here knows a
layer kind. Rules for the configurations to come: routed experts count
each token once for every expert it is sent to among those held here,
at the expected share top_k x held / num_experts of the tokens; capacity
padding and dropped slots count nothing; a shared expert counts every
token.
"""
from __future__ import annotations

import math


def train_step_flops(matmul_params: int, tokens: int,
                     mix_fwd: float) -> float:
    """Model FLOPs of one training step (forward and backward): 6 N per
    token over the `matmul_params` each token meets, plus three times
    the forward sequence-mixing work `mix_fwd` that no parameter count
    covers."""
    return 6.0 * matmul_params * tokens + 3.0 * mix_fwd


def causal_attention_fwd_flops(batch: int, seq: int, num_heads: int,
                               head_dim: int) -> float:
    """Causal score and value products of one attention layer, forward:
    half of the 4 * S^2 * N * H of the full square."""
    return 2.0 * batch * seq * seq * num_heads * head_dim


def ssd_fwd_flops(batch: int, seq: int, *, chunk: int, d_state: int,
                  n_groups: int, num_heads: int, head_dim: int) -> float:
    """The SSD chunked scan of one Mamba-2 layer, forward (arXiv
    2405.21060 §6): per chunk of Q, the causal C.B products (Q^2 N G),
    their product with the inputs (Q^2 H P), and the chunk states in and
    out (4 Q H P N); the state passing between chunks is left out."""
    q = min(chunk, seq)
    chunks = seq // q
    n, g, h, p = d_state, n_groups, num_heads, head_dim
    per_chunk = q * q * n * g + q * q * h * p + 4 * q * h * p * n
    return float(batch * chunks * per_chunk)


def round_flops_per_client(ref, model, traffic) -> float:
    """Model FLOPs one client does in one FL round, as the
    configuration's reference module `ref` counts a training step."""
    return traffic["local_steps"] * ref.step_flops(
        model, traffic["batch"], traffic["seq"])


def flash_fwd_cost(model, batch: int, seq: int, itemsize: int = 2):
    """(FLOPs, bytes) one causal flash-attention forward call needs:
    the causal half of the products; q and o at the query heads, k and
    v once at the key-value heads (GQA shares them, whatever the program
    repeats before the kernel)."""
    nq, nk = model["num_heads"], model["num_kv_heads"]
    h = model.get("head_dim") or model["d_model"] // nq
    flops = causal_attention_fwd_flops(batch, seq, nq, h)
    nbytes = 2.0 * batch * seq * (nq + nk) * h * itemsize
    return flops, nbytes


CODEC_BLOCK = 2048


def codec_bytes(n: int) -> float:
    """HBM bytes the int8 block codec needs for an n-element fp32 delta:
    quantize reads 4n and writes n codes and one fp32 scale per block of
    2048; dequantize reads those and writes 4n."""
    blocks = math.ceil(n / CODEC_BLOCK)
    return 10.0 * n + 8.0 * blocks


def least_time(flops: float, nbytes: float, peak_flops: float,
               hbm_bw: float):
    """(seconds, bound) of the roofline: the larger of the two terms."""
    tc, tm = flops / peak_flops, nbytes / hbm_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
