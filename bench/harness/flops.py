"""Operations and bytes the algorithms need, counted from shapes.

These counts are the benchmark's, not the program's: MFU and every
roofline share divide them by measured time, so a kernel that skips work
raises its share and one that counts work twice cannot pass 100 %.
Counts follow the work the algorithm needs whatever implements it:
causal attention counts half the square, recomputation counts nothing,
and the embedding lookup is not a matmul.

`model` is the "model" object of a configuration file (bench/configs).
"""
from __future__ import annotations

import math


def _ssm(model):
    s = model["ssm"]
    d_in = s["expand"] * model["d_model"]
    nh = d_in // s["head_dim"]
    conv_dim = d_in + 2 * s["n_groups"] * s["d_state"]
    return s, d_in, nh, conv_dim


def layer_params(model, kind: str) -> int:
    """Parameters of one layer of `kind` ("attn" or "mamba2")."""
    d = model["d_model"]
    if kind == "attn":
        h = model.get("head_dim") or d // model["num_heads"]
        nq, nk = model["num_heads"], model["num_kv_heads"]
        attn = 2 * d * nq * h + 2 * d * nk * h
        f = model["d_ff"]
        mlp = (3 if model["mlp_kind"] == "swiglu" else 2) * d * f
        return attn + mlp + 2 * d                   # + two norm scales
    if kind == "mamba2":
        s, d_in, nh, conv_dim = _ssm(model)
        gn = s["n_groups"] * s["d_state"]
        return (d * (2 * d_in + 2 * gn + nh)        # z, x, B, C, dt
                + s["conv_width"] * conv_dim + conv_dim
                + 3 * nh                            # A_log, dt_bias, D
                + d_in                              # gated norm scale
                + d_in * d                          # out projection
                + d)                                # pre-norm scale
    raise ValueError(f"no count for layer kind {kind!r}")


def layer_kinds(model):
    """The kind of every layer, in order."""
    pat = list(model["pattern"])
    n = model["num_layers"]
    return (pat * (n // len(pat)) + pat[:n % len(pat)])


def total_params(model) -> int:
    """Every parameter of the model."""
    d, v = model["d_model"], model["vocab_size"]
    head = 0 if model["tie_embeddings"] else d * v
    return (v * d + d + head
            + sum(layer_params(model, k) for k in layer_kinds(model)))


def matmul_params(model) -> int:
    """Parameters that multiply activations: all but the embedding table
    when the head is separate; all of them when the head is the tied
    table (its lookup is free, its product with the activations not)."""
    d, v = model["d_model"], model["vocab_size"]
    return total_params(model) - (0 if model["tie_embeddings"] else v * d)


def attention_fwd_flops(model, batch: int, seq: int) -> float:
    """Causal score and value products of one attention layer, forward:
    half of the 4 * S^2 * N * H of the full square."""
    d = model["d_model"]
    h = model.get("head_dim") or d // model["num_heads"]
    return 2.0 * batch * seq * seq * model["num_heads"] * h


def ssd_fwd_flops(model, batch: int, seq: int) -> float:
    """The SSD chunked scan of one Mamba-2 layer, forward (arXiv
    2405.21060 §6): per chunk of Q, the causal C.B products (Q^2 N G),
    their product with the inputs (Q^2 H P), and the chunk states in and
    out (4 Q H P N); the state passing between chunks is left out."""
    s, d_in, nh, _ = _ssm(model)
    q = min(s["chunk_size"], seq)
    chunks = seq // q
    n, g, p = s["d_state"], s["n_groups"], s["head_dim"]
    per_chunk = q * q * n * g + q * q * nh * p + 4 * q * nh * p * n
    return float(batch * chunks * per_chunk)


def mixer_fwd_flops(model, kind: str, batch: int, seq: int) -> float:
    """Sequence-mixing work that no parameter count covers."""
    if kind == "attn":
        return attention_fwd_flops(model, batch, seq)
    if kind == "mamba2":
        return ssd_fwd_flops(model, batch, seq)
    raise ValueError(f"no count for layer kind {kind!r}")


def train_step_flops(model, batch: int, seq: int) -> float:
    """Model FLOPs of one training step (forward and backward): 6 N per
    token over the matmul parameters, plus three times each layer's
    forward mixing work. Recomputation does not count."""
    tokens = batch * seq
    mix = sum(mixer_fwd_flops(model, k, batch, seq)
              for k in layer_kinds(model))
    return 6.0 * matmul_params(model) * tokens + 3.0 * mix


def round_flops_per_client(model, traffic) -> float:
    """Model FLOPs one client does in one FL round."""
    return traffic["local_steps"] * train_step_flops(
        model, traffic["batch"], traffic["seq"])


def flash_fwd_cost(model, batch: int, seq: int, itemsize: int = 2):
    """(FLOPs, bytes) one causal flash-attention forward call needs:
    the causal half of the products, and q, k, v read and o written."""
    d = model["d_model"]
    h = model.get("head_dim") or d // model["num_heads"]
    flops = attention_fwd_flops(model, batch, seq)
    nbytes = 4.0 * batch * seq * model["num_heads"] * h * itemsize
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
