"""phi3-mini-3.8b [dense] — RoPE SwiGLU GQA(kv=32 -> MHA).
[arXiv:2404.14219; unverified]  32L d_model=3072 32H d_ff=8192 vocab=32064.
"""
import dataclasses

from repro.common.config import ModelConfig, ATTN

FULL = ModelConfig(
    name="phi3-mini-3.8b", family="dense",
    num_layers=32, d_model=3072, num_heads=32, num_kv_heads=32,
    d_ff=8192, vocab_size=32064,
    pattern=(ATTN,), mlp_kind="swiglu", rope_theta=10_000.0,
)

SMOKE = ModelConfig(
    name="phi3-smoke", family="dense",
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
    d_ff=128, vocab_size=128,
    pattern=(ATTN,), mlp_kind="swiglu",
    dtype="float32", param_dtype="float32", remat=False, attn_chunk=8,
)

# One FL client slot on one TPU v5e chip (16 GB HBM), at the published
# widths (d_model 3072, 32 heads of 96, d_ff 8192, vocab 32064, bf16).
# Cuts, each sized by `compiled.memory_analysis()` of the round program
# compiled for a described v5e so that at least 2 GB of HBM stay free:
#   * depth 32 -> 5 layers: a slot holds bf16 params, fp32 momentum and,
#     undonated, the next round's copies of both (~14 B/param). 5 layers
#     (~763M params) peak at 12.71 GB in the local-training program;
#     6 layers peak at 14.32 GB.
#   * batch 1 x sequence 2048 per local step (half of phi3-mini's 4K
#     context), as the benchmark's phi3 slot runs it. This was sized
#     when the flash-attention backward recomputed through the
#     reference's batch x 32 heads x seq^2 fp32 scores; the backward is
#     now a Pallas kernel pair that holds no S x S scores, and a longer
#     sequence or a larger batch has not been re-sized since.
#   * 2 local steps per FL round, one client per chip.
ONE_CHIP = dataclasses.replace(FULL, name="phi3-mini-3.8b-1chip",
                               num_layers=5, use_pallas=True)
ONE_CHIP_BATCH = 1
ONE_CHIP_SEQ = 2048
ONE_CHIP_LOCAL_STEPS = 2
