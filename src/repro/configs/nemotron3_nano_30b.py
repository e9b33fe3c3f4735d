"""nemotron-3-nano-30b-a3b [hybrid] — single-mixer blocks of three kinds.
[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json]
52 blocks (23 Mamba-2, 23 expert, 6 GQA attention), d_model=2688;
Mamba-2 64 heads x 64, state 128, 8 groups, conv 4, chunk 128; attention
32 query / 2 key-value heads of 128 with no rotary embedding; 128 routed
experts of 1856, top-6, sigmoid router with a selection bias, routed
scaling 2.5, one shared expert of 3712; squared-ReLU MLPs; vocab 131072,
untied head.

Each block is x + mixer(RMSNorm(x)) with one mixer: no layer carries a
separate MLP. Not in the registry (`ARCH_IDS`): the one-chip cut
below is what runs, in the chip benchmark
(bench/configs/nemotron-3-nano-30b-a3b-slot.json restates it).
"""
import dataclasses

from repro.common.config import (ATTN, MAMBA2, MOE, ModelConfig, MoEConfig,
                                 SSMConfig)

# the published hybrid_override_pattern: M Mamba-2, E experts, * attention
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
KINDS = {"M": MAMBA2, "E": MOE, "*": ATTN}

FULL = ModelConfig(
    name="nemotron-3-nano-30b-a3b", family="hybrid",
    num_layers=52, d_model=2688, num_heads=32, num_kv_heads=2,
    head_dim=128, d_ff=0, vocab_size=131072,
    pattern=tuple(KINDS[c] for c in PATTERN),
    rope_theta=None, mlp_kind="relu2",
    moe=MoEConfig(num_experts=128, top_k=6, d_ff=1856, router="sigmoid",
                  routed_scaling=2.5, shared_d_ff=3712),
    ssm=SSMConfig(d_state=128, head_dim=64, conv_width=4, n_groups=8,
                  chunk_size=128, num_heads=64),
    norm_eps=1e-5,
)

SMOKE = ModelConfig(
    name="nemotron-smoke", family="hybrid",
    num_layers=5, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=0, vocab_size=128,
    pattern=(MAMBA2, MOE, MAMBA2, ATTN, MOE),
    rope_theta=None, mlp_kind="relu2",
    moe=MoEConfig(num_experts=16, top_k=4, d_ff=32, router="sigmoid",
                  routed_scaling=2.5, held=4, shared_d_ff=48),
    ssm=SSMConfig(d_state=16, head_dim=16, conv_width=4, n_groups=2,
                  chunk_size=8, num_heads=4),
    dtype="float32", param_dtype="float32", remat=False, attn_chunk=8,
)

# One FL client slot on one TPU v5e chip (16 GB HBM): the first stage of
# a pipeline, at the published widths. Cuts:
#   * depth 52 -> 7: one period, the first seven blocks (MEMEM*E: 3
#     Mamba-2, 1 attention, 3 expert, the stack's own ratio);
#   * experts held 128 -> 8: experts 0-7 of each expert layer, 16 chips
#     sharing a layer; the router still spans all 128;
#   * vocabulary 131072 -> 16384, an eighth (the embedding and the head
#     split 8 ways).
# Batch 2 x sequence 2048 per local step: the flash-attention backward
# recomputes through the reference, which holds 32 heads x seq^2 fp32
# scores per sequence.
ONE_CHIP = dataclasses.replace(
    FULL, name="nemotron-3-nano-30b-a3b-slot", num_layers=7,
    pattern=FULL.pattern[:7], vocab_size=16384,
    moe=dataclasses.replace(FULL.moe, held=8), use_pallas=True)
ONE_CHIP_BATCH = 2
ONE_CHIP_SEQ = 2048
ONE_CHIP_LOCAL_STEPS = 2
