"""The benchmark's FLOP and byte counts against hand counts at small
shapes."""
import math

import jax
import pytest

import bench_tiny  # noqa: F401  (paths)
from harness import flops as F
from harness.cell import load_module, BENCH_DIR
from reference.common import is_spec

DENSE = {"d_model": 8, "num_heads": 2, "num_kv_heads": 2, "head_dim": None,
         "d_ff": 16, "vocab_size": 32, "num_layers": 3, "pattern": ["attn"],
         "mlp_kind": "swiglu", "tie_embeddings": False,
         "param_dtype": "float32"}
SSM = {"d_model": 8, "num_layers": 2, "vocab_size": 32, "pattern": ["mamba2"],
       "tie_embeddings": True, "param_dtype": "float32",
       "ssm": {"d_state": 4, "head_dim": 4, "expand": 2, "conv_width": 4,
               "n_groups": 1, "chunk_size": 4, "dt_min": 0.001,
               "dt_max": 0.1, "a_init_range": [1.0, 16.0]}}


def test_dense_params_by_hand():
    # q, k, v, o: 4 * 8 * 8; swiglu 3 * 8 * 16; two norms 2 * 8
    per_layer = 4 * 64 + 3 * 128 + 16
    assert F.layer_params(DENSE, "attn") == per_layer
    # embedding 32 x 8, final norm 8, head 8 x 32
    assert F.total_params(DENSE) == 3 * per_layer + 256 + 8 + 256
    assert F.matmul_params(DENSE) == 3 * per_layer + 8 + 256


def test_ssm_params_by_hand():
    # d_in 16, heads 4, conv_dim 16 + 2 * 4 = 24
    per_layer = (8 * (2 * 16 + 2 * 4 + 4) + 4 * 24 + 24 + 3 * 4 + 16
                 + 16 * 8 + 8)
    assert F.layer_params(SSM, "mamba2") == per_layer
    assert F.total_params(SSM) == 2 * per_layer + 32 * 8 + 8
    assert F.matmul_params(SSM) == F.total_params(SSM)


@pytest.mark.parametrize("model,ref", [(DENSE, "dense_lm"),
                                       (SSM, "mamba2_lm")])
def test_param_count_matches_reference_specs(model, ref):
    mod = load_module(BENCH_DIR / "reference" / f"{ref}.py",
                      f"reference.{ref}")
    specs = mod.param_specs(model)
    n = sum(math.prod(s.shape) for s in jax.tree.leaves(specs,
                                                        is_leaf=is_spec))
    assert n == F.total_params(model)


def test_attention_flops_by_hand():
    # causal half of 2 products of 2 * S^2 * N * H each: 2 * B S^2 N H
    assert F.attention_fwd_flops(DENSE, batch=2, seq=8) == 2 * 2 * 64 * 2 * 4
    fl, nb = F.flash_fwd_cost(DENSE, batch=1, seq=8)
    assert fl == 2 * 64 * 2 * 4
    assert nb == 4 * 8 * 2 * 4 * 2          # q, k, v, o in bf16


def test_ssd_flops_by_hand():
    # Q 4, 2 chunks of seq 8; N 4, G 1, H 4, P 4
    per_chunk = 16 * 4 * 1 + 16 * 4 * 4 + 4 * 4 * 4 * 4 * 4
    assert F.ssd_fwd_flops(SSM, batch=1, seq=8) == 2 * per_chunk


def test_train_step_flops_by_hand():
    n = F.matmul_params(DENSE)
    mix = 3 * F.attention_fwd_flops(DENSE, 1, 8)
    assert F.train_step_flops(DENSE, 1, 8) == 6 * n * 8 + 3 * mix
    traffic = {"local_steps": 2, "batch": 1, "seq": 8}
    assert F.round_flops_per_client(DENSE, traffic) \
        == 2 * F.train_step_flops(DENSE, 1, 8)


def test_codec_bytes_by_hand():
    # 4n in, n codes and 4 per block out; then back: n + 4 per block in,
    # 4n out
    assert F.codec_bytes(2048) == 10 * 2048 + 8
    assert F.codec_bytes(2049) == 10 * 2049 + 16


def test_least_time_names_its_bound():
    assert F.least_time(197e12, 1.0, 197e12, 819e9) == (1.0, "compute")
    assert F.least_time(1.0, 819e9, 197e12, 819e9) == (1.0, "memory")


def test_peaks_unknown_kind_is_an_error():
    from harness.peaks import chip_peaks
    assert chip_peaks("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(ValueError):
        chip_peaks("TPU v99")
