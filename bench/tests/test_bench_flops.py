"""The benchmark's FLOP and byte counts against hand counts at small
shapes, and each configuration's count of a round as its reference
gives it."""
import pytest

import bench_tiny  # noqa: F401  (paths)
from harness import cell as cells
from harness import flops as F
from harness.cell import load_module, BENCH_DIR
from reference.common import matmul_params, spec_size

DENSE = {"d_model": 8, "num_heads": 2, "num_kv_heads": 2, "head_dim": None,
         "d_ff": 16, "vocab_size": 32, "num_layers": 3, "pattern": ["attn"],
         "mlp_kind": "swiglu", "tie_embeddings": False,
         "param_dtype": "float32"}
SSM = {"d_model": 8, "num_layers": 2, "vocab_size": 32, "pattern": ["mamba2"],
       "tie_embeddings": True, "param_dtype": "float32",
       "ssm": {"d_state": 4, "head_dim": 4, "expand": 2, "conv_width": 4,
               "n_groups": 1, "chunk_size": 4, "dt_min": 0.001,
               "dt_max": 0.1, "a_init_range": [1.0, 16.0]}}


def _ref(name):
    return load_module(BENCH_DIR / "reference" / f"{name}.py",
                       f"reference.{name}")


def test_dense_params_by_hand():
    # q, k, v, o: 4 * 8 * 8; swiglu 3 * 8 * 16; two norms 2 * 8
    per_layer = 4 * 64 + 3 * 128 + 16
    specs = _ref("dense_lm").param_specs(DENSE)
    assert spec_size(specs["blocks"]) == 3 * per_layer
    # embedding 32 x 8, final norm 8, head 8 x 32
    assert spec_size(specs) == 3 * per_layer + 256 + 8 + 256
    assert matmul_params(specs, False) == 3 * per_layer + 8 + 256


def test_ssm_params_by_hand():
    # d_in 16, heads 4, conv_dim 16 + 2 * 4 = 24
    per_layer = (8 * (2 * 16 + 2 * 4 + 4) + 4 * 24 + 24 + 3 * 4 + 16
                 + 16 * 8 + 8)
    specs = _ref("mamba2_lm").param_specs(SSM)
    assert spec_size(specs["blocks"]) == 2 * per_layer
    assert spec_size(specs) == 2 * per_layer + 32 * 8 + 8
    assert matmul_params(specs, True) == spec_size(specs)


@pytest.mark.parametrize("model,ref", [(DENSE, "dense_lm"),
                                       (SSM, "mamba2_lm")])
def test_param_count_matches_reference_specs(model, ref):
    """The parameters a reference's 6 N counts from its own specs are
    the program's own analytic count."""
    import run as bench_run
    cfg = bench_run.model_config(dict({"num_heads": 1, "num_kv_heads": 1,
                                       "d_ff": 0}, **model, name="t",
                                      family="dense"))
    assert spec_size(_ref(ref).param_specs(model)) == cfg.param_count()


def test_attention_flops_by_hand():
    # causal half of 2 products of 2 * S^2 * N * H each: 2 * B S^2 N H
    assert F.causal_attention_fwd_flops(2, 8, 2, 4) == 2 * 2 * 64 * 2 * 4
    fl, nb = F.flash_fwd_cost(DENSE, batch=1, seq=8)
    assert fl == 2 * 64 * 2 * 4
    assert nb == 4 * 8 * 2 * 4 * 2          # q, k, v, o in bf16


def test_flash_bytes_count_gqa_kv_heads_once():
    # 8 query heads share 2 kv heads of 16: q and o at 8 heads, k and v
    # at 2, in bf16
    gqa = dict(DENSE, d_model=128, num_heads=8, num_kv_heads=2)
    fl, nb = F.flash_fwd_cost(gqa, batch=2, seq=32)
    assert fl == 2 * 2 * 32 * 32 * 8 * 16
    assert nb == 2 * 32 * 16 * 2 * (8 + 8 + 2 + 2)


def test_ssd_flops_by_hand():
    # Q 4, 2 chunks of seq 8; N 4, G 1, H 4, P 4
    per_chunk = 16 * 4 * 1 + 16 * 4 * 4 + 4 * 4 * 4 * 4 * 4
    assert F.ssd_fwd_flops(1, 8, chunk=4, d_state=4, n_groups=1,
                           num_heads=4, head_dim=4) == 2 * per_chunk
    mod = _ref("mamba2_lm")
    n = spec_size(mod.param_specs(SSM))      # tied: every parameter
    assert mod.step_flops(SSM, 1, 8) == 6 * n * 8 + 3 * 2 * (2 * per_chunk)


def test_train_step_flops_by_hand():
    mod = _ref("dense_lm")
    n = matmul_params(mod.param_specs(DENSE), False)
    assert n == 3 * (4 * 64 + 3 * 128 + 16) + 8 + 256
    mix = 3 * (3 * 2 * 64 * 2 * 4)             # 3 x, 3 layers, causal
    assert mod.step_flops(DENSE, 1, 8) == 6 * n * 8 + mix
    traffic = {"local_steps": 2, "batch": 1, "seq": 8}
    assert F.round_flops_per_client(mod, DENSE, traffic) \
        == 2 * mod.step_flops(DENSE, 1, 8)


# Model FLOPs per client round of the configurations as run, as the
# count by layer kind gave them before each reference counted its own.
PINNED = {"phi3.1chip.int8": 17_110_369_566_720,
          "phi3.4chip.fp32": 17_110_369_566_720,
          "mamba2.1chip.fp32": 22_761_006_170_112}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_round_flops_of_the_cells_are_pinned(name):
    cell = cells.load_cell(name)
    got = F.round_flops_per_client(cells.reference_module(cell),
                                   cell.model, cell.traffic)
    assert got == PINNED[name]


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
