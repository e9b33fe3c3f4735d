"""Compiles for a described TPU v5e: the four Pallas kernels and the held
experts' grouped matmuls at published widths, and the one-chip FL round
programs of the phi3-mini and Nemotron 3 Nano cuts.

Nothing runs: the TPU compiler, which is installed without a chip,
refuses what the chip would refuse (block shapes off the (8, 128) tiling,
ops Mosaic cannot lower, a program that does not fit HBM). The topology
is described inside a module fixture, never at import, so every test
worker collects the same tests and only the worker running this file
loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

# the HBM share the one-chip cut must leave free
MIN_FREE_BYTES = 2e9


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host, with the persistent compilation cache
    off: a TPU compile cached here could not be read back without a
    chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler to describe it with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_phi3_width(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention
    qkv = _shape(one_chip, (1, 2048, 32, 96), jnp.bfloat16)
    text = _compiled_text(lambda q, k, v: flash_attention(q, k, v),
                          qkv, qkv, qkv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", [
    (1, 2048, 32, 96),       # phi3-mini
    (2, 2048, 32, 128),      # Nemotron 3 Nano: kv repeated to 32 heads
])
def test_flash_attention_grad_widths(one_chip, shape):
    """The gradient runs the forward kernel and the backward kernel pair,
    and nothing of size S x S: the backward's calls are named after
    their own wrappers, so a name starting with `flash_attention` (which
    the benchmark counts as a forward call) marks the forward alone."""
    from repro.kernels.flash_attention.ops import flash_attention
    qkv = _shape(one_chip, shape, jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v).astype(jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    calls = re.findall(r"\n\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    bwd = sorted(re.sub(r"(\.\d+)+$", "", c) for c in calls
                 if "fa_bwd" in c)
    assert bwd == ["fa_bwd_dkv", "fa_bwd_dq"], calls
    assert len(calls) == 3, calls
    assert not any(c.startswith("flash_attention") for c in bwd)
    S = shape[1]
    assert not re.search(rf"\[(\d+,)*{S},{S}\]", text)


def test_ssd_mamba2_width(one_chip):
    from repro.kernels.ssd.ops import ssd
    b, s, h, p, n = 1, 2048, 64, 64, 128
    text = _compiled_text(
        lambda x, la, B, C: ssd(x, la, B, C, chunk=256)[0],
        _shape(one_chip, (b, s, h, p), jnp.bfloat16),
        _shape(one_chip, (b, s, h), jnp.float32),
        _shape(one_chip, (b, s, h, n), jnp.bfloat16),
        _shape(one_chip, (b, s, h, n), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_rglru_recurrentgemma_width(one_chip):
    from repro.kernels.rglru.ops import rglru_scan
    x = _shape(one_chip, (1, 2048, 2560), jnp.float32)
    text = _compiled_text(
        lambda la, b: rglru_scan(la, b, chunk=128, block_w=128), x, x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", [
    (3072, 8192),        # one phi3-mini MLP projection: many full tiles
    (32064, 3072),       # the embedding: a partial last tile
    (3072,),             # a norm scale: fewer rows than one tile
])
def test_grad_quant_phi3_leaves(one_chip, shape):
    from repro.kernels.grad_quant import ops as gq
    x = _shape(one_chip, shape, jnp.float32)
    text = _compiled_text(
        lambda x: gq.dequantize(*gq.quantize(x, use_pallas=True), shape,
                                jnp.float32, use_pallas=True), x)
    assert text.count("tpu_custom_call") >= 2


@pytest.fixture(scope="module")
def phi3_round(topo):
    """Both round programs of the one-chip phi3 cut (int8 FedAvg),
    compiled for the described v5e, and the cut's parameter count."""
    from repro.configs.phi3_mini_3p8b import (ONE_CHIP, ONE_CHIP_BATCH,
                                              ONE_CHIP_LOCAL_STEPS,
                                              ONE_CHIP_SEQ)
    from repro.fl.training import make_round_programs
    from repro.models import lm
    mesh = jax.sharding.Mesh(np.array(topo.devices[:1]), ("pod",),
                             axis_types=(jax.sharding.AxisType.Auto,))
    stk = NamedSharding(mesh, P("pod"))
    local, fedavg = make_round_programs(ONE_CHIP, mesh, lr=5e-3,
                                        quantize=True, use_pallas=True)
    params = jax.tree.map(lambda a: _shape(stk, (1,) + a.shape, a.dtype),
                          lm.abstract_params(ONE_CHIP))
    mu = jax.tree.map(lambda a: _shape(stk, a.shape, jnp.float32), params)
    batches = {k: _shape(stk, (1, ONE_CHIP_LOCAL_STEPS, ONE_CHIP_BATCH,
                               ONE_CHIP_SEQ), jnp.int32)
               for k in ("tokens", "labels")}
    w = _shape(stk, (1,), jnp.float32)
    return {"local": local.lower(params, mu, batches).compile(),
            "fedavg": fedavg.lower(params, params, mu, mu, w).compile(),
            "leaves": len(jax.tree.leaves(params))}


def test_phi3_one_chip_round_fits(phi3_round):
    """Both round programs of the one-chip cut compile, run the Pallas
    kernels, and leave the HBM share the cut was sized for."""
    from repro.launch.roofline import V5E
    for compiled in (phi3_round["local"], phi3_round["fedavg"]):
        assert "tpu_custom_call" in compiled.as_text()
        m = compiled.memory_analysis()
        used = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
        assert used <= V5E.hbm_bytes - MIN_FREE_BYTES, used


def test_phi3_fedavg_keeps_the_benchmark_hooks(phi3_round):
    """The FedAvg program is the module `jit_fedavg`, and each parameter
    leaf has one Pallas call named after a `quantize` wrapper and one
    after a `dequantize` wrapper, under vmap: the names by which the
    benchmark finds FedAvg's device time and the codec's kernels."""
    text = phi3_round["fedavg"].as_text()
    assert text.startswith("HloModule jit_fedavg")
    calls = re.findall(r"\n\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    for prefix in ("vmap_jit_quantize", "vmap_jit_dequantize"):
        assert sum(c.startswith(prefix) for c in calls) == \
            phi3_round["leaves"], (prefix, calls)


def test_expert_layer_nemotron_width(one_chip):
    """The held share of a Nemotron 3 Nano expert layer, forward and
    backward, at 2 x 2048 tokens: 8 of 128 experts of 2688 -> 1856, top
    6, and the shared expert of 3712. The grouped matmuls lower to the
    megablox kernels inside the `expert_layer` scope, over every token's
    6 slots, and the weight gradients come out per held expert."""
    from repro.configs.nemotron3_nano_30b import ONE_CHIP
    from repro.models import layers as L
    p = jax.tree.map(lambda s: _shape(one_chip, s.shape,
                                      s.dtype or jnp.bfloat16),
                     L.held_experts_schema(ONE_CHIP), is_leaf=L.is_spec)
    x = _shape(one_chip, (2, 2048, 2688), jnp.bfloat16)

    def loss(p, x):
        y = L.held_experts(p, x, ONE_CHIP)
        return jnp.sum(y.astype(jnp.float32))

    text = _compiled_text(jax.grad(loss, argnums=(0, 1)), p, x)
    for name, shape in (("gmm", "24576,1856"), ("gmm", "24576,2688"),
                        ("tgmm", "8,2688,1856"), ("tgmm", "8,1856,2688")):
        assert re.search(rf"%{name}[.\d]* = bf16\[{shape}\].*op_name="
                         rf'"[^"]*expert_layer[^"]*"', text), (name, shape)


@pytest.fixture(scope="module")
def nemotron_round(topo):
    """Both round programs of the one-chip Nemotron 3 Nano cut (fp32
    FedAvg), compiled for the described v5e."""
    from repro.configs.nemotron3_nano_30b import (ONE_CHIP, ONE_CHIP_BATCH,
                                                  ONE_CHIP_LOCAL_STEPS,
                                                  ONE_CHIP_SEQ)
    from repro.fl.training import make_round_programs
    from repro.models import lm
    mesh = jax.sharding.Mesh(np.array(topo.devices[:1]), ("pod",),
                             axis_types=(jax.sharding.AxisType.Auto,))
    stk = NamedSharding(mesh, P("pod"))
    local, fedavg = make_round_programs(ONE_CHIP, mesh, lr=5e-3,
                                        quantize=False, use_pallas=True)
    params = jax.tree.map(lambda a: _shape(stk, (1,) + a.shape, a.dtype),
                          lm.abstract_params(ONE_CHIP))
    mu = jax.tree.map(lambda a: _shape(stk, a.shape, jnp.float32), params)
    batches = {k: _shape(stk, (1, ONE_CHIP_LOCAL_STEPS, ONE_CHIP_BATCH,
                               ONE_CHIP_SEQ), jnp.int32)
               for k in ("tokens", "labels")}
    w = _shape(stk, (1,), jnp.float32)
    return {"local": local.lower(params, mu, batches).compile(),
            "fedavg": fedavg.lower(params, params, mu, mu, w).compile()}


def test_nemotron_one_chip_round_fits(nemotron_round):
    """Both round programs of the one-chip cut compile, leave the HBM
    share the cut was sized for, and keep the names the benchmark reads:
    the module `jit_local_train` with the flash-attention kernel and
    the grouped-matmul kernels, and `jit_fedavg`."""
    from repro.launch.roofline import V5E
    local = nemotron_round["local"].as_text()
    assert local.startswith("HloModule jit_local_train")
    assert "flash_attention" in local and "%tgmm" in local
    assert nemotron_round["fedavg"].as_text().startswith(
        "HloModule jit_fedavg")
    for compiled in nemotron_round.values():
        m = compiled.memory_analysis()
        used = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
        assert used <= V5E.hbm_bytes - MIN_FREE_BYTES, used
