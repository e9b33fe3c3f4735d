"""Client-update payload sizing.

One FL round uploads one model-sized update per participating client,
and the dollars/seconds that upload costs scale with its byte size —
the knob practitioners actually control (FeatureCloud, Multi-FedLS).
This module turns a param pytree (or a `ModelConfig`, via its abstract
parameter shapes, `models.lm.abstract_params`) into an
`UpdatePayload` with an exact byte count for the two wire formats the
bridge supports:

* fp32 — each leaf uploads as raw float32, 4 bytes per element.
* quantized — each leaf uploads in the `kernels.grad_quant` block
  layout: int8 values padded to full `BLOCK`-wide rows plus one fp32
  scale per row. The byte math here mirrors `grad_quant.ops.quantize`
  exactly (`tests/test_properties.py` pins the equality against real
  quantized arrays), so billed egress is the true wire size including
  padding overhead — quantization only pays off once a leaf amortizes
  its scale rows, which is precisely the trade the accountant should
  see.

Import-light on purpose: jax and the kernel package load lazily, so
`cloud.pricing` (which imports the sibling `billing` module through the
package) stays cheap to import.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

_FP32_BYTES = 4


def _quant_block() -> int:
    # Lazy: pulling BLOCK from the kernel package imports jax.
    from repro.kernels.grad_quant.ops import BLOCK
    return BLOCK


def fp32_leaf_bytes(n: int) -> int:
    """Wire bytes for one n-element leaf uploaded as raw float32."""
    return int(n) * _FP32_BYTES


def quantized_leaf_bytes(n: int) -> int:
    """Wire bytes for one n-element leaf in the grad_quant block layout.

    `quantize` flattens the leaf and pads it to `nb = ceil(n/BLOCK)`
    full rows (minimum one), returning an int8 `(nb, BLOCK)` value
    array plus an fp32 `(nb, 1)` scale column — so the wire carries
    `nb*BLOCK` int8 bytes plus `nb*4` scale bytes, padding included.
    """
    block = _quant_block()
    nb = max((int(n) + block - 1) // block, 1)
    return nb * block + nb * _FP32_BYTES


def _leaf_elements(tree: Any) -> list:
    """Element counts per leaf; accepts arrays or ShapeDtypeStructs."""
    import jax

    counts = []
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", ())
        n = 1
        for d in shape:
            n *= int(d)
        counts.append(n)
    return counts


@dataclasses.dataclass(frozen=True)
class UpdatePayload:
    """Byte-exact size of one client's update upload.

    `n_params`/`n_leaves` describe the pytree the bytes were derived
    from; `num_bytes` is the wire size in the chosen format. Frozen so
    engines and the accountant can share one instance per run.
    """
    n_params: int
    n_leaves: int
    num_bytes: int
    quantized: bool = False

    @property
    def size_mb(self) -> float:
        """Wire size in MB (2**20 bytes), the unit provider rates use."""
        return self.num_bytes / float(1 << 20)

    # ------------------------------------------------------------------
    @classmethod
    def from_tree(cls, tree: Any, quantized: bool = False) -> "UpdatePayload":
        """Size an update from the actual param pytree (arrays or
        `ShapeDtypeStruct`s), leaf by leaf — each leaf is quantized
        independently, so padding overhead is summed per leaf."""
        counts = _leaf_elements(tree)
        per_leaf = quantized_leaf_bytes if quantized else fp32_leaf_bytes
        return cls(n_params=sum(counts), n_leaves=len(counts),
                   num_bytes=sum(per_leaf(n) for n in counts),
                   quantized=quantized)

    @classmethod
    def from_model_config(cls, cfg: Any,
                          quantized: bool = False) -> "UpdatePayload":
        """Size an update for a `ModelConfig` without materializing
        params, via its abstract parameter pytree
        (`models.lm.abstract_params`)."""
        from repro.models import lm
        return cls.from_tree(lm.abstract_params(cfg), quantized=quantized)

    @classmethod
    def from_mb(cls, size_mb: float,
                quantized: bool = False) -> "UpdatePayload":
        """Back a modeled size (`FLRunConfig.update_payload_mb`) into a
        payload, treating it as one flat fp32 leaf of the equivalent
        element count so the quantized variant prices consistently."""
        n = max(int(round(size_mb * (1 << 20))) // _FP32_BYTES, 0)
        per_leaf = quantized_leaf_bytes if quantized else fp32_leaf_bytes
        return cls(n_params=n, n_leaves=1, num_bytes=per_leaf(n),
                   quantized=quantized)
