"""grad_quant_roofline: the least time the int8 update codec needs per
round (harness/flops.py: each leaf's fp32 delta read and its int8 codes
and block scales written, then read back and the fp32 delta written, at
the HBM peak), times the FedAvg runs in the traced window, over the
device time of its Pallas calls (custom calls named after the program's
`quantize` and `dequantize` wrappers, under vmap; the kernels inside
are `_quant_kernel` and `_dequant_kernel`), averaged over the chips.
Source: the device trace."""
import math

from harness import trace as T
from harness.flops import codec_bytes

KERNELS = ("vmap_jit_quantize", "vmap_jit_dequantize")


def read(r):
    import jax
    from reference.common import is_spec
    specs = r.reference.param_specs(r.model)
    sizes = [math.prod(s.shape) for s in jax.tree.leaves(specs,
                                                         is_leaf=is_spec)]
    per_round = sum(codec_bytes(n) for n in sizes) / r.peaks.hbm_bw

    def chip(dev):
        calls = T.kernel_ops(dev, KERNELS, r.lo, r.hi)
        runs = T.module_events(dev, "jit_fedavg", r.lo, r.hi)
        if not calls or not runs:
            return None
        return 100.0 * len(runs) * per_round / (T.length(calls) / 1e9)
    return r.per_chip(chip)
