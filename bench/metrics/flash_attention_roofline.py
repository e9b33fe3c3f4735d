"""flash_attention_roofline: the least time the causal flash-attention
forward calls in the traced window need (harness/flops.py: the causal
half of the score and value products at the bf16 peak, or q, k, v read
and o written at the HBM peak, whichever is longer), over the device
time of its Pallas calls (custom calls named after the program's
`flash_attention` wrapper; the kernel inside is `_flash_kernel`),
averaged over the chips. Source: the device trace."""
from harness import trace as T
from harness.flops import flash_fwd_cost, least_time

KERNELS = ("flash_attention",)


def read(r):
    flops, nbytes = flash_fwd_cost(r.model, r.traffic["batch"],
                                   r.traffic["seq"])
    t_min, _ = least_time(flops, nbytes, r.peaks.bf16_flops,
                          r.peaks.hbm_bw)

    def chip(dev):
        calls = T.kernel_ops(dev, KERNELS, r.lo, r.hi)
        if not calls:
            return None
        return 100.0 * len(calls) * t_min / (T.length(calls) / 1e9)
    return r.per_chip(chip)
