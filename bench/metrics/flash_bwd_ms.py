"""flash_bwd_ms: device milliseconds per round of the flash-attention
backward's Pallas calls in the traced window (custom calls named after
the program's `fa_bwd_dkv` and `fa_bwd_dq` wrappers), averaged over the
chips; None where the program makes no such call. Source: the device
trace."""
from harness import trace as T

KERNELS = ("fa_bwd_dkv", "fa_bwd_dq")


def read(r):
    def chip(dev):
        calls = T.kernel_ops(dev, KERNELS, r.lo, r.hi)
        if not calls or not r.rounds:
            return None
        return T.length(calls) / r.rounds / 1e6
    return r.per_chip(chip)
