"""local_device_ms: device milliseconds of one run of the local-training
program (HLO module `jit_local_train`), averaged over its runs in the
traced window and over the chips. Source: the device trace."""
from harness import trace as T

MODULE = "jit_local_train"


def read(r):
    def chip(dev):
        runs = T.module_events(dev, MODULE, r.lo, r.hi)
        return T.length(runs) / len(runs) / 1e6 if runs else None
    return r.per_chip(chip)
