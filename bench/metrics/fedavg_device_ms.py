"""fedavg_device_ms: device milliseconds of one run of the FedAvg
barrier program (HLO module `jit_fedavg`: delta, optional int8 codec,
weighted sum and the psum over chips), averaged over its runs in the
traced window and over the chips. Source: the device trace."""
from harness import trace as T

MODULE = "jit_fedavg"


def read(r):
    def chip(dev):
        runs = T.module_events(dev, MODULE, r.lo, r.hi)
        return T.length(runs) / len(runs) / 1e6 if runs else None
    return r.per_chip(chip)
