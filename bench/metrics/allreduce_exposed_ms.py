"""allreduce_exposed_ms: per round, the milliseconds of all-reduce ops
(the FedAvg psum over the chips; opcode `all-reduce`, named `psum.N`
in the trace) during which no other op runs on that chip, averaged
over the chips; rounds are the runs of `jit_fedavg` in the traced
window. Source: the device trace."""
from harness import trace as T

ALL_REDUCE = ("all-reduce", "all-reduce-start", "all-reduce-done")


def _is_allreduce(op):
    return op[1] in ALL_REDUCE


def read(r):
    def chip(dev):
        runs = T.module_events(dev, "jit_fedavg", r.lo, r.hi)
        if not runs or not any(_is_allreduce(op) for op in dev["ops"]):
            return None
        return T.exposed_ns(dev, _is_allreduce, r.lo, r.hi) / len(runs) / 1e6
    return r.per_chip(chip)
