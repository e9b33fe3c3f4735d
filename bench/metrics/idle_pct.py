"""idle_pct: share of the traced window in which no operation runs on
the chip (1 - union of the op intervals / window), averaged over the
chips. Source: the device trace."""
from harness import trace as T


def read(r):
    span = r.hi - r.lo
    if span <= 0 or not r.devices:
        return None
    return r.per_chip(lambda d: 100.0 * (1.0 - T.busy_ns(d, r.lo, r.hi)
                                         / span))
