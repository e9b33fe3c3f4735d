"""engine_host_ms: host milliseconds per round outside `aggregate`: the
runner, the SyncEngine and the cloud simulator between two rounds.
Source: the benchmark's host spans (window wall time minus the time
inside its `aggregate` spans), over the rounds completed."""


def read(r):
    if not r.rounds:
        return None
    return (r.window_s - r.span_s("aggregate")) / r.rounds * 1e3
