"""input_prep_ms: host milliseconds per round inside
`MeshTrainerHooks.next_batches` (stacking the clients' rows and placing
them one client per chip). Source: the benchmark's host span around it."""


def read(r):
    n = len(r.spans.get("next_batches", ()))
    return r.span_s("next_batches") / n * 1e3 if n else None
