"""expert_roofline: the least time the expert layers' matmuls of one run
of the local-training program need (the reference's `expert_step_flops`
for each local step: the held routed experts at their expected share of
the tokens, top_k x held / num_experts, and the shared expert at every
token, at the bf16 peak), over `expert_layer_ms`. That time also holds
the router, the sort and gathers of the dispatch, the rows of the
grouped matmuls that no held slot fills, and the remat recompute, none
of which counts as work. Source: the device trace."""
from harness import cell as cells


def read(r):
    ms = cells.metric_reader("expert_layer_ms")(r)
    if not ms:
        return None
    tr = r.traffic
    flops = tr["local_steps"] * r.reference.expert_step_flops(
        r.model, tr["batch"], tr["seq"])
    return 100.0 * flops / r.peaks.bf16_flops / (ms / 1e3)
