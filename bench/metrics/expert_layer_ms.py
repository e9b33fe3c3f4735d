"""expert_layer_ms: device milliseconds per run of the local-training
program (HLO module `jit_local_train`) in its expert layers, forward and
backward (remat recompute included), averaged over the runs in the
traced window and over the chips: the ops whose op_name holds the
program's `expert_layer` scope (router, dispatch, the held experts'
grouped-matmul kernels, the shared expert). Source: the device trace,
with each op's op_name read from the program's compiled HLO text (run.py
`Record.scope_ms`)."""
PROGRAM = "jit_local_train"


def read(r):
    return r.scope_ms(PROGRAM, "expert_layer")
