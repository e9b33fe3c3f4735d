"""local_bwd_ms: device milliseconds per run of the local-training
program (HLO module `jit_local_train`) in its backward pass: the ops
whose op_name holds the program's `forward` scope under `transpose`,
which takes in the rematerialised forward recomputed for the backward,
averaged over the runs in the traced window and over the chips. Source:
the device trace, with each op's op_name read from the program's
compiled HLO text (run.py `Record.scope_ms`)."""


def read(r):
    return r.scope_ms("jit_local_train", "forward", "backward")
