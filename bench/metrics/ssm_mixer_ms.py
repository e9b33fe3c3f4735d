"""ssm_mixer_ms: device milliseconds per run of the local-training
program (HLO module `jit_local_train`) in its Mamba-2 mixers: the ops
whose op_name holds the program's `ssm_mixer` scope (projections,
convolution, the chunked SSD scan, the gated norm), forward and backward
(remat recompute included), averaged over the runs in the traced window
and over the chips. Source: the device trace, with each op's op_name read
from the program's compiled HLO text (run.py `Record.scope_ms`)."""


def read(r):
    return r.scope_ms("jit_local_train", "ssm_mixer")
