"""round_mfu: model FLOP utilisation of the whole round: the model FLOPs
each client's local training needs per round (the configuration's
reference counts a step, `step_flops`, by harness/flops.py's rules: 6 N
per token over the matmul parameters plus the sequence-mixing work,
recomputation not counted), times the rounds completed in the traced
window, over the window's host wall time and the chip's bf16 peak. One
client per chip, so the chips cancel. Source: host clock and the
benchmark's FLOP count."""
from harness.flops import round_flops_per_client


def read(r):
    if not r.rounds or r.window_s <= 0:
        return None
    flops = round_flops_per_client(r.reference, r.model,
                                   r.traffic) * r.rounds
    return 100.0 * flops / (r.window_s * r.peaks.bf16_flops)
