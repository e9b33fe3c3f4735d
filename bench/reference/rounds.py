"""The reference's first FL rounds, and the faults planted in it.

`run_rounds` follows the program's first `rounds` rounds from the same
weights (made here from the seed) on the same rows: every client trains
`local_steps` SGD-momentum steps from the global model on its own
device, then the FedAvg barrier folds the deltas into the next global
model. It returns the losses, the per-leaf momentum norms after round 1
and the per-leaf norms of each slot's change over all rounds.

`precision` "fp32" is the reference; "fp8" is the control, the same
computation a precision step below bfloat16. `fault` plants one of the
faults the comparison has to catch, in the reference put in the
program's place:

  half_batch    the loss is the mean over the first half of each row's
                positions only (the rows here are single sequences)
  token_altered the first label of every row is changed where the feed
                produces it
  half_clients  the barrier takes the mean over half of the clients
  no_exchange   the barrier leaves out the exchange between chips:
                each slot keeps its own client's result
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from reference.common import (PRECISIONS, fedavg, make_local_step,
                              make_params, seed_halves, tree_to)

FAULTS = ("half_batch", "token_altered", "half_clients", "no_exchange")


def _norms(tree, fn) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v, np.float64) for k, v in fn(tree).items()}


def run_rounds(ref, model: Dict, traffic: Dict, seed: int,
               batches: List[List[List[Dict[str, np.ndarray]]]],
               leaf_norms, change_norms, rounds: int = 3,
               precision: str = "fp32", fault: Optional[str] = None,
               devices=None) -> Dict:
    """`batches[r][c][s]` is the {"tokens", "labels"} row block client c
    trained on in local step s of round r. `leaf_norms(tree)` and
    `change_norms(now, before)` give per-leaf norms of one slot."""
    n = traffic["clients"]
    devices = devices or jax.devices()[:n]
    specs = ref.param_specs(model)
    mm = PRECISIONS[precision]
    loss = functools.partial(ref.loss, model, mm)
    out = {"losses": [], "mom": None, "change": None}
    with jax.default_matmul_precision("highest"):
        step = make_local_step(loss, traffic["lr"])
        gen = jax.jit(functools.partial(make_params, specs))
        lo, hi = seed_halves(seed)
        params = tree_to(gen(lo, hi), devices[0])
        mus = [tree_to(jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params), d)
            for d in devices]
        slots = None
        for r in range(rounds):
            news, losses = [], []
            for c, dev in enumerate(devices):
                p = jax.tree.map(lambda x: jax.device_put(jnp.copy(x), dev),
                                 params if slots is None else slots[c])
                row = []
                for s in range(traffic["local_steps"]):
                    b = batches[r][c][s]
                    tok, lab = b["tokens"], b["labels"]
                    if fault == "half_batch":
                        half = tok.shape[1] // 2
                        tok, lab = tok[:, :half], lab[:, :half]
                    if fault == "token_altered":
                        lab = lab.copy()
                        lab[:, 0] = (lab[:, 0] + 1) % model["vocab_size"]
                    p, mus[c], val = step(p, mus[c], jax.device_put(tok, dev),
                                          jax.device_put(lab, dev))
                    row.append(val)
                news.append(p)
                losses.append(row)
            out["losses"].append([[float(v) for v in row]
                                  for row in losses])
            if r == 0:
                out["mom"] = [_norms(m, leaf_norms) for m in mus]
            w = np.ones(n)
            if fault == "half_clients":
                w[n // 2:] = 0.0
            if fault == "no_exchange":
                slots = news
            else:
                params = fedavg(params if slots is None else slots[0], news,
                                w, traffic["quantize"], devices[0])
            del news
        before = tree_to(gen(lo, hi), devices[0])
        finals = slots if slots is not None else [params] * n
        out["change"] = [_norms(tree_to(f, devices[0]),
                                lambda t: change_norms(t, before))
                         for f in finals]
    return out
