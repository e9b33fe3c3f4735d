"""The numbers that decide `correct`, and how they are compared.

A training cell compares the program's first three FL rounds, driven in
set-up through the window's own call and feed, with the plain reference
of the same rounds on the same weights and rows:

  loss_gap         worst |loss - reference loss| over every local step
                   of every client in rounds 1-3 (nats)
  grad_norm_gap    the momentum after round 1 (0.9 g1 + g2: the
                   gradients as the optimizer took them), per leaf:
                   |norm - reference norm| / max(reference norm, median
                   leaf's reference norm), worst leaf and client
  change_norm_gap  the same measure of each leaf's change over rounds
                   1-3 of the global model, on every client slot;
                   leaves whose reference gradient is under a
                   thousandth of the median leaf's are left out

A leaf is one parameter array, and each layer of a stacked one.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

TINY_GRAD = 1e-3      # a leaf under this share of the median leaf's
                      # reference gradient is rounding, and left out


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def leaf_norms(tree, stacked: bool) -> Dict[str, jnp.ndarray]:
    """fp32 norm of every leaf; leaves under "blocks" per layer. With
    `stacked`, every leaf has a leading client dim that is kept."""
    out = {}
    lead = 1 if stacked else 0
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = _name(path)
        keep = lead + (1 if name.startswith("blocks/") else 0)
        axes = tuple(range(keep, x.ndim))
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                     axis=axes))
    return out


leaf_norms_jit = jax.jit(leaf_norms, static_argnums=1)


def _change(now, before, stacked):
    return leaf_norms(jax.tree.map(lambda x, y: x.astype(jnp.float32)
                                   - y.astype(jnp.float32), now, before),
                      stacked)


change_norms_jit = jax.jit(_change, static_argnums=2)


def change_norms(now, before, stacked: bool) -> Dict[str, np.ndarray]:
    """Per-leaf norms of now - before, in one program (no fp32 copy of
    the whole tree is kept)."""
    return to_host(change_norms_jit(now, before, stacked))


def to_host(norms) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v, np.float64) for k, v in norms.items()}


def _flat(norms: Dict[str, np.ndarray], client: int, stacked: bool
          ) -> Tuple[List[str], np.ndarray]:
    names, vals = [], []
    for k in sorted(norms):
        v = np.atleast_1d(norms[k][client] if stacked else norms[k])
        for i, x in enumerate(v):
            names.append(f"{k}[{i}]" if v.size > 1 else k)
            vals.append(x)
    return names, np.asarray(vals, np.float64)


def norm_gap(got: Dict[str, np.ndarray], ref: List[Dict[str, np.ndarray]],
             grad: List[Dict[str, np.ndarray]] = None
             ) -> Tuple[float, str]:
    """Worst leaf of |got - ref| / max(ref, median ref leaf), over every
    client. `got` leaves carry a leading client dim; `ref` and `grad`
    hold one dict per client. With `grad`, leaves whose reference
    gradient is under TINY_GRAD of the median leaf's are left out."""
    worst, where = 0.0, ""
    for c, r in enumerate(ref):
        names, rv = _flat(r, 0, stacked=False)
        _, gv = _flat(got, c, stacked=True)
        keep = np.ones(rv.shape, bool)
        if grad is not None:
            _, gr = _flat(grad[c], 0, stacked=False)
            keep = gr >= TINY_GRAD * np.median(gr)
        med = np.median(rv[keep])
        gaps = np.abs(gv - rv) / np.maximum(rv, med)
        gaps = np.where(keep, gaps, 0.0)
        if not np.all(np.isfinite(gv[keep])):
            return float("inf"), "non-finite"
        i = int(np.argmax(gaps))
        if gaps[i] > worst or not where:
            worst, where = float(gaps[i]), f"client {c} {names[i]}"
    return worst, where


def readings(prog: Dict, ref: Dict) -> Dict[str, Tuple[float, str]]:
    """The compared numbers. `prog` and `ref` each hold "losses" as
    [round][client][step], "mom" and "change" as leaf norms (program:
    client-stacked dicts; reference: a list of per-client dicts)."""
    pl = np.asarray(prog["losses"], np.float64)
    rl = np.asarray(ref["losses"], np.float64)
    loss = float(np.max(np.abs(pl - rl))) if np.all(np.isfinite(pl)) \
        else float("inf")
    return {
        "loss_gap": (loss, "rounds 1-3"),
        "grad_norm_gap": norm_gap(prog["mom"], ref["mom"]),
        "change_norm_gap": norm_gap(prog["change"], ref["change"],
                                    grad=ref["mom"]),
    }
