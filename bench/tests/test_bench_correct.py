"""`correct` on tiny cells, on CPU host devices: the harness's run past
its look for a chip, sound and with the timed path broken underneath.

A sound run reads correct. Each fault a cell can have reads not
correct: a round that returns its state unchanged, half of the batch
left out of the mean, the exchange between chips left out, a token
altered where the program's feed produces it, a client left out of
what the engine hands to `aggregate`; and the control, the
reference a precision step below the configuration's, put in the
program's place."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny

PHI3 = "phi3-mini-3.8b-slot"
ONE = (PHI3, "silo1.steps2.int8")
FOUR = (PHI3, "silo4.steps2.fp32")


@pytest.mark.parametrize("cell", [ONE, ("mamba2-1.3b-slot",
                                        "silo1.steps2.fp32"), FOUR],
                         ids=lambda c: f"{c[0]}/{c[1]}")
def test_sound_run_is_correct(cell):
    res = bench_tiny.run_tiny(bench_tiny.tiny_cell(*cell))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res["checks"]) == ["loss_gap", "grad_norm_gap",
                                   "change_norm_gap"]
    assert list(res)[-1] == "checks"


def _state_unchanged(monkeypatch):
    from repro.fl.training import MeshTrainerHooks
    monkeypatch.setattr(MeshTrainerHooks, "fedavg",
                        lambda self, p, m, w: (self.params_stk, self.mu_stk))


def _half_batch(monkeypatch):
    from repro.models import lm
    loss_fn = lm.loss_fn

    def half(params, cfg, batch, **kw):
        n = batch["tokens"].shape[-1] // 2
        return loss_fn(params, cfg, {k: v[..., :n] for k, v in
                                     batch.items()}, **kw)
    monkeypatch.setattr(lm, "loss_fn", half)


def _no_exchange(monkeypatch):
    from jax import lax
    monkeypatch.setattr(lax, "psum", lambda x, axis_name: x)


def _token_altered(monkeypatch):
    from repro.fl.training import MeshTrainerHooks
    nb = MeshTrainerHooks.next_batches

    def altered(self):
        b = nb(self)
        lab = b["labels"]
        b["labels"] = lab.at[..., 0].set((lab[..., 0] + 1) % 64)
        return b
    monkeypatch.setattr(MeshTrainerHooks, "next_batches", altered)


def _participant_dropped(monkeypatch):
    from repro.fl.engines.base import BaseEngine
    call = BaseEngine._call_aggregate

    def dropped(self, participants, round_idx, staleness=None):
        return call(self, participants[1:], round_idx, staleness)
    monkeypatch.setattr(BaseEngine, "_call_aggregate", dropped)


FAULTS = {"state_unchanged": (_state_unchanged, [ONE, FOUR]),
          "half_batch": (_half_batch, [ONE, FOUR]),
          "no_exchange": (_no_exchange, [FOUR]),
          "token_altered": (_token_altered, [ONE]),
          "participant_dropped": (_participant_dropped, [FOUR])}


@pytest.mark.parametrize("fault,cell", [(f, c) for f, (_, cs) in
                                        FAULTS.items() for c in cs],
                         ids=lambda x: x if isinstance(x, str) else x[1])
def test_fault_is_not_correct(monkeypatch, fault, cell):
    FAULTS[fault][0](monkeypatch)
    res = bench_tiny.run_tiny(bench_tiny.tiny_cell(*cell))
    assert not res["correct"], res["checks"]


def test_control_is_not_correct():
    """The fp8 reference, put in the program's place, against fp32."""
    import run as bench_run
    from control import as_program
    from harness.check import readings
    from harness.data import client_streams
    cell = bench_tiny.tiny_cell(*ONE)
    seed = 2 ** 31 + 11
    tr = cell.traffic
    streams = client_streams(seed, tr, cell.model["vocab_size"])
    batches = [[[next(s) for _ in range(tr["local_steps"])]
                for s in streams] for _ in range(3)]
    ref = bench_run.reference_readings(cell, seed, batches)
    ctl = bench_run.reference_readings(cell, seed, batches, precision="fp8")
    ok, checks = bench_run.judge(cell, readings(as_program(ctl), ref))
    assert not ok, checks
    same, _ = bench_run.judge(cell, readings(as_program(ref), ref))
    assert same
