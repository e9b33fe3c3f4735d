"""`chip_smoke.py` off the chip: it refuses to run without a TPU, and
its phases pass on CPU at a tiny size (the phi3 smoke config with the
Pallas kernels in TPU interpret mode, clients on host devices)."""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro import configs

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
import chip_smoke as CS  # noqa: E402

SIZES = dict(batch=2, seq=32, local_steps=2)


@pytest.fixture
def cfg():
    return dataclasses.replace(
        configs.get_config("phi3-mini-3.8b", smoke=True), use_pallas=True)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "not a TPU" in r.stderr


def test_round_codec_and_parity_phases(cfg, capsys):
    with pltpu.force_tpu_interpret_mode():
        first = CS.round_phase(cfg, **SIZES)
        CS.parity_phase(cfg, first, **SIZES)
    out = capsys.readouterr().out
    assert "round: per-round losses" in out
    assert "codec: " in out and "parity: " in out


def test_four_chip_phase_on_host_devices(cfg, capsys):
    if jax.device_count() < 4:
        pytest.skip(f"needs 4 devices, found {jax.device_count()}")
    with pltpu.force_tpu_interpret_mode():
        CS.four_chip_phase(cfg, **SIZES)
    out = capsys.readouterr().out
    assert "four-chip fp32" in out and "four-chip int8" in out


def test_four_chip_check_catches_a_wrong_average(cfg, monkeypatch):
    """The fp32-reference comparison fails when the barrier averages
    with other weights than the reference does."""
    if jax.device_count() < 4:
        pytest.skip(f"needs 4 devices, found {jax.device_count()}")
    from repro.fl.training import MeshTrainerHooks
    fedavg = MeshTrainerHooks.fedavg
    monkeypatch.setattr(MeshTrainerHooks, "fedavg",
                        lambda self, p, mu, w: fedavg(self, p, mu, w[::-1]))
    with pltpu.force_tpu_interpret_mode(), \
            pytest.raises(CS.SmokeFailure, match="FedAvg reference"):
        CS.four_chip_phase(cfg, **SIZES)
