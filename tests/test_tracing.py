"""The program's tracer (repro.common.tracing): spans and counters of the
FL round on host devices, their profiler annotations, and the device
scopes the round programs name."""
import functools
import glob
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common import tracing
from repro.common.config import CloudConfig, ClientProfile, FLRunConfig
from repro.fl.runner import FLCloudRunner
from repro.fl import training
from repro.fl.training import MeshTrainerHooks, make_round_programs
from repro.models import lm

HOOK_SPANS = ["fl.next_batches", "fl.local_dispatch", "fl.fedavg_dispatch",
              "fl.loss_fetch"]
INIT_SPANS = ["fl.build_programs", "fl.init_params", "fl.streams"]


@pytest.fixture(autouse=True)
def tracing_off():
    """Every test starts and ends with tracing off."""
    assert tracing._recorder is None
    yield
    if tracing._recorder is not None:
        tracing.stop()


def make_hooks(n_clients):
    if jax.device_count() < n_clients:
        pytest.skip(f"needs {n_clients} devices, found "
                    f"{jax.device_count()}")
    return MeshTrainerHooks([f"client_{i}" for i in range(n_clients)],
                            local_steps=1, batch=2, seq=8)


def run(hooks, rounds):
    clients = tuple(ClientProfile(c, mean_epoch_s=60.0, jitter=0.0)
                    for c in hooks.clients)
    cfg = FLRunConfig(dataset="t", clients=clients, n_epochs=rounds,
                      policy="fedcostaware", seed=0)
    return FLCloudRunner(cfg, cloud_cfg=CloudConfig(spot_rate_sigma=0.0),
                         hooks=hooks).run()


def children(rec, i):
    return [s for s in rec.spans if s.parent == i]


def test_off_records_nothing():
    assert tracing.span("fl.run") is tracing.span("fl.aggregate", round=3)
    tracing.count("rounds")
    hooks = make_hooks(1)
    res = run(hooks, rounds=1)
    assert res.rounds_completed == 1
    assert tracing._recorder is None
    rec = tracing.start()
    assert rec.spans == [] and rec.counters == {}
    assert tracing.stop() is rec


def test_start_and_stop_pair():
    tracing.start()
    with pytest.raises(RuntimeError, match="already on"):
        tracing.start()
    tracing.stop()
    with pytest.raises(RuntimeError, match="off"):
        tracing.stop()


@pytest.mark.parametrize("n_clients", [1, 4])
def test_round_spans_and_counters(n_clients):
    rec = tracing.start()
    hooks = make_hooks(n_clients)
    compiles = []          # compiles and cache loads, after each round
    aggregate = hooks.aggregate

    def counted(participants, round_idx, staleness=None):
        aggregate(participants, round_idx, staleness)
        compiles.append(rec.counters.get("compiles", 0))

    hooks.aggregate = counted
    at_init = rec.counters.get("compiles", 0)
    res = run(hooks, rounds=3)
    tracing.stop()
    assert res.rounds_completed == 3

    names = [s.name for s in rec.spans]
    assert names.count("fl.hooks_init") == 1
    init = names.index("fl.hooks_init")
    assert [s.name for s in children(rec, init)] == INIT_SPANS

    (top,) = [i for i, s in enumerate(rec.spans) if s.name == "fl.run"]
    assert rec.spans[top].parent is None
    aggs = [i for i, s in enumerate(rec.spans) if s.name == "fl.aggregate"]
    assert [s.name for s in children(rec, top)] == ["fl.aggregate"] * 3
    assert [rec.spans[i].round for i in aggs] == \
        [r["round"] for r in hooks.losses]
    assert len({rec.spans[i].round for i in aggs}) == 3
    for i in aggs:
        agg = rec.spans[i]
        kids = children(rec, i)
        assert [s.name for s in kids] == HOOK_SPANS
        assert all(s.round == agg.round for s in kids)
        assert all(agg.start_ns <= s.start_ns <= s.end_ns <= agg.end_ns
                   for s in kids)
    run_span = rec.spans[top]
    assert all(run_span.start_ns <= rec.spans[i].start_ns
               and rec.spans[i].end_ns <= run_span.end_ns for i in aggs)

    assert rec.counters["rounds"] == 3
    assert compiles[0] > at_init and compiles[1:] == [compiles[0]] * 2
    assert rec.counters["compile_s"] > 0
    assert rec.counters.get("cache_hits", 0) <= compiles[0]


def test_run_span_closes_when_aggregate_raises():
    class Stop(Exception):
        pass

    rec = tracing.start()
    hooks = make_hooks(1)

    def stop(*_, **__):
        raise Stop

    hooks.aggregate = stop
    with pytest.raises(Stop):
        run(hooks, rounds=2)
    tracing.stop()
    (span,) = rec.named("fl.run")
    assert span.end_ns >= span.start_ns
    assert [s.name for s in rec.named("fl.aggregate")] == ["fl.aggregate"]


def test_self_time():
    rec = tracing.Recorder()
    S = tracing.Span
    rec.spans = [S("fl.run", 0, 100, None, None),
                 S("fl.aggregate", 10, 40, 0, 0),
                 S("fl.loss_fetch", 20, 30, 1, 0),
                 S("fl.aggregate", 50, 90, 0, 1),
                 S("fl.loss_fetch", 60, 85, 3, 1)]
    assert rec.self_ns(0) == 100 - 30 - 40
    assert rec.self_ns(1) == 30 - 10
    assert rec.self_ns(2) == 10
    assert rec.self_ns(3) == 40 - 25
    assert [s.round for s in rec.named("fl.loss_fetch")] == [0, 1]


def test_annotations_in_the_profiler_trace(tmp_path):
    from jax._src.profiler import ProfileData
    hooks = make_hooks(1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        rec = tracing.start()
        run(hooks, rounds=1)
        tracing.stop()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    traced = {e.name for plane in pd.planes if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith("fl.")}
    assert traced == {s.name for s in rec.spans}
    assert traced >= {"fl.run", "fl.aggregate", *HOOK_SPANS}


# each part of the round programs, by the `op_name` segment that names
# it in the compiled HLO: differentiation wraps the forward scope as
# `jvp(forward)`, and the backward pass as `transpose(jvp(forward))`
PARTS = {
    "forward": f"jvp({training.FORWARD})",
    "backward": f"transpose(jvp({training.FORWARD}))",
    "optimizer": training.OPTIMIZER,
    "delta": training.DELTA,
    "codec": training.CODEC,
    "sum": training.SUM,
}


@functools.lru_cache(maxsize=None)
def compiled_parts(quantize):
    """The parts named in the compiled HLO of a tiny round pair:
    {"local": {...}, "fedavg": {...}}."""
    from repro import configs
    cfg = configs.get_config("phi3-mini-3.8b", smoke=True)
    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:1])
    stk = NamedSharding(mesh, P("pod"))
    local, fedavg = make_round_programs(cfg, mesh, lr=5e-3,
                                        quantize=quantize, use_pallas=False)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=stk)
    params = jax.tree.map(lambda a: sds((1,) + a.shape, a.dtype),
                          lm.abstract_params(cfg))
    mu = jax.tree.map(lambda a: sds(a.shape, jnp.float32), params)
    batches = {k: sds((1, 1, 2, 8), jnp.int32) for k in ("tokens", "labels")}
    w = sds((1,), jnp.float32)
    texts = {"local": local.lower(params, mu, batches).compile().as_text(),
             "fedavg": fedavg.lower(params, params, mu, mu, w)
             .compile().as_text()}
    out = {}
    for program, text in texts.items():
        segments = {seg for op_name in re.findall(r'op_name="([^"]*)"', text)
                    for seg in op_name.split("/")}
        out[program] = {part for part, seg in PARTS.items()
                        if seg in segments}
    return out


@pytest.mark.parametrize("quantize", [False, True])
@pytest.mark.parametrize("program, part", [
    ("local", "forward"), ("local", "backward"), ("local", "optimizer"),
    ("fedavg", "delta"), ("fedavg", "codec"), ("fedavg", "sum"),
])
def test_round_programs_name_their_scopes(quantize, program, part):
    """Every scope the round's programs name reaches its program's
    compiled HLO: forward, backward and optimizer in the local program;
    delta, sum and, only when quantizing, codec in FedAvg."""
    present = quantize if part == "codec" else True
    assert (part in compiled_parts(quantize)[program]) == present


@pytest.mark.parametrize("quantize", [False, True])
def test_scopes_stay_in_their_program(quantize):
    """No op of one program carries the other program's scopes."""
    parts = compiled_parts(quantize)
    assert not parts["local"] & {"delta", "codec", "sum"}
    assert not parts["fedavg"] & {"forward", "backward", "optimizer"}
