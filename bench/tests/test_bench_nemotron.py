"""The Nemotron-H slot against its plain reference.

Its configuration file builds the program's cut whole, its reference
counts the cell's work, and on CPU at a small size (the smoke
configuration, fp32, weights made from a seed) the program's loss and
gradients are the reference's, its expert layer drops no token and its
shares add up to the uncut layer, while each planted fault fails the
same comparison.
"""
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from harness import cell as cells
from harness.flops import round_flops_per_client
from reference.common import (abstract, is_spec, make_params, mm_fp32,
                              seed_halves)
from repro.configs import nemotron3_nano_30b as N
from repro.models import layers as L
from repro.models import lm
from repro.models import ssm as S

CELL = "nemotron3nano.1chip.fp32"
SEED = 2 ** 31 + 101

# fp32 on both sides: the program and the reference differ only in the
# order of their sums and in the SSD form (chunked scan against the
# quadratic dual), which moves the loss and each gradient leaf by under
# 3e-6 of its largest entry; 30x that is still far under any fault's gap
LOSS_TOL = 1e-5
GRAD_TOL = 1e-4


def _ref():
    return cells.load_module(bench_tiny.BENCH / "reference" /
                             "nemotron_h_lm.py", "reference.nemotron_h_lm")


def _model(cfg):
    return json.loads(json.dumps(dataclasses.asdict(cfg)))


def _params(cfg, seed=SEED):
    """The reference's weights of `seed`, with each selection bias drawn
    too (std 1/sqrt(experts)): the reference starts it at zero, and the
    comparison must see it choose."""
    params = make_params(_ref().param_specs(_model(cfg)), *seed_halves(seed))
    key = jax.random.key(seed)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: (jax.random.normal(key, a.shape) / math.sqrt(
            a.shape[-1]) if path[-1].key == "router_bias" else a), params)


def _batch(cfg, seed=0, shape=(2, 16)):
    rng = np.random.RandomState(seed)
    return {k: jnp.asarray(rng.randint(0, cfg.vocab_size, shape), jnp.int32)
            for k in ("tokens", "labels")}


def _gaps(cfg, ref_cfg=None):
    """(|loss - reference loss|, worst gradient leaf's max |g - ref| over
    its largest reference entry) of the program under `cfg` against the
    reference under `ref_cfg` (default the same), on the same weights."""
    ref_cfg = ref_cfg or cfg
    params, batch = _params(ref_cfg), _batch(ref_cfg)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: lm.loss_fn(p, cfg, batch))(params)
        lr, gr = jax.value_and_grad(
            lambda p: _ref().loss(_model(ref_cfg), mm_fp32, p,
                                  batch["tokens"], batch["labels"]))(params)
    leaf = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30)), gp, gr)
    return abs(float(lp) - float(lr)), max(jax.tree.leaves(leaf))


def test_config_file_builds_the_cut_whole():
    """The configuration file's "model" is the program's one-chip cut,
    field for field, and the reference's tree is the program's."""
    import run as bench_run
    cell = cells.load_cell(CELL)
    cfg = bench_run.model_config(cell.model)
    assert cfg == N.ONE_CHIP
    want = abstract(cells.reference_module(cell).param_specs(cell.model))
    got = lm.abstract_params(cfg)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all((a.shape, a.dtype) == (b.shape, b.dtype) for a, b in
               zip(jax.tree.leaves(want), jax.tree.leaves(got)))
    assert lm.param_count(cfg) == cfg.param_count() == 528_093_120
    assert N.FULL.param_count() == lm.param_count(N.FULL) == 31_577_940_288


def test_config_file_restates_the_source():
    """The keys beside "model" are the source's config.json; "reduced"
    gives the published and the cut value of each key cut."""
    data = json.loads((bench_tiny.BENCH / "configs" /
                       "nemotron-3-nano-30b-a3b-slot.json").read_text())
    src = data["model"]
    assert data["num_hidden_layers"] == 52 == len(data[
        "hybrid_override_pattern"])
    assert "".join({"mamba2": "M", "moe": "E", "attn": "*"}[k]
                   for k in src["pattern"]) == \
        data["hybrid_override_pattern"][:src["num_layers"]]
    assert (src["d_model"], src["num_heads"], src["num_kv_heads"],
            src["head_dim"]) == (data["hidden_size"],
                                 data["num_attention_heads"],
                                 data["num_key_value_heads"],
                                 data["head_dim"])
    ssm, moe = src["ssm"], src["moe"]
    assert (ssm["num_heads"], ssm["head_dim"], ssm["d_state"],
            ssm["n_groups"], ssm["conv_width"], ssm["chunk_size"]) == (
        data["mamba_num_heads"], data["mamba_head_dim"],
        data["ssm_state_size"], data["n_groups"], data["conv_kernel"],
        data["chunk_size"])
    assert (moe["num_experts"], moe["top_k"], moe["d_ff"],
            moe["shared_d_ff"], moe["routed_scaling"]) == (
        data["n_routed_experts"], data["num_experts_per_tok"],
        data["moe_intermediate_size"],
        data["moe_shared_expert_intermediate_size"],
        data["routed_scaling_factor"])
    cut = data["reduced"]
    assert cut["num_layers"]["published"] == data["num_hidden_layers"]
    assert cut["vocab_size"]["published"] == data["vocab_size"]
    assert cut["moe"]["published"]["held"] is None
    assert cut["moe"]["here"]["held"] == 8


# Model FLOPs of one training step at the cell's batch 2 x 2048, as the
# reference counts them: 6 N over the matmul parameters (routed experts
# at top_k / num_experts of their parameters), plus 3 x the SSD and
# causal attention forwards.
STEP_FLOPS = 6_594_400_616_448
EXPERT_STEP_FLOPS = 1_747_162_497_024


def test_step_flops_of_the_cell_are_pinned():
    cell = cells.load_cell(CELL)
    ref = cells.reference_module(cell)
    tr = cell.traffic
    assert ref.step_flops(cell.model, tr["batch"], tr["seq"]) == STEP_FLOPS
    assert ref.expert_step_flops(cell.model, tr["batch"], tr["seq"]) \
        == EXPERT_STEP_FLOPS
    assert round_flops_per_client(ref, cell.model, tr) == 2 * STEP_FLOPS
    # by hand: held experts 3 layers x 8 x 2 x 2688 x 1856, met by 6/128
    # of the tokens; the shared expert 3 x 2 x 2688 x 3712 by all
    routed = 3 * 8 * 2 * 2688 * 1856
    shared = 3 * 2 * 2688 * 3712
    assert EXPERT_STEP_FLOPS == 6 * (routed * 6 // 128 + shared) * 4096


def test_program_matches_reference():
    loss_gap, grad_gap = _gaps(N.SMOKE)
    assert loss_gap <= LOSS_TOL and grad_gap <= GRAD_TOL, (loss_gap,
                                                          grad_gap)


def _capacity_dropping(monkeypatch):
    """The GShard capacity rule at factor 1.0: each held expert takes
    its first ceil(tokens x k / experts) slots in (k, token) order and
    drops the rest."""
    held_slots = L.held_slots

    def dropping(chosen, m):
        n, k = chosen.shape
        cap = math.ceil(n * k / m.num_experts)
        local = chosen.T.reshape(-1)                     # (k, token) order
        onehot = jax.nn.one_hot(local, m.num_held, dtype=jnp.int32)
        rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
        kept = (rank < cap).reshape(k, n).T
        return held_slots(jnp.where(kept, chosen, m.num_held), m)
    monkeypatch.setattr(L, "held_slots", dropping)


def _held_only_weights(monkeypatch):
    """Weights normalised over the held experts among the chosen."""
    def route(p, x, m):
        s = jax.nn.sigmoid(x.astype(jnp.float32) @ p["router"])
        _, chosen = jax.lax.top_k(s + p["router_bias"], m.top_k)
        w = jnp.take_along_axis(s, chosen, axis=1)
        held = chosen < m.num_held
        return chosen, m.routed_scaling * w / jnp.maximum(
            jnp.sum(jnp.where(held, w, 0.0), -1, keepdims=True), 1e-9)
    monkeypatch.setattr(L, "route", route)


def _ungrouped_norm(monkeypatch):
    gated_norm = S.gated_norm
    monkeypatch.setattr(S, "gated_norm", lambda y, z, scale, g, eps:
                        gated_norm(y, z, scale, 1, eps))


FAULTS = {"capacity_dropping": _capacity_dropping,
          "held_only_weights": _held_only_weights,
          "ungrouped_gated_norm": _ungrouped_norm}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["rope_applied"])
def test_planted_fault_fails_the_comparison(monkeypatch, fault):
    cfg = N.SMOKE
    if fault == "rope_applied":
        cfg = dataclasses.replace(cfg, rope_theta=10_000.0)
    else:
        FAULTS[fault](monkeypatch)
    loss_gap, grad_gap = _gaps(cfg, ref_cfg=N.SMOKE)
    assert loss_gap > LOSS_TOL or grad_gap > GRAD_TOL, (loss_gap, grad_gap)


def _expert_layer(cfg, seed=SEED):
    """The first expert layer's weights and its normed input at the
    smoke size, for the program and the reference alike."""
    params = _params(cfg, seed)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["01_moe"]["mix"])
    x = jax.random.normal(jax.random.key(seed), (2, 16, cfg.d_model))
    return p, x


def _layer_ref(cfg, p, x):
    with jax.default_matmul_precision("highest"):
        return _ref()._experts(_model(cfg), mm_fp32, x, p)


def _layer_prog(cfg, p, x):
    """The program's layer output, and the slots routed to each held
    expert."""
    with jax.default_matmul_precision("highest"):
        chosen, _ = L.route(p, x.reshape(-1, x.shape[-1]), cfg.moe)
        return L.held_experts(p, x, cfg), L.held_slots(chosen, cfg.moe)[1]


def _with_moe(cfg, **kw):
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **kw))


def test_shares_add_up_to_the_uncut_layer():
    """Four chips holding 4 of 16 experts each: their parts, the shared
    expert counted once, are the layer with all 16 held."""
    whole = _with_moe(N.SMOKE, held=None)
    p, x = _expert_layer(whole)
    full, slots = _layer_prog(whole, p, x)
    np.testing.assert_allclose(full, _layer_ref(whole, p, x), rtol=1e-5,
                               atol=1e-5)
    held = N.SMOKE.moe.held
    parts, counts = [], []
    for first in range(0, whole.moe.num_experts, held):
        # the chip holding experts first .. first + held - 1: the same
        # router with its experts rolled so that they come first
        ps = dict(p, router=jnp.roll(p["router"], -first, axis=1),
                  router_bias=jnp.roll(p["router_bias"], -first),
                  wi=p["wi"][first:first + held],
                  wo=p["wo"][first:first + held])
        y, c = _layer_prog(N.SMOKE, ps, x)
        parts.append(y)
        counts.append(c)
    sh = p["shared"]
    shared = jnp.square(jax.nn.relu(x @ sh["wi"])) @ sh["wo"]
    total = sum(parts) - (len(parts) - 1) * shared
    np.testing.assert_allclose(total, full, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(counts), slots)
    assert int(slots.sum()) == x.shape[0] * x.shape[1] * whole.moe.top_k


def test_no_token_is_dropped_when_all_go_to_one_expert():
    """Every token's top k holds held expert 0: it computes all of them,
    as the dense reference does."""
    cfg = N.SMOKE
    p, x = _expert_layer(cfg)
    p = dict(p, router_bias=p["router_bias"].at[0].set(100.0))
    y, slots = _layer_prog(cfg, p, x)
    n = x.shape[0] * x.shape[1]
    assert int(slots[0]) == n
    np.testing.assert_allclose(y, _layer_ref(cfg, p, x), rtol=1e-5,
                               atol=1e-5)


def test_decode_matches_forward():
    """The cached one-token path (grouped Mamba-2 state, uncached expert
    layers) reproduces the teacher-forced logits."""
    cfg = N.SMOKE
    params = _params(cfg)
    toks = _batch(cfg, seed=1)["tokens"]
    full, _ = lm.forward(params, cfg, toks)
    cache = lm.init_cache(cfg, 2, 16)
    step = jax.jit(lambda p, t, pos, c: lm.decode_step(p, cfg, t, pos, c))
    outs = []
    for t in range(16):
        lg, cache = step(params, toks[:, t:t + 1],
                         jnp.full((2,), t, jnp.int32), cache)
        outs.append(lg[:, 0])
    err = float(jnp.max(jnp.abs(jnp.stack(outs, axis=1) - full)))
    assert err < 2e-3, err


@pytest.mark.parametrize("config", ["phi3-mini-3.8b-slot",
                                    "mamba2-1.3b-slot"])
def test_other_slots_keep_their_trees(config):
    """The benchmark's other configurations keep their parameter trees at
    full size: no MLP dropped, no leaf added."""
    import run as bench_run
    data = json.loads((bench_tiny.BENCH / "configs" /
                       f"{config}.json").read_text())
    ref = cells.load_module(bench_tiny.BENCH / "reference" /
                            f"{data['reference']}.py",
                            f"reference.{data['reference']}")
    want = abstract(ref.param_specs(data["model"]))
    got = lm.abstract_params(bench_run.model_config(data["model"]))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all((a.shape, a.dtype) == (b.shape, b.dtype) for a, b in
               zip(jax.tree.leaves(want), jax.tree.leaves(got)))


def test_tiny_cell_runs_correct(monkeypatch):
    """The harness's whole run on CPU at the smoke size, bf16 as the cell
    runs, the flash-attention kernel interpreted, with each selection
    bias drawn (the reference starts it at zero) so that the run sees it
    choose."""
    load = cells.reference_module

    def with_seeded_bias(cell):
        mod = load(cell)
        specs = mod.param_specs
        mod.param_specs = lambda model: jax.tree_util.tree_map_with_path(
            lambda path, s: (dataclasses.replace(
                s, init="normal", std=1.0 / math.sqrt(s.shape[-1]))
                if path[-1].key == "router_bias" else s),
            specs(model), is_leaf=is_spec)
        return mod
    monkeypatch.setattr(cells, "reference_module", with_seeded_bias)
    cell = bench_tiny.tiny_cell("nemotron-3-nano-30b-a3b-slot",
                                "silo1.steps2.b2.fp32")
    cell.config["model"] = dict(_model(N.SMOKE), dtype="bfloat16",
                                param_dtype="bfloat16", remat=False,
                                use_pallas=True)
    res = bench_tiny.run_tiny(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


US = 1e3
LOCAL = "jit_local_train"


@pytest.fixture(scope="module")
def programs():
    """The smoke configuration's round programs' op_names, as the
    harness reads them."""
    import run as bench_run
    from repro.fl.training import MeshTrainerHooks
    hooks = MeshTrainerHooks(["c0"], cfg=N.SMOKE, local_steps=2, batch=2,
                             seq=16)
    return bench_run.program_op_names(hooks, 2, 16)


def _record(programs):
    """Two runs of each program, one op per instruction laid end to end
    (op i lasts 1 + i % 5 us), on one chip."""
    import run as bench_run
    from harness import trace as T
    from harness.clock import Spans
    from harness.peaks import chip_peaks
    t, ops, modules = 0.0, [], []
    for _ in range(2):
        for prog in sorted(programs):
            start = t
            for i, n in enumerate(sorted(programs[prog])):
                ops.append([n, "fusion", "", t, (1 + i % 5) * US])
                t += (1 + i % 5) * US
            modules.append([f"{prog}(1)", start, t - start])
    trace = {"devices": [{"id": 0, "ops": ops, "modules": modules}],
             "host": [["bench.window", 0.0, t]]}
    lo, hi = T.window(trace)
    win = {"rounds": 2, "t0": lo / 1e9, "t_close": hi / 1e9}
    return bench_run.Record(cells.load_cell(CELL), trace, win, Spans(),
                            chip_peaks("TPU v5 lite"), programs)


def _ms(programs, pick):
    names = sorted(programs[LOCAL])
    return sum((1 + i % 5) * US for i, n in enumerate(names)
               if pick(n, programs[LOCAL][n])) / 1e6


def test_scopes_of_the_expert_and_ssm_layers(programs):
    """expert_layer_ms reads the `expert_layer` scope, which holds the
    grouped matmuls; ssm_mixer_ms the `ssm_mixer` scope, both passes;
    expert_roofline the reference's expert work over that time."""
    from harness import trace as T
    r = _record(programs)
    local = programs[LOCAL]
    for scope in ("expert_layer", "ssm_mixer"):
        for pass_ in ("forward", "backward"):
            assert any(T.in_scope(op, scope) and T.in_scope(op, "forward",
                                                            pass_)
                       for op in local.values()), (scope, pass_)
    for part in ("router", "experts", "shared_expert"):
        assert all(T.in_scope(op, "expert_layer") for op in local.values()
                   if T.in_scope(op, part))
    assert any(T.in_scope(op, "experts") and "dot_general" in op
               for op in local.values())
    expert = _ms(programs, lambda n, op: T.in_scope(op, "expert_layer"))
    ssm = _ms(programs, lambda n, op: T.in_scope(op, "ssm_mixer"))
    assert expert > 0
    assert cells.metric_reader("expert_layer_ms")(r) == \
        pytest.approx(expert, rel=1e-12)
    assert cells.metric_reader("ssm_mixer_ms")(r) == \
        pytest.approx(ssm, rel=1e-12)
    want = 100.0 * 2 * EXPERT_STEP_FLOPS / 197e12 / (expert / 1e3)
    assert cells.metric_reader("expert_roofline")(r) == \
        pytest.approx(want, rel=1e-12)


def test_no_expert_scope_reads_nothing(programs):
    """A program without the scopes (as the parent commit's) gives no
    value, never 0, and raises nothing."""
    bare = {p: {n: op.replace("expert_layer", "x").replace("ssm_mixer", "y")
                for n, op in names.items()}
            for p, names in programs.items()}
    r = _record(bare)
    for name in ("expert_layer_ms", "expert_roofline", "ssm_mixer_ms"):
        assert cells.metric_reader(name)(r) is None


def test_rows_outside_the_groups_reach_nothing(monkeypatch):
    """A grouped matmul that leaves the rows outside its groups unwritten,
    in its result and in its transpose's (NaN here, as the TPU's may
    hold anything), changes neither the loss nor any gradient."""
    ragged_dot = jax.lax.ragged_dot

    @jax.custom_vjp
    def unwritten(lhs, rhs, sizes):
        inside = jnp.arange(lhs.shape[0]) < jnp.sum(sizes)
        return jnp.where(inside[:, None], ragged_dot(lhs, rhs, sizes),
                         jnp.nan)

    def fwd(lhs, rhs, sizes):
        return unwritten(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: ragged_dot(a, b, sizes), lhs, rhs)
        dl, dr = vjp(g)
        inside = jnp.arange(lhs.shape[0]) < jnp.sum(sizes)
        return (jnp.where(inside[:, None], dl, jnp.nan), dr,
                np.zeros(sizes.shape, jax.dtypes.float0))

    unwritten.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    loss_gap, grad_gap = _gaps(N.SMOKE)
    assert loss_gap <= LOSS_TOL and grad_gap <= GRAD_TOL, (loss_gap,
                                                          grad_gap)
