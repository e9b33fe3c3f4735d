"""Multi-pod dry-run: AOT-lower + compile every (arch x shape) cell on the
production meshes and extract memory / cost / collective analyses.

Runs on 512 host devices, which the XLA flag must provide before jax
starts, so run it as its own process::

    XLA_FLAGS=--xla_force_host_platform_device_count=512 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python -m repro.launch.dryrun --arch all \
        --shape all --mesh both

Also lowers the FL-in-the-mesh round step (the paper-representative
program) when ``--fl-round`` is passed.
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.common.config import SHAPES
from repro.configs.shapes import input_specs
from repro.launch import mesh as M
from repro.launch import roofline as RF
from repro.launch import steps as ST
from repro.models import lm
from repro.optim import optimizers
from repro.sharding import rules as R


def abstract_opt_state(cfg):
    p = lm.abstract_params(cfg)
    f32 = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p)
    return optimizers.OptState(
        step=jax.ShapeDtypeStruct((), jnp.int32), mu=f32, nu=f32)


def lower_cell(arch: str, shape_name: str, mesh, rule_overrides=None):
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    jitted, _ = ST.jit_step_for(cfg, shape, mesh,
                                rule_overrides=rule_overrides)
    specs = input_specs(cfg, shape)
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            lowered = jitted.lower(lm.abstract_params(cfg),
                                   abstract_opt_state(cfg), specs["batch"])
        elif shape.kind == "prefill":
            args = [lm.abstract_params(cfg), specs["tokens"]]
            if cfg.family == "vlm":
                args.append(specs["cond"])
            lowered = jitted.lower(*args)
        else:
            lowered = jitted.lower(lm.abstract_params(cfg),
                                   specs["tokens"], specs["pos"],
                                   specs["cache"])
    return cfg, shape, lowered


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             rule_overrides=None, verbose: bool = True):
    t0 = time.time()
    cfg, shape, lowered = lower_cell(arch, shape_name, mesh, rule_overrides)
    compiled = lowered.compile()
    t1 = time.time()

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception as e:   # backend-dependent
        mem["error"] = str(e)

    n_chips = M.mesh_chips(mesh)
    trip = max(cfg.n_super, 1)
    rl = RF.analyze(compiled, n_chips=n_chips, scan_trip_count=trip,
                    model_flops_global=RF.model_flops(cfg, shape))
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": n_chips, "compile_s": round(t1 - t0, 1),
        "memory": mem, "roofline": rl.as_dict(),
        "params": lm.param_count(cfg),
    }
    if verbose:
        dom = rl.dominant
        print(f"[OK] {arch:24s} {shape_name:12s} {mesh_name:6s} "
              f"compile={t1-t0:6.1f}s flops/dev={rl.flops:.3e} "
              f"bytes/dev={rl.bytes_accessed:.3e} "
              f"coll/dev={rl.collective_bytes:.3e} dom={dom} "
              f"useful={rl.useful_ratio:.2f}")
        if mem and "error" not in mem:
            print(f"     memory_analysis: {mem}")
    return rec


def run_fl_round(mesh, mesh_name: str, arch: str = "phi3-mini-3.8b",
                 local_steps: int = 4, compressed: bool = False,
                 verbose: bool = True):
    """Lower the FL-in-the-mesh round step (paper-representative cell)."""
    from repro.fl import mesh_fl
    cfg = configs.get_config(arch)
    n_pods = mesh.shape.get("pod", 1)
    n_clients = max(n_pods, 1)
    rules = R.make_rules("train")
    shard = R.ShardingCtx(mesh, rules)
    step = mesh_fl.make_fl_round_step(
        cfg, opt=3e-4, shard=shard, local_steps=local_steps,
        compressed=compressed, mesh=mesh, n_pods=n_clients)

    p_abs = lm.abstract_params(cfg)
    stack = lambda s, extra=(): jax.ShapeDtypeStruct(
        (n_clients,) + tuple(extra) + s.shape, s.dtype)
    params_stk = jax.tree.map(lambda s: stack(s), p_abs)
    mu_stk = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_clients,) + s.shape, jnp.float32),
        p_abs)
    B_local, S = 16, 4096
    batches = {
        "tokens": jax.ShapeDtypeStruct(
            (n_clients, local_steps, B_local, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct(
            (n_clients, local_steps, B_local, S), jnp.int32),
    }
    weights = jax.ShapeDtypeStruct((n_clients,), jnp.float32)

    def shard_stacked(axes_tree):
        return jax.tree.map(
            lambda axes: R.resolve_sharding(("fl_clients",) + axes, rules,
                                            mesh),
            axes_tree,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x))

    pshard = shard_stacked(lm.logical_axes(cfg))
    mushard = pshard
    bshard = {
        "tokens": R.resolve_sharding(("fl_clients", None, "fl_batch", None),
                                     rules, mesh),
        "labels": R.resolve_sharding(("fl_clients", None, "fl_batch", None),
                                     rules, mesh),
    }
    wshard = R.resolve_sharding(("fl_clients",), rules, mesh)
    jitted = jax.jit(step, in_shardings=(pshard, mushard, bshard, wshard),
                     out_shardings=(pshard, mushard, wshard))
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jitted.lower(params_stk, mu_stk, batches, weights)
        compiled = lowered.compile()
    t1 = time.time()
    trip = max(configs.get_config(arch).n_super, 1) * local_steps
    rl = RF.analyze(compiled, n_chips=M.mesh_chips(mesh),
                    scan_trip_count=trip,
                    model_flops_global=6.0 * lm.param_count(cfg)
                    * n_clients * local_steps * B_local * S)
    rec = {"arch": arch, "shape": f"fl_round(ls={local_steps},"
           f"compressed={compressed})", "mesh": mesh_name,
           "chips": M.mesh_chips(mesh), "compile_s": round(t1 - t0, 1),
           "roofline": rl.as_dict()}
    if verbose:
        print(f"[OK] FL-round {arch} {mesh_name} compressed={compressed} "
              f"compile={t1-t0:.1f}s coll/dev={rl.collective_bytes:.3e}")
    return rec


def run_fl_agg(mesh, mesh_name: str, arch: str = "phi3-mini-3.8b",
               compressed: bool = False, verbose: bool = True):
    """Lower ONLY the synchronous FedAvg aggregation (the paper's round
    barrier) to isolate its collective cost: plain bf16 weighted average
    vs int8-ring compressed (beyond-paper)."""
    from repro.fl import mesh_fl
    cfg = configs.get_config(arch)
    n_pods = mesh.shape.get("pod", 1)
    n_clients = max(n_pods, 1)
    rules = R.make_rules("train")
    p_abs = lm.abstract_params(cfg)
    params_stk = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_clients,) + s.shape, s.dtype),
        p_abs)
    weights = jax.ShapeDtypeStruct((n_clients,), jnp.float32)

    def shard_stacked(axes_tree):
        return jax.tree.map(
            lambda axes: R.resolve_sharding(("fl_clients",) + axes, rules,
                                            mesh),
            axes_tree,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x))

    pshard = shard_stacked(lm.logical_axes(cfg))
    if compressed:
        gshard = ST.param_shardings(cfg, rules, mesh)
        g_abs = p_abs
        specs = jax.tree.map(lambda s: s.spec, pshard)

        def agg(p_stk, g, w):
            return mesh_fl.fedavg_sync_compressed(p_stk, g, w, mesh,
                                                  n_clients,
                                                  stacked_specs=specs)

        jitted = jax.jit(agg, in_shardings=(pshard, gshard, None),
                         out_shardings=pshard)
        args_ = (params_stk, g_abs, weights)
    else:
        jitted = jax.jit(lambda p, w: mesh_fl.fedavg_sync(p, w),
                         in_shardings=(pshard, None),
                         out_shardings=pshard)
        args_ = (params_stk, weights)
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args_)
        compiled = lowered.compile()
    t1 = time.time()
    rl = RF.analyze(compiled, n_chips=M.mesh_chips(mesh), scan_trip_count=1,
                    model_flops_global=0.0)
    rec = {"arch": arch,
           "shape": f"fl_agg(compressed={compressed})", "mesh": mesh_name,
           "chips": M.mesh_chips(mesh), "compile_s": round(t1 - t0, 1),
           "roofline": rl.as_dict()}
    if verbose:
        print(f"[OK] FL-agg {arch} {mesh_name} compressed={compressed} "
              f"coll/dev={rl.collective_bytes:.3e} "
              f"by_kind={rl.collective_by_kind}")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--fl-round", action="store_true")
    ap.add_argument("--fl-agg", action="store_true")
    ap.add_argument("--compressed", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun.json")
    ap.add_argument("--fail-fast", action="store_true")
    args = ap.parse_args(argv)

    if jax.device_count() < 512:
        raise SystemExit(
            f"dry-run needs 512 devices, found {jax.device_count()}: set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "starting it")

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single", M.make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi", M.make_production_mesh(multi_pod=True)))

    records, failures = [], []
    if args.fl_agg:
        for name, mesh in meshes:
            records.append(run_fl_agg(mesh, name,
                                      compressed=args.compressed))
    elif args.fl_round:
        for name, mesh in meshes:
            records.append(run_fl_round(mesh, name,
                                        compressed=args.compressed))
    else:
        archs = configs.ARCH_IDS if args.arch == "all" else [args.arch]
        for arch in archs:
            shapes = (configs.applicable_shapes(arch)
                      if args.shape == "all" else [args.shape])
            for shape_name in shapes:
                for mesh_name, mesh in meshes:
                    try:
                        records.append(
                            run_cell(arch, shape_name, mesh, mesh_name))
                    except Exception as e:
                        failures.append((arch, shape_name, mesh_name,
                                         repr(e)))
                        print(f"[FAIL] {arch} {shape_name} {mesh_name}: "
                              f"{e}", file=sys.stderr)
                        traceback.print_exc()
                        if args.fail_fast:
                            raise

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    existing = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            existing = json.load(f)
    keyed = {(r["arch"], r["shape"], r["mesh"]): r for r in existing}
    for r in records:
        keyed[(r["arch"], r["shape"], r["mesh"])] = r
    with open(args.out, "w") as f:
        json.dump(list(keyed.values()), f, indent=1)
    print(f"\n{len(records)} cells OK, {len(failures)} failed "
          f"-> {args.out}")
    if failures:
        for f_ in failures:
            print("  FAILED:", *f_)
        sys.exit(1)


if __name__ == "__main__":
    main()
