"""Training driver: runs real steps of any ``--arch`` (smoke scale on CPU,
full scale on a TPU mesh) with checkpoint/restart fault tolerance.

  PYTHONPATH=src python -m repro.launch.train --arch phi3-mini-3.8b \\
      --smoke --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

Restart the same command after killing it mid-run: training resumes from
the latest checkpoint (the FedCostAware fault-tolerance path, §III-D).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.ckpt import Checkpointer
from repro.checkpoint.store import FileStore
from repro.common.compile_cache import enable_compile_cache
from repro.data.synthetic import token_stream
from repro.launch import steps as ST
from repro.models import lm
from repro.optim import optimizers


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get_config(args.arch, smoke=args.smoke)
    train_step, opt = ST.make_train_step(cfg, lr=args.lr)
    train_step = jax.jit(train_step, donate_argnums=(0, 1))

    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    opt_state = opt.init(params)
    start_step = 0

    ck = None
    if args.ckpt_dir:
        ck = Checkpointer(FileStore(args.ckpt_dir))
        latest = ck.latest_step(f"{args.arch}")
        if latest is not None:
            tpl = {"params": params, "opt": opt_state}
            saved = ck.restore(f"{args.arch}/step={latest}", template=tpl)
            params, opt_state = saved["params"], saved["opt"]
            start_step = latest
            print(f"resumed from checkpoint step {latest}")

    stream = token_stream(cfg.vocab_size, args.batch, args.seq, seed=1)
    for _ in range(start_step):      # keep the data stream deterministic
        next(stream)

    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(stream).items()}
        if cfg.family == "audio":
            rng = np.random.RandomState(step)
            batch["tokens"] = jnp.asarray(
                rng.randn(args.batch, args.seq, cfg.d_model), jnp.float32)
        if cfg.family == "vlm":
            batch["cond"] = jnp.zeros(
                (args.batch, cfg.n_cond_tokens, cfg.d_model),
                cfg.activation_dtype)
        params, opt_state, metrics = train_step(params, opt_state, batch)
        if (step + 1) % args.log_every == 0:
            dt = (time.time() - t0) / args.log_every
            print(f"step {step+1:5d} loss {float(metrics['loss']):.4f} "
                  f"({dt*1e3:.0f} ms/step)")
            t0 = time.time()
        if ck is not None and (step + 1) % args.ckpt_every == 0:
            ck.save(f"{args.arch}/step={step+1}",
                    {"params": params, "opt": opt_state})
    print("done.")


if __name__ == "__main__":
    main()
