"""Training launcher.

  python -m repro.launch.train --arch granite-3-2b --reduced \\
      --steps 200 --seq 128 --batch 8 --ckpt-dir /tmp/ckpt

Full-scale configs target the production mesh (use --mesh data,model on a
real slice); on this CPU container use --reduced for executable runs. The
driver wires: config -> sharded params/opt -> synthetic data pipeline ->
jitted train step -> fault-tolerant runtime (periodic async checkpoints,
preemption-safe, resume-from-latest).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--moe-impl", default="dense")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs import get_config
    from repro.data import SyntheticTokens, make_batch_iterator
    from repro.models import init_params, model_specs
    from repro.optim import cosine_schedule, opt_init_specs
    from repro.runtime import TrainingRuntime
    from repro.sharding.rules import make_rules
    from repro.train.steps import make_train_step

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        cfg = dataclasses.replace(cfg, grad_accum=1)
    rules = make_rules(cfg, None, None)
    specs = model_specs(cfg)
    params = init_params(specs, jax.random.PRNGKey(0))
    opt = init_params(opt_init_specs(cfg, specs), jax.random.PRNGKey(1),
                      dtype=None)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params:,} vocab={cfg.vocab_size}")

    sched = lambda s: cosine_schedule(s, peak_lr=args.lr, warmup=20,
                                      total=args.steps)
    step_raw = jax.jit(make_train_step(cfg, rules, moe_impl=args.moe_impl,
                                       schedule=sched))

    ds = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=args.seq,
                         global_batch=args.batch, seed=0)
    rt = TrainingRuntime(args.ckpt_dir, ckpt_every=args.ckpt_every,
                         install_signal_handlers=True)
    state = {"params": params, "opt": opt}
    start = 0
    if args.resume:
        state, start, _ = rt.maybe_restore(state)
        print(f"resumed at step {start}")

    def step_fn(state, batch):
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        p, o, m = step_raw(state["params"], state["opt"], b)
        return {"params": p, "opt": o}, m

    it = make_batch_iterator(ds, start_step=start)
    t0 = time.time()
    state, step, preempted = rt.run(state, it, step_fn, start_step=start,
                                    total_steps=args.steps,
                                    log_every=args.log_every)
    it.close()
    dt = time.time() - t0
    toks = (step - start) * args.batch * args.seq
    print(f"done: {step - start} steps in {dt:.1f}s "
          f"({toks/max(dt,1e-9):.0f} tok/s){' [preempted]' if preempted else ''}")


if __name__ == "__main__":
    main()
