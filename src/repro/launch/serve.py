"""Serving launcher: batched prefill/decode with slot recycling.

  python -m repro.launch.serve --arch granite-3-2b --reduced \\
      --requests 8 --slots 4 --max-new 16

``--st-mode st|host|fused`` routes the decode step's collectives
through scheduled triggered-op programs (repro.serving.st_decode), one
cached schedule per active-slot bucket; ``--st-config auto`` resolves
each bucket's schedule from the tuned cache (autotuning on a miss),
``--st-config default`` pins the default ScheduleConfig.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--moe-impl", default="dense")
    ap.add_argument("--st-mode", default=None,
                    choices=["st", "host", "fused"],
                    help="route decode collectives through scheduled "
                         "triggered-op programs (default: plain jitted "
                         "baseline)")
    ap.add_argument("--st-config", default="auto",
                    help="'auto' (tuned cache), 'default', or a "
                         "ScheduleConfig JSON object")
    ap.add_argument("--tuned", default=None,
                    help="tuned-cache path for --st-config auto")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs import get_config
    from repro.models import init_params, model_specs
    from repro.serving import Request, ServingEngine
    from repro.sharding.rules import make_rules

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rules = make_rules(cfg, None, None)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    st_config = args.st_config
    if st_config == "default":
        from repro.core.autotune import ScheduleConfig
        st_config = ScheduleConfig()
    elif st_config not in ("auto",):
        import json
        from repro.core.autotune import ScheduleConfig
        st_config = ScheduleConfig.from_dict(json.loads(st_config))
    eng = ServingEngine(cfg, params, rules, batch_slots=args.slots,
                        max_len=args.max_len, moe_impl=args.moe_impl,
                        st_mode=args.st_mode, st_config=st_config,
                        tuned_path=args.tuned)

    rng = np.random.RandomState(0)
    t0 = time.time()
    for i in range(args.requests):
        L = rng.randint(4, 16)
        eng.submit(Request(prompt=rng.randint(1, cfg.vocab_size, L)
                           .astype(np.int32),
                           max_new_tokens=args.max_new))
    steps = eng.run_until_drained()
    dt = time.time() - t0
    new_toks = sum(len(r.out_tokens) for r in eng.completed)
    lat = [r.done_at - r.submitted_at for r in eng.completed]
    print(f"served {len(eng.completed)} requests, {new_toks} tokens in "
          f"{dt:.2f}s over {steps} engine steps "
          f"({new_toks/max(dt,1e-9):.1f} tok/s)")
    print(f"latency p50={np.percentile(lat,50)*1e3:.0f}ms "
          f"p99={np.percentile(lat,99)*1e3:.0f}ms")
    if args.st_mode:
        st = eng.stats()["st"]
        buckets = {b: m["dispatches"] for b, m in st["buckets"].items()}
        print(f"st decode path: mode={st['mode']} pattern={st['pattern']}"
              f" dispatches per slot bucket {buckets}")


if __name__ == "__main__":
    main()
