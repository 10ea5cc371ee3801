"""Bring-up smoke on a TPU: drives the system's main paths once through
the entry points a user calls, and checks what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips of one host

One chip runs two phases:

  * Faces halo exchange (the paper's 26-neighbour stencil exchange) at
    a stencil-code block of 64^3 float32 per rank, on the one-device
    grid (1,1,1), through ``STStream.synchronize`` with the ``st``,
    ``host`` and ``fused`` executors. Every output is checked against
    the numpy oracle ``halo.faces_reference``, every signal counter
    against the iteration count, and the fused run must have emitted
    the Pallas counter bump.
  * granite-3-2b serving at its published widths and depth (random
    weights from a seed): four requests through a ``ServingEngine``
    with the plain jitted decode, the ``st`` decode router and the
    ``fused`` one. The greedy tokens must be identical across the three,
    and the first-token logits of the bf16 prefill must agree with a
    float32 prefill of the same weights (relative L2 error at most
    ``LOGITS_REL_TOL``).

``--chips 4`` runs only the Faces exchange, on a (2,2,1) grid with one
rank per chip (the cross-chip path), with the same three executors and
checks.

The run fails (nonzero exit, no result line) when JAX finds no TPU or
any check fails. Times printed on ``#`` lines are bring-up timings, not
benchmark numbers. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
FACES_BLOCK = (64, 64, 64)      # float32 per rank: a stencil-code block
FACES_NITER = 4
FACES_EXECUTORS = ("st", "host", "fused")
ARCH = "granite-3-2b"
SLOTS = 4
PROMPT_LEN = 16
NEW_TOKENS = 16
MAX_LEN = 64
# bf16 vs float32 first-token logits, relative L2 error over the real
# vocabulary: about 13 bf16 roundings (2**-8 each) of budget for the 40
# residual layers' rounding noise
LOGITS_REL_TOL = 5e-2


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def timing(label, seconds):
    print(f"# bring-up timing (not a benchmark): {label}: {seconds:.3f} s",
          flush=True)


def run_faces(jax, grid):
    """Faces exchange on ``grid`` under every executor, against the numpy
    oracle."""
    from repro.core import STStream, halo
    from repro.launch.mesh import make_mesh

    n, niter = FACES_BLOCK, FACES_NITER
    nranks = int(np.prod(grid))
    mesh = make_mesh(grid, ("x", "y", "z"))
    ids = [d.id for d in mesh.devices.flat]
    for rank, dev in enumerate(mesh.devices.flat):
        print(f"# faces rank {rank} -> device {dev.id} ({dev.device_kind})")
    check(len(set(ids)) == nranks,
          f"faces grid {grid} does not span {nranks} distinct devices: {ids}")

    src0 = np.random.RandomState(SEED).rand(nranks, *n).astype(np.float32)
    src_exp, acc_exp = halo.faces_reference(src0, grid, n, niter)
    sched = dict(throttle="adaptive", resources=16, merged=True)
    for mode in FACES_EXECUTORS:
        stream = STStream(mesh, ("x", "y", "z"))
        win, _ = halo.build_faces_program(stream, n, niter)

        def fresh_state():
            st = stream.allocate()
            st[win.qual("src")] = jax.device_put(
                src0, st[win.qual("src")].sharding)
            return st

        for run in ("cold, incl. compile", "warm"):
            state = fresh_state()
            jax.block_until_ready(state)
            t0 = time.perf_counter()
            state = stream.synchronize(state, mode=mode, donate=False,
                                       **sched)
            jax.block_until_ready(state)
            timing(f"faces {mode} grid={grid} block={n} niter={niter} "
                   f"({run})", time.perf_counter() - t0)

        got = {k: np.asarray(v) for k, v in state.items()}
        where = f"faces {mode} grid={grid}"
        np.testing.assert_allclose(got[win.qual("src")], src_exp, rtol=1e-6,
                                   err_msg=f"{where}: src")
        np.testing.assert_allclose(got[win.qual("acc")], acc_exp, rtol=1e-5,
                                   err_msg=f"{where}: acc")
        np.testing.assert_allclose(
            got[win.qual("res")].reshape(nranks),
            np.abs(acc_exp).reshape(nranks, -1).max(axis=1), rtol=1e-5,
            err_msg=f"{where}: res")
        np.testing.assert_array_equal(got[win.qual("it")].reshape(nranks),
                                      np.full(nranks, niter, np.float32),
                                      err_msg=f"{where}: it")
        for cname in win.counter_names():
            np.testing.assert_array_equal(
                got[cname], np.full((nranks, len(halo.DIRECTIONS)), niter,
                                    np.int32),
                err_msg=f"{where}: signal counter {cname}")
        if mode == "fused":
            progs = stream.scheduled_programs(fused=True, **sched)
            backends = {p.meta.get("fusion_backend") for p in progs}
            check(backends == {"pallas"},
                  f"{where}: fused emission backend {backends}, expected "
                  "the Pallas counter bump")
        print(f"# faces {mode} grid={grid}: outputs and counters match the "
              "numpy oracle"
              + (" (Pallas counter bump)" if mode == "fused" else ""),
              flush=True)


def run_serving(jax):
    """granite-3-2b at published widths through the serving engine, under
    the baseline and both ST decode routes, plus the float32 check of
    the first-token logits."""
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.autotune import ScheduleConfig
    from repro.models import init_params, model_specs
    from repro.models.params import param_count
    from repro.serving import Request, ServingEngine
    from repro.sharding.rules import make_rules
    from repro.train.steps import make_prefill_step

    cfg = get_config(ARCH)
    rules = make_rules(cfg, None, None)
    specs = model_specs(cfg)
    t0 = time.perf_counter()
    params = init_params(specs, jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    timing(f"{ARCH} init of {param_count(specs)} float32 parameters",
           time.perf_counter() - t0)
    print(f"# {ARCH}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; parameters float32, "
          f"compute {cfg.compute_dtype}", flush=True)

    prompts = np.random.RandomState(SEED).randint(
        1, cfg.vocab_size, size=(SLOTS, PROMPT_LEN)).astype(np.int32)
    served = {}
    for st_mode in (None, "st", "fused"):
        kw = ({} if st_mode is None
              else dict(st_mode=st_mode, st_config=ScheduleConfig()))
        eng = ServingEngine(cfg, params, rules, batch_slots=SLOTS,
                            max_len=MAX_LEN, **kw)
        reqs = [Request(prompt=p, max_new_tokens=NEW_TOKENS) for p in prompts]
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        eng.run_until_drained()
        jax.block_until_ready(eng.cache)
        name = st_mode or "baseline"
        timing(f"{ARCH} serve {name}: {SLOTS} requests x {NEW_TOKENS} "
               "tokens (incl. compile)", time.perf_counter() - t0)
        check(all(len(r.out_tokens) == NEW_TOKENS and r.done_at is not None
                  for r in reqs),
              f"serve {name}: not every request got {NEW_TOKENS} tokens")
        served[name] = [list(r.out_tokens) for r in reqs]
        if st_mode == "fused":
            buckets = eng.stats()["st"]["buckets"]
            backends = {m["fusion_backend"] for m in buckets.values()}
            check(buckets and backends == {"pallas"},
                  f"serve fused: emission backends {backends}, expected "
                  "the Pallas counter bump")
        del eng
    check(served["st"] == served["baseline"]
          and served["fused"] == served["baseline"],
          f"greedy tokens differ across decode paths: {served}")
    print(f"# {ARCH} serve: greedy tokens identical across baseline, st "
          f"and fused ({SLOTS} x {NEW_TOKENS})", flush=True)

    batch = {"tokens": jnp.asarray(prompts),
             "positions": jnp.broadcast_to(
                 jnp.arange(PROMPT_LEN, dtype=jnp.int32),
                 (SLOTS, PROMPT_LEN))}
    t0 = time.perf_counter()
    lo, _ = jax.jit(make_prefill_step(cfg, rules, max_len=MAX_LEN,
                                      moe_impl="dense"))(params, batch)
    lo = np.asarray(lo[:, -1, :cfg.vocab_size], np.float32)
    timing(f"{ARCH} prefill {cfg.compute_dtype} (incl. compile)",
           time.perf_counter() - t0)
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32")
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref, _ = jax.jit(make_prefill_step(cfg32, rules, max_len=MAX_LEN,
                                           moe_impl="dense"))(params, batch)
        ref = np.asarray(ref[:, -1, :cfg.vocab_size], np.float32)
    timing(f"{ARCH} prefill float32 reference (incl. compile)",
           time.perf_counter() - t0)
    check(np.isfinite(lo).all() and np.isfinite(ref).all(),
          "first-token logits are not finite")
    rel = float(np.linalg.norm(lo - ref) / np.linalg.norm(ref))
    max_abs = float(np.abs(lo - ref).max())
    print(f"# {ARCH} first-token logits vs float32: rel L2 {rel!r}, max abs "
          f"{max_abs!r}, max |ref| {float(np.abs(ref).max())!r} "
          f"(tolerance rel L2 <= {LOGITS_REL_TOL})", flush=True)
    check(rel <= LOGITS_REL_TOL,
          f"bf16 first-token logits off the float32 reference: rel L2 {rel}")
    first = np.asarray([toks[0] for toks in served["baseline"]])
    gap = ref.max(axis=1) - ref[np.arange(SLOTS), first]
    check((gap <= 2 * max_abs).all(),
          f"served first tokens are not near-argmax of the float32 "
          f"logits: gaps {gap}")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"# device peak bytes in use: {stats['peak_bytes_in_use']} of "
              f"{stats.get('bytes_limit')}", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: Faces on one chip + granite-3-2b serving; "
                         "4: Faces on a (2,2,1) grid over four chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{len(devices)} {dev.platform} device(s)")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices; JAX found {len(devices)}")
    print(f"# {len(devices)} x {dev.device_kind}; compile cache {cache_dir}",
          flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        run_faces(jax, (2, 2, 1))
    else:
        run_faces(jax, (1, 1, 1))
        run_serving(jax)
    timing("whole smoke", time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
