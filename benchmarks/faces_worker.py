"""ST benchmark worker (runs in its own process so it owns its devices:
the fake host devices of CPU emulation under ``JAX_PLATFORMS=cpu``, or
the chips it runs on). Originally Faces-only, now pattern-agnostic:
``--pattern`` selects any registered ST program builder (faces / ring /
a2a / broadcast / serve) and the whole worker body — build, schedule,
execute, simulate, stats — is shared. Prints one CSV line:
name,us_per_call,derived — plus a "#stats" comment line with the
scheduled program's descriptor counts.

  us_per_call — measured wall-clock per inner-loop iteration on the
                devices the worker ran on (under CPU emulation the
                host-dispatch overheads are real; network latencies
                are not).
  derived     — critical-path time from the calibrated schedule simulator
                (core/throttle.py) walking the SAME scheduled descriptor
                DAG the executor emits, with paper-like cost constants —
                the number to compare against the paper's relative claims.

``BENCH_INJECT_FAIL=1`` makes the worker exit nonzero immediately — the
hook the CI bench-smoke job uses to prove the harness gates on worker
failures instead of swallowing them.
"""
import argparse
import json
import os
import sys


# per-pattern pattern-output and seedable-input buffer names, shared by
# every bit-identity verification path (--verify_overlap /
# --verify_node_aware / --verify_pack / --verify_chunk /
# --verify_multicast)
VERIFY_OUTPUTS = {"faces": ["acc", "res", "src", "it"],
                  "ring": ["out"], "a2a": ["out", "aux"],
                  "broadcast": ["ctile", "it"],
                  "serve": ["mirror", "outtok", "hmir", "step"]}
VERIFY_INPUTS = {"faces": ["src"], "ring": ["q", "k", "v"],
                 "a2a": ["x", "router", "wg", "wu", "wd"],
                 "broadcast": ["abase", "b"],
                 "serve": ["kv", "tok", "hid"]}


def seeded_state(stream, win, pattern, seed):
    """Allocate the stream's state with randomized pattern inputs —
    zero-initialized state would make any bit-identity comparison
    vacuous (all-zero outputs match under any schedule bug). Input
    buffers are never ping-ponged, so seeding the ping key covers
    double-buffered windows too."""
    import jax
    import numpy as np
    st = stream.allocate()
    rng = np.random.RandomState(seed)
    for b in VERIFY_INPUTS[pattern]:
        k = win.qual(b)
        dtype = np.asarray(st[k]).dtype
        if np.issubdtype(dtype, np.integer):
            # token-id style buffers: rand*0.3 truncates to all-zero
            val = rng.randint(1, 97, size=st[k].shape).astype(dtype)
        else:
            val = rng.rand(*st[k].shape).astype(dtype) * 0.3
        st[k] = jax.device_put(val, st[k].sharding)
    return st


def verify_outputs(pattern, what, got_state, got_win, ref_state, ref_win):
    """Exit nonzero unless every pattern output is bit-identical between
    the schedule under test and its reference, and non-vacuous."""
    import numpy as np
    for b in VERIFY_OUTPUTS[pattern]:
        got = np.asarray(got_state[got_win.qual(b)])
        ref = np.asarray(ref_state[ref_win.qual(b)])
        if not (got == ref).all():
            sys.exit(f"{what} schedule changed output {b!r} "
                     f"(max abs diff {abs(got - ref).max()})")
        if not got.any():
            sys.exit(f"{what} verification is vacuous: output {b!r} is "
                     "all-zero despite seeded inputs")


def build_kwargs(args, ndev):
    """Per-pattern size mapping from the shared --block knob."""
    if args.pattern == "faces":
        import jax.numpy as jnp
        overlap = ((lambda a: a @ a), "overlapbuf") if args.overlap else None
        extra = {"overlapbuf": ((64, 64), jnp.float32)} if args.overlap \
            else None
        return dict(n=(args.block,) * 3, overlap_kernel=overlap,
                    extra_buffers=extra)
    if args.pattern == "ring":
        return dict(batch=1, seq_per_rank=args.block, heads=2, head_dim=8)
    if args.pattern == "a2a":
        return dict(batch=1, seq=args.block, d_model=16, expert_ff=16,
                    experts=2 * ndev, top_k=2)
    if args.pattern == "broadcast":
        return dict(tile=args.block, multicast=bool(args.multicast))
    if args.pattern == "serve":
        return dict(slots=args.block, kv_dim=16, d_model=16)
    raise ValueError(f"no size mapping for pattern {args.pattern!r}")


def main():
    inject = os.environ.get("BENCH_INJECT_FAIL", "").strip().lower()
    if inject not in ("", "0", "false", "no"):
        sys.exit("injected worker failure (BENCH_INJECT_FAIL is set)")

    ap = argparse.ArgumentParser()
    ap.add_argument("--pattern", default="faces",
                    choices=["faces", "ring", "a2a", "broadcast", "serve"])
    ap.add_argument("--grid", default="2,2,2",
                    help="process grid, e.g. 2,2,2 (faces) or 4 (ring/a2a)")
    ap.add_argument("--block", type=int, default=8,
                    help="faces: block edge; ring: seq per rank; a2a: seq")
    ap.add_argument("--niter", type=int, default=10)
    ap.add_argument("--mode", default="st", choices=["st", "host"])
    ap.add_argument("--exec", dest="exec_", default="",
                    choices=["", "st", "host", "fused"],
                    help="executor override: 'fused' runs the "
                         "device-resident progress engine (segment "
                         "planner + fused per-segment emission); empty "
                         "defers to --mode")
    ap.add_argument("--throttle", default="adaptive")
    ap.add_argument("--merged", type=int, default=1)
    ap.add_argument("--ordered", type=int, default=0,
                    help="P2P message-matching serialization")
    ap.add_argument("--overlap", type=int, default=0,
                    help="enqueue an independent compute kernel per iter "
                         "(faces only)")
    ap.add_argument("--resources", type=int, default=16)
    ap.add_argument("--nstreams", type=int, default=1,
                    help="stream-assignment pass: 1 compute stream + "
                         "nstreams-1 communication streams")
    ap.add_argument("--double_buffer", type=int, default=0,
                    help="ping/pong window buffers (alternating epochs)")
    ap.add_argument("--verify_overlap", type=int, default=0,
                    help="also run the single-stream schedule and require "
                         "bit-identical pattern outputs")
    ap.add_argument("--ranks_per_node", type=int, default=0,
                    help="hardware node mapping (0 = single node): puts "
                         "lower with intra/inter link tags and the "
                         "simulator prices + serializes the NIC link")
    ap.add_argument("--node_aware", type=int, default=0,
                    help="node-aware schedule pass: off-node puts first")
    ap.add_argument("--coalesce", type=int, default=0,
                    help="aggregate same-target-node off-node puts "
                         "(with --node_aware)")
    ap.add_argument("--verify_node_aware", type=int, default=0,
                    help="also run the naive (non-node-aware) schedule "
                         "and require bit-identical pattern outputs")
    ap.add_argument("--pack", type=int, default=0,
                    help="materialize off-node aggregation groups as "
                         "packed multi-buffer put descriptors "
                         "(schedule.pack_puts; needs --ranks_per_node)")
    ap.add_argument("--verify_pack", type=int, default=0,
                    help="also run the unpacked schedule and require "
                         "bit-identical pattern outputs")
    ap.add_argument("--chunk_bytes", type=int, default=0,
                    help="split larger off-node puts into pipelined "
                         "chunk chains (schedule.chunk_puts; 0 = off)")
    ap.add_argument("--verify_chunk", type=int, default=0,
                    help="also run the monolithic (unchunked) schedule "
                         "and require bit-identical pattern outputs")
    ap.add_argument("--multicast", type=int, default=0,
                    help="broadcast pattern: one multicast put "
                         "descriptor instead of the unicast fanout")
    ap.add_argument("--verify_multicast", type=int, default=0,
                    help="also run the unicast-fanout program and "
                         "require bit-identical pattern outputs")
    ap.add_argument("--verify_fused", type=int, default=0,
                    help="also run the compiled ST executor over the "
                         "unfused schedule and require bit-identical "
                         "pattern outputs vs the fused progress engine")
    ap.add_argument("--config", default="",
                    help="tuned schedule config: 'auto' consults the "
                         "tuned cache (autotuning on a miss) under the "
                         "(pattern, grid, ranks_per_node, b<block>) key; "
                         "or a ScheduleConfig JSON object. Overrides the "
                         "individual schedule flags AND the build-time "
                         "double_buffer/multicast knobs")
    ap.add_argument("--tuned", default="",
                    help="tuned-cache path for --config auto (default: "
                         "$REPRO_TUNED or results/tuned.json)")
    ap.add_argument("--verify_tuned", type=int, default=0,
                    help="also run the flag-default schedule and require "
                         "bit-identical pattern outputs vs the tuned one")
    ap.add_argument("--verify_static", type=int, default=0,
                    help="run the static schedule verifier "
                         "(repro.core.verify) over the scheduled "
                         "program(s) before executing; exits nonzero on "
                         "any error finding and records the findings "
                         "count in #stats/JSON")
    ap.add_argument("--name", default=None)
    ap.add_argument("--json-dir", default=None,
                    help="also write a {name}.json record (descriptor "
                         "stats + timings) into this directory")
    args = ap.parse_args()
    mode = args.exec_ or args.mode

    grid = tuple(int(x) for x in args.grid.split(","))
    ndev = 1
    for g in grid:
        ndev *= g
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        # CPU emulation: one fake host device per rank, added to (never
        # replacing) whatever XLA flags the caller set
        os.environ["XLA_FLAGS"] = " ".join(filter(None, (
            os.environ.get("XLA_FLAGS", ""),
            f"--xla_force_host_platform_device_count={ndev}")))

    import time

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devices = jax.devices()
    if len(devices) < ndev:
        raise SystemExit(
            f"grid {args.grid} needs {ndev} devices, but only "
            f"{len(devices)} {devices[0].platform} device(s) are "
            "available (set JAX_PLATFORMS=cpu to emulate the grid on "
            "fake host devices)")
    from repro.core import STStream, get_pattern
    from repro.core.throttle import (CostModel, host_dispatch_count,
                                     simulate_pipeline)
    from repro.launch.mesh import make_mesh

    pat = get_pattern(args.pattern)
    if len(grid) != len(pat.grid_axes):
        raise SystemExit(f"pattern {args.pattern!r} wants a "
                         f"{len(pat.grid_axes)}-d grid, got {args.grid!r}")
    mesh = make_mesh(grid, pat.grid_axes)

    double_buffer = bool(args.double_buffer)
    ranks_per_node = args.ranks_per_node or None
    build_kw = build_kwargs(args, ndev)
    cfg = None
    if args.config:
        # resolve BEFORE building: double_buffer and multicast are
        # build-time knobs — a tuned config can change the enqueued
        # program itself, not just the schedule passes
        from repro.core.autotune import resolve_config
        spec = args.config if args.config == "auto" \
            else json.loads(args.config)
        cfg = resolve_config(spec, args.pattern, grid=grid,
                             ranks_per_node=ranks_per_node,
                             size=f"b{args.block}",
                             path=args.tuned or None, **build_kw)
        double_buffer = cfg.double_buffer
        build_kw = dict(build_kw,
                        **{k: v for k, v in cfg.build_overrides().items()
                           if k != "double_buffer"})
    stream = STStream(mesh, pat.grid_axes)
    win, _ = pat.build(stream, args.niter,
                       merged=(cfg.merged if cfg else bool(args.merged)),
                       double_buffer=double_buffer,
                       ranks_per_node=ranks_per_node, **build_kw)
    state = stream.allocate()

    if cfg is not None:
        sched_opts = cfg.sched_kwargs()
    else:
        sched_opts = dict(throttle=args.throttle, resources=args.resources,
                          merged=bool(args.merged),
                          ordered=bool(args.ordered),
                          nstreams=args.nstreams,
                          node_aware=bool(args.node_aware),
                          coalesce=bool(args.coalesce),
                          pack=bool(args.pack),
                          chunk_bytes=args.chunk_bytes)
    if mode == "fused":
        # the progress engine needs the segment planner's metadata on
        # the scheduled program regardless of where the config came from
        sched_opts["fused"] = True
    if mode == "host":
        # the host baseline has no runtime throttling engine — its
        # resource reclaim is the blocking per-op dispatch itself.
        # Schedule (and therefore simulate) exactly what run_host
        # executes; ordering IS preserved by the serialized dispatch,
        # so ordered edges stay. Merged signal kernels (§5.4) are an
        # ST-side contribution: the standard active-RMA baseline posts
        # per-neighbor signals and wire completions. It also has no
        # device streams: every dispatch serializes on the host.
        sched_opts.update(throttle="none", merged=False, nstreams=1)
    throttle = sched_opts["throttle"]
    merged = sched_opts["merged"]
    nstreams = sched_opts["nstreams"]

    def run_once(st):
        return stream.synchronize(st, mode=mode, donate=False,
                                  **sched_opts)

    verify_findings = None
    if args.verify_static:
        # prove the schedule race/deadlock/lint/resource-clean BEFORE
        # the first launch — the same pass suite CI runs over the whole
        # quick space, here over exactly the schedule this worker runs
        from repro.core.verify import verify_programs
        vreport = verify_programs(stream.scheduled_programs(**sched_opts))
        verify_findings = len(vreport.findings)
        if not vreport.ok:
            sys.exit("static schedule verification failed:\n"
                     + vreport.summary())
        print(f"# static-verified {args.pattern} "
              f"findings={verify_findings} "
              f"events={vreport.checked.get('events', 0)} "
              f"conflict_pairs={vreport.checked.get('conflict_pairs', 0)}")

    state = run_once(state)              # warm-up (compiles)
    reps = int(os.environ.get("FACES_REPS", "1"))
    t0 = time.perf_counter()
    for _ in range(reps):
        state = run_once(state)
    dt = (time.perf_counter() - t0) / reps
    us_per_iter = dt / args.niter * 1e6

    # derived: the calibrated simulator walks the IDENTICAL scheduled
    # descriptor DAG the executor just emitted
    progs = stream.scheduled_programs(**sched_opts)
    derived = simulate_pipeline(
        progs, CostModel(),
        host_orchestrated=(mode == "host")) / args.niter

    if args.verify_overlap:
        # the overlapped schedule must not change a single output bit vs
        # the single-stream schedule on a single-buffered window (the
        # overlapped run reuses this worker's compiled executable)
        got_state = stream.synchronize(
            seeded_state(stream, win, args.pattern, 0), mode=mode,
            donate=False, **sched_opts)
        ref_stream = STStream(mesh, pat.grid_axes)
        ref_win, _ = pat.build(ref_stream, args.niter,
                               merged=bool(args.merged),
                               double_buffer=False,
                               **build_kwargs(args, ndev))
        ref_state = ref_stream.synchronize(
            seeded_state(ref_stream, ref_win, args.pattern, 0),
            mode=mode, donate=False, **dict(sched_opts, nstreams=1))
        verify_outputs(args.pattern, "overlap", got_state, win,
                       ref_state, ref_win)
        print(f"# overlap-verified {args.pattern} nstreams={nstreams} "
              f"double_buffer={int(double_buffer)} "
              f"outputs={VERIFY_OUTPUTS[args.pattern]}")

    if args.verify_node_aware:
        # the node-aware ordering must not change a single output bit vs
        # the naive schedule (same DAG, different emission order)
        if not args.node_aware:
            sys.exit("--verify_node_aware without --node_aware compares "
                     "the naive schedule against itself")
        got_state = stream.synchronize(
            seeded_state(stream, win, args.pattern, 0), mode=mode,
            donate=False, **sched_opts)
        naive_state = stream.synchronize(
            seeded_state(stream, win, args.pattern, 0), mode=mode,
            donate=False,
            **dict(sched_opts, node_aware=False, coalesce=False))
        verify_outputs(args.pattern, "node-aware", got_state, win,
                       naive_state, win)
        print(f"# node-aware-verified {args.pattern} "
              f"ranks_per_node={args.ranks_per_node} "
              f"coalesce={args.coalesce} "
              f"outputs={VERIFY_OUTPUTS[args.pattern]}")

    if args.verify_pack:
        # the packed schedule (multi-buffer descriptors riding one
        # collective each) must not change a single output bit vs the
        # unpacked schedule
        if not args.pack:
            sys.exit("--verify_pack without --pack compares the unpacked "
                     "schedule against itself")
        got_state = stream.synchronize(
            seeded_state(stream, win, args.pattern, 1), mode=mode,
            donate=False, **sched_opts)
        ref_state = stream.synchronize(
            seeded_state(stream, win, args.pattern, 1), mode=mode,
            donate=False, **dict(sched_opts, pack=False))
        verify_outputs(args.pattern, "packed", got_state, win,
                       ref_state, win)
        if not any(len(p.srcs) > 1 for prog in progs for p in prog.puts()):
            sys.exit("pack verification is vacuous: the packed schedule "
                     "contains no packed multi-buffer descriptor")
        print(f"# pack-verified {args.pattern} "
              f"ranks_per_node={args.ranks_per_node} "
              f"outputs={VERIFY_OUTPUTS[args.pattern]}")

    if args.verify_chunk:
        # the chunked schedule (pipelined chunk chains) must not change
        # a single output bit vs the monolithic schedule — the union of
        # a chain's chunks covers every destination element exactly once
        if not args.chunk_bytes:
            sys.exit("--verify_chunk without --chunk_bytes compares the "
                     "monolithic schedule against itself")
        got_state = stream.synchronize(
            seeded_state(stream, win, args.pattern, 2), mode=mode,
            donate=False, **sched_opts)
        ref_state = stream.synchronize(
            seeded_state(stream, win, args.pattern, 2), mode=mode,
            donate=False, **dict(sched_opts, chunk_bytes=0))
        verify_outputs(args.pattern, "chunked", got_state, win,
                       ref_state, win)
        if not any(prog.chunked_puts() for prog in progs):
            sys.exit("chunk verification is vacuous: the chunked "
                     "schedule contains no chunk chain")
        print(f"# chunk-verified {args.pattern} "
              f"chunk_bytes={args.chunk_bytes} "
              f"outputs={VERIFY_OUTPUTS[args.pattern]}")

    if args.verify_multicast:
        # the multicast program (one descriptor, one completion tree)
        # must not change a single output bit vs the unicast fanout —
        # both deliver identical bytes into the same landing buffers
        if args.pattern != "broadcast" or not args.multicast:
            sys.exit("--verify_multicast needs --pattern broadcast "
                     "--multicast 1")
        got_state = stream.synchronize(
            seeded_state(stream, win, args.pattern, 3), mode=mode,
            donate=False, **sched_opts)
        ref_stream = STStream(mesh, pat.grid_axes)
        ref_win, _ = pat.build(
            ref_stream, args.niter, merged=bool(args.merged),
            double_buffer=double_buffer, ranks_per_node=ranks_per_node,
            **dict(build_kwargs(args, ndev), multicast=False))
        ref_state = ref_stream.synchronize(
            seeded_state(ref_stream, ref_win, args.pattern, 3),
            mode=mode, donate=False, **sched_opts)
        verify_outputs(args.pattern, "multicast", got_state, win,
                       ref_state, ref_win)
        if not any(prog.multicast_puts() for prog in progs):
            sys.exit("multicast verification is vacuous: the program "
                     "contains no multicast descriptor")
        print(f"# multicast-verified {args.pattern} "
              f"outputs={VERIFY_OUTPUTS[args.pattern]}")

    if args.verify_tuned:
        # the tuned schedule (whatever point the autotuner picked —
        # possibly a different BUILD: double-buffered windows, multicast
        # vs unicast fanout) must not change a single output bit vs the
        # flag-default schedule: tuning is a pure performance choice
        if cfg is None:
            sys.exit("--verify_tuned needs --config (auto or an explicit "
                     "ScheduleConfig JSON)")
        got_state = stream.synchronize(
            seeded_state(stream, win, args.pattern, 4), mode=mode,
            donate=False, **sched_opts)
        ref_stream = STStream(mesh, pat.grid_axes)
        ref_win, _ = pat.build(ref_stream, args.niter,
                               merged=bool(args.merged),
                               double_buffer=bool(args.double_buffer),
                               ranks_per_node=ranks_per_node,
                               **build_kwargs(args, ndev))
        ref_opts = dict(throttle=args.throttle, resources=args.resources,
                        merged=bool(args.merged),
                        ordered=bool(args.ordered),
                        nstreams=args.nstreams,
                        node_aware=bool(args.node_aware),
                        coalesce=bool(args.coalesce),
                        pack=bool(args.pack),
                        chunk_bytes=args.chunk_bytes)
        if mode == "host":
            ref_opts.update(throttle="none", merged=False, nstreams=1)
        ref_state = ref_stream.synchronize(
            seeded_state(ref_stream, ref_win, args.pattern, 4),
            mode=mode, donate=False, **ref_opts)
        verify_outputs(args.pattern, "tuned", got_state, win,
                       ref_state, ref_win)
        print(f"# tuned-verified {args.pattern} config={cfg.label()} "
              f"mode={mode} outputs={VERIFY_OUTPUTS[args.pattern]}")

    if args.verify_fused:
        # the fused progress engine (segment planner + per-segment
        # fused emission) must not change a single output bit vs the
        # compiled ST executor walking the unfused schedule
        got_state = stream.synchronize(
            seeded_state(stream, win, args.pattern, 5), mode="fused",
            donate=False, **dict(sched_opts, fused=True))
        ref_state = stream.synchronize(
            seeded_state(stream, win, args.pattern, 5), mode="st",
            donate=False, **dict(sched_opts, fused=False))
        verify_outputs(args.pattern, "fused", got_state, win,
                       ref_state, win)
        fprogs = stream.scheduled_programs(**dict(sched_opts, fused=True))
        nseg = sum(p.meta.get("segments", 0) for p in fprogs)
        if not nseg:
            sys.exit("fused verification is vacuous: the segment "
                     "planner produced no segments")
        print(f"# fused-verified {args.pattern} nstreams={nstreams} "
              f"segments={nseg} outputs={VERIFY_OUTPUTS[args.pattern]}")

    stats = progs[0].stats()
    stats["programs"] = len(progs)
    # planner segment count across the pipeline (0 unless fused), and
    # the host-dispatch totals the progress engine trades against the
    # per-op counts: fused schedules dispatch once per SEGMENT
    stats["segments"] = sum(p.meta.get("segments", 0) for p in progs)
    stats["ops"] = sum(len(p.nodes) for p in progs)
    stats["host_dispatches"] = sum(host_dispatch_count(p) for p in progs)
    if verify_findings is not None:
        stats["verify_findings"] = verify_findings
    name = args.name or (f"{args.pattern}_{mode}_{throttle}"
                         f"_m{int(merged)}_o{args.ordered}_{ndev}r")
    print(f"{name},{us_per_iter:.1f},{derived:.2f}")
    print(f"#stats {name} pattern={stats['pattern']} "
          f"puts_per_epoch={stats['puts_per_epoch']:.0f} "
          f"packed_puts={stats['packed_puts']} "
          f"chunked_puts={stats['chunked_puts']} "
          f"multicast_puts={stats['multicast_puts']} "
          f"inter_puts={stats['inter_puts']} "
          f"resource_high_water={stats['resource_high_water']} "
          f"critical_path_depth={stats['critical_path_depth']} "
          f"descriptors={stats['descriptors']} "
          f"dep_edges={stats['dep_edges']} "
          f"exec={mode} segments={stats['segments']} "
          f"host_dispatches={stats['host_dispatches']}"
          + (f" verify_findings={verify_findings}"
             if verify_findings is not None else ""))
    if args.json_dir:
        os.makedirs(args.json_dir, exist_ok=True)
        rec = dict(name=name, pattern=args.pattern, mode=mode,
                   grid=list(grid), block=args.block, niter=args.niter,
                   us_per_iter=us_per_iter, derived_us_per_iter=derived,
                   double_buffer=double_buffer,
                   ranks_per_node=ranks_per_node, **sched_opts, stats=stats)
        if cfg is not None:
            rec["config"] = cfg.to_dict()
        # an unbounded policy holds no descriptor slots: report the real
        # (None) R from program meta, not the CLI default
        rec["resources"] = progs[0].meta.get("resources")
        with open(os.path.join(args.json_dir, f"{name}.json"), "w") as f:
            json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
