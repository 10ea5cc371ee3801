"""Benchmark harness — one section per paper figure plus the non-halo
ST transports. Prints ``name,us_per_call,derived`` CSV (derived =
critical path per iteration in us from the calibrated simulator walking
the scheduled triggered-op descriptor DAG for ST benches; roofline
fraction for dry-run rows; tokens/s for throughput rows), plus
``#stats`` lines with per-program descriptor counts (puts/epoch,
resource high-water, critical-path depth).

Sections:
  fig12  Faces overall: ST vs host-orchestrated active RMA (8 & 64 ranks)
  fig13  throttling algorithms (adaptive/static/application), 64 ranks
  fig14  merged vs independent kernels (8 & 64 ranks)
  fig15  overlapping compute kernel
  fig16_17 P2P-ordered vs RMA vs ST, intra (8r) and multi (64r)
  ring   ST-lowered ring-attention rotation vs host baseline (4 ranks)
  a2a    expert-parallel MoE aggregated-put combine vs host baseline
  overlap  multi-stream schedule (assign_streams + double-buffered
         windows) vs single stream, all patterns, outputs verified
         bit-identical in-worker
  sweep  message-size x topology derived latency curves (single-node vs
         2-node ranks_per_node mappings, naive vs node-aware ordering)
         plus one executor worker per pattern verifying the node-aware
         schedule bit-identical in-process
  pack   materialized put aggregation: packed multi-buffer descriptors
         (schedule.pack_puts) vs the unpacked schedule over the same
         sweep grid, plus one executor worker per pattern verifying the
         packed schedule bit-identical in-process
  chunk  chunked-pipelined transport (schedule.chunk_puts): chunked vs
         monolithic derived latency at large-message off-node points,
         plus executor workers verifying the chunked schedule
         bit-identical in-process
  broadcast  SUMMA-style row fanout: ONE multicast put descriptor vs
         the cols-1 unicast fanout, derived + executor verification
  fused  device-resident progress engine (segment planner + fused
         per-segment emission, core/engine.py): fused vs compiled
         derived latency and host-dispatch counts per pattern, plus
         executor workers running --exec fused with in-process
         bit-identity verification against run_compiled
  autotune  simulator-guided schedule search (core/autotune.py): tuned
         vs default derived latency per pattern, winner cached in
         results/tuned.json, plus executor workers running
         ``--config auto`` through BOTH backends with in-process
         bit-identity verification against the default schedule
  serve  ST-driven serving fast path (repro.serving): derived decode-
         epoch cost per active-slot bucket — scheduled ST program vs
         the host-orchestrated baseline over the same epoch — executor
         workers over the serve pattern (host / adaptive ST / fused
         progress engine with bit-identity), and a 2-replica
         Poisson traffic smoke (in a child process) reporting p50/p99
         latency, TTFT, and tokens/sec (wall metrics in us_per_call,
         derived=0.00 so container timing never gates the trajectory)
  roofline  per (arch x shape x mesh) terms from results/dryrun
  throughput  tiny-config train tokens/s

The harness process itself never initialises a JAX backend: executor
workers, the serving smoke and the throughput step each run in a child
process, so each can own the devices it runs on.

Worker failures are COUNTED and the harness exits nonzero (CI gates on
this). ``--json PATH`` writes every parsed row + failures + invariant
checks as one JSON record AND a repo-root ``<BENCH_ID>.json`` perf-
trajectory record (row-name -> derived latency, rows, invariants; the
id comes from ``--bench-id``/``$BENCH_ID``, default BENCH_10) that CI
uploads — and diffs against the previous PR's record via
``scripts/check_trajectory.py`` — so regressions in derived numbers
show up as a one-line diff instead of flying blind;
``--check-invariants`` asserts the Fig. 13
structural ordering adaptive <= static <= application, the overlap
rule (nstreams=2 + double_buffer derived cost <= single stream), the
topology rules over the sweep grid (derived cost monotone in
payload bytes, inter-node link strictly costlier than intra-node,
multi-node mapping never cheaper than single-node, node-aware ordering
never costlier than naive), the aggregation rules (packed derived
latency <= unpacked per pattern/link, packing the identity on single-
node topologies, packed descriptor counts exactly as the group
structure predicts), the chunk-pipeline rule (chunked derived latency
STRICTLY below monolithic at the large-message off-node points), the
multicast rule (one multicast descriptor strictly below the
unicast fanout), the autotune rule (the searched config's derived
latency <= the default config's), the progress-engine rules (fused
derived latency <= compiled, per-segment host-dispatch counts strictly
below per-op counts for every multi-epoch pattern) for every ST
pattern, and the serving SLO rules (ST decode-epoch derived cost <=
the host-orchestrated baseline per slot bucket, ST-routed tokens
bit-identical to the baseline engine, traffic queue drained with
bounded finite p99, serve-program meta present on every ST replica).
``BENCH_SMOKE=1``
keeps only the small-grid configs (CI), ``BENCH_NITER`` overrides
iterations per worker.
"""
import functools
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(ROOT, "benchmarks", "faces_worker.py")


def env_flag(name):
    """"", "0", "false", "no" (any case) are OFF; anything else is ON."""
    return os.environ.get(name, "").strip().lower() \
        not in ("", "0", "false", "no")


SMOKE = env_flag("BENCH_SMOKE")

RESULTS = []       # parsed CSV rows across all sections
FAILURES = []      # worker invocations that exited nonzero or hung


def _spawn(section, cmd, args):
    """Run one JAX-using child process and return its stdout, or None
    after recording the failure. Everything that touches JAX runs in a
    child: the harness parent never initialises a JAX backend, so each
    child can own the devices (a chip belongs to one process at a
    time)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    try:
        r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=2400)
        returncode, stderr = r.returncode, r.stderr
    except subprocess.TimeoutExpired as e:
        r, returncode = None, -1
        stderr = f"timeout after {e.timeout}s"
    if returncode != 0:
        print(f"# WORKER FAILED {args}: {stderr[-400:]}", flush=True)
        FAILURES.append({"section": section,
                         "args": {k: str(v) for k, v in args.items()},
                         "returncode": returncode,
                         "stderr": stderr[-400:]})
        return None
    return r.stdout


def _child(section, body):
    """Run ``body`` (a key of ``CHILD_BODIES``) as ``run.py --child``;
    returns its stdout, or None after recording the failure."""
    return _spawn(section, [sys.executable, os.path.abspath(__file__),
                            "--child", body], {"child": body})


def _worker(section="", **kw):
    kw.setdefault("niter", os.environ.get("BENCH_NITER", "10"))
    if env_flag("BENCH_VERIFY_STATIC"):
        # CI bench-smoke sets this: every worker statically verifies its
        # scheduled program (races, liveness, descriptor lint, slot
        # bounds) before the first launch and dies on any error finding
        kw.setdefault("verify_static", 1)
    cmd = [sys.executable, WORKER]
    for k, v in kw.items():
        cmd += [f"--{k}", str(v)]
    out = _spawn(section, cmd, kw)
    if out is None:
        return False
    for line in out.strip().splitlines():
        if line.startswith("#"):
            print(line, flush=True)
        elif "," in line:
            print(line, flush=True)
            parts = line.split(",")
            if len(parts) >= 3:
                try:
                    RESULTS.append({"section": section, "name": parts[0],
                                    "us_per_call": float(parts[1]),
                                    "derived": float(parts[2]),
                                    "nstreams": int(kw.get("nstreams", 1)),
                                    "double_buffer": bool(int(
                                        kw.get("double_buffer", 0))),
                                    "ranks_per_node": int(
                                        kw.get("ranks_per_node", 0)),
                                    "node_aware": bool(int(
                                        kw.get("node_aware", 0))),
                                    "pack": bool(int(kw.get("pack", 0))),
                                    "chunk_bytes": int(
                                        kw.get("chunk_bytes", 0)),
                                    "multicast": bool(int(
                                        kw.get("multicast", 0)))})
                except ValueError:
                    pass
    return True


def _grids(pairs):
    """Under BENCH_SMOKE keep only the smallest grid config."""
    return pairs[:1] if SMOKE else pairs


def fig12():
    print("# fig12: Faces overall — ST vs host-orchestrated active RMA")
    for grid, tag in _grids([("2,2,2", "8r"), ("4,4,4", "64r")]):
        _worker("fig12", grid=grid, mode="host", throttle="none", merged=1,
                name=f"fig12_activeRMA_{tag}")
        _worker("fig12", grid=grid, mode="st", throttle="adaptive", merged=1,
                name=f"fig12_stRMA_{tag}")


def fig13():
    print("# fig13: throttling algorithms (64 ranks, resources=16)")
    for thr in ("adaptive", "static"):
        _worker("fig13", grid="4,4,4", mode="st", throttle=thr, resources=16,
                name=f"fig13_{thr}_64r")
    # application-level throttling == host-orchestrated resource reclaim
    _worker("fig13", grid="4,4,4", mode="host", throttle="none",
            resources=16, name="fig13_application_64r")


def fig14():
    print("# fig14: merged vs independent kernels")
    for grid, tag in _grids([("2,2,2", "8r"), ("4,4,4", "64r")]):
        for m in (1, 0):
            _worker("fig14", grid=grid, mode="st", throttle="adaptive",
                    merged=m, name=f"fig14_{'merged' if m else 'indep'}_{tag}")


def fig15():
    print("# fig15: overlapping compute kernel (64 ranks)")
    for mode in ("st", "host"):
        _worker("fig15", grid="4,4,4", mode=mode, throttle="adaptive",
                merged=1, overlap=1, name=f"fig15_{mode}_overlap_64r")


def fig16_17():
    print("# fig16/17: traditional P2P (ordered) vs active RMA vs ST")
    for grid, fig in _grids([("2,2,2", "fig16"), ("4,4,4", "fig17")]):
        tag = "8r" if fig == "fig16" else "64r"
        _worker(fig, grid=grid, mode="host", throttle="none", merged=1,
                ordered=1, name=f"{fig}_p2p_{tag}")
        _worker(fig, grid=grid, mode="host", throttle="none", merged=1,
                name=f"{fig}_activeRMA_{tag}")
        _worker(fig, grid=grid, mode="st", throttle="adaptive", merged=1,
                name=f"{fig}_stRMA_{tag}")


def ring():
    print("# ring: ST-lowered ring-attention KV rotation (4 ranks)")
    _worker("ring", pattern="ring", grid="4", block=16, mode="host",
            throttle="none", merged=1, name="ring_activeRMA_4r")
    for thr in ("adaptive", "static"):
        _worker("ring", pattern="ring", grid="4", block=16, mode="st",
                throttle=thr, resources=8, name=f"ring_st_{thr}_4r")


def a2a():
    print("# a2a: expert-parallel MoE aggregated-put combine (4 ranks)")
    _worker("a2a", pattern="a2a", grid="4", block=16, mode="host",
            throttle="none", merged=1, name="a2a_activeRMA_4r")
    for thr in ("adaptive", "static"):
        _worker("a2a", pattern="a2a", grid="4", block=16, mode="st",
                throttle=thr, resources=8, name=f"a2a_st_{thr}_4r")


def overlap():
    """Multi-stream overlap: stream-assignment pass + double-buffered
    windows vs the single-stream schedule, for every registered pattern.
    Each overlapped worker also re-runs the single-stream schedule
    in-process and requires bit-identical pattern outputs."""
    print("# overlap: nstreams/double_buffer sweep (st mode, adaptive)")
    specs = [("faces", dict(grid="2,2,2", block=8)),
             ("ring", dict(pattern="ring", grid="4", block=16)),
             ("a2a", dict(pattern="a2a", grid="4", block=16))]
    sweeps = [(2, 1)] if SMOKE else [(2, 0), (2, 1), (3, 1)]
    for pat, kw in specs:
        _worker("overlap", mode="st", throttle="adaptive", merged=1,
                resources=8, nstreams=1,
                name=f"overlap_{pat}_1s", **kw)
        for ns, db in sweeps:
            _worker("overlap", mode="st", throttle="adaptive", merged=1,
                    resources=8, nstreams=ns, double_buffer=db,
                    verify_overlap=1,
                    name=f"overlap_{pat}_{ns}s_db{db}", **kw)


_SWEEP_GRIDS = {"faces": (2, 2, 2), "ring": (4,), "a2a": (4,)}
_SWEEP_RPN = {"faces": 4, "ring": 2, "a2a": 2}      # 2 hardware nodes
_SWEEP_CACHE = None


def _sweep_size_kw(pat, block):
    return {"faces": dict(n=(block,) * 3),
            "ring": dict(seq_per_rank=block),
            "a2a": dict(seq=block)}[pat]


def _mode_tag(node_aware, coalesce, pack):
    tag = "na" if node_aware else "naive"
    if node_aware and not coalesce:
        tag += "_nc"
    if pack:
        tag += "_pk"
    return tag


def _sweep_points():
    """Device-free message-size x topology grid shared by the ``sweep``/
    ``pack`` sections and ``check_invariants``: derived cost +
    bytes/epoch + descriptor counts per (pattern, block, ranks_per_node,
    node_aware, coalesce, pack) point, adaptive/merged (the off-node
    regime the node-aware and aggregation passes target). The pack
    points pair with a coalesce=False baseline on purpose: materialized
    packing replaces the marked-aggregation alpha waiver (the simulator-
    only approximation PR 4 shipped), so the fair unpacked comparison is
    the unmarked schedule."""
    global _SWEEP_CACHE
    if _SWEEP_CACHE is not None:
        return _SWEEP_CACHE
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.patterns import pattern_programs
    from repro.core.throttle import CostModel, simulate_pipeline

    blocks = {"faces": [2, 4] if SMOKE else [2, 4, 6, 8],
              "ring": [8, 16] if SMOKE else [8, 16, 32, 64],
              "a2a": [8, 16] if SMOKE else [8, 16, 32, 64]}
    niter = 2
    points = []
    for pat, grid in _SWEEP_GRIDS.items():
        for rpn in (None, _SWEEP_RPN[pat]):
            # node-aware ordering only exists on a multi-node topology;
            # packing only fires on off-node groups, so the single-node
            # pack point is the identity check (intra link: equal cost)
            modes = [(False, False, False), (False, False, True)] \
                if rpn is None \
                else [(False, False, False), (True, True, False),
                      (True, False, False), (True, False, True)]
            for node_aware, coalesce, pack in modes:
                for b in blocks[pat]:
                    progs = pattern_programs(
                        pat, niter, grid=grid, throttle="adaptive",
                        resources=8, ranks_per_node=rpn,
                        node_aware=node_aware, coalesce=coalesce,
                        pack=pack, **_sweep_size_kw(pat, b))
                    derived = simulate_pipeline(progs, CostModel()) / niter
                    s = progs[0].stats()
                    points.append(dict(
                        pattern=pat, block=b,
                        bytes_per_epoch=s["bytes_per_epoch"],
                        inter_puts=s["inter_puts"],
                        puts_per_epoch=s["puts_per_epoch"],
                        packed_puts=s["packed_puts"],
                        put_buffers=s["put_buffers"],
                        ranks_per_node=rpn or 0, node_aware=node_aware,
                        coalesce=coalesce, pack=pack,
                        derived=derived))
    _SWEEP_CACHE = points
    return points


def sweep():
    """Message-size x topology sweep (the paper's Fig. 10-12 latency-
    curve shape): derived cost per pattern across payload sizes, single-
    node vs 2-node mappings, naive vs node-aware ordering — plus one
    executor worker per pattern verifying the node-aware schedule
    bit-identical to the naive one in-process."""
    print("# sweep: message-size x topology derived latency curves "
          "(adaptive, R=8; rpn = ranks per node)")
    _sweep_rows("sweep")
    for pat, grid in _SWEEP_GRIDS.items():
        kw = dict(pattern=pat) if pat != "faces" else {}
        _worker("sweep", mode="st", throttle="adaptive", merged=1,
                resources=8, block=8 if pat == "faces" else 16,
                grid=",".join(str(g) for g in grid),
                ranks_per_node=_SWEEP_RPN[pat], node_aware=1, coalesce=1,
                verify_node_aware=1, name=f"sweep_{pat}_nodeaware_exec",
                **kw)


def _sweep_rows(section):
    """Print + record the sweep-grid rows belonging to ``section``:
    "sweep" keeps its pre-aggregation point set (naive + node-aware/
    coalesce-marked) so row names stay diffable across PRs; "pack" owns
    every materialized-aggregation point plus its unpacked
    (coalesce=False) baseline."""
    rows = []
    for p in _sweep_points():
        in_pack = p["pack"] or (p["node_aware"] and not p["coalesce"])
        if (section == "pack") != in_pack:
            continue
        tag = _mode_tag(p["node_aware"], p["coalesce"], p["pack"])
        name = (f"sweep_{p['pattern']}_b{p['block']}"
                f"_rpn{p['ranks_per_node']}_{tag}")
        print(f"{name},0.0,{p['derived']:.2f}")
        row = dict(section=section, name=name, us_per_call=0.0,
                   derived=p["derived"], nstreams=1,
                   double_buffer=False, **{
                       k: p[k] for k in
                       ("pattern", "block", "bytes_per_epoch",
                        "inter_puts", "puts_per_epoch", "packed_puts",
                        "ranks_per_node", "node_aware", "coalesce",
                        "pack")})
        RESULTS.append(row)
        rows.append(row)
    return rows


def pack():
    """Materialized put aggregation sweep: packed multi-buffer
    descriptors (schedule.pack_puts) vs the unpacked schedule, per
    pattern and link class — device-free derived curves from the shared
    sweep grid, plus one executor worker per pattern verifying the
    packed schedule bit-identical to the unpacked one in-process
    (run_compiled path; the packed-vs-unpacked host path is covered by
    tests/test_pack.py)."""
    print("# pack: materialized put aggregation (packed multi-buffer "
          "descriptors) vs unpacked, adaptive R=8")
    _sweep_rows("pack")
    for pat, grid in _SWEEP_GRIDS.items():
        kw = dict(pattern=pat) if pat != "faces" else {}
        _worker("pack", mode="st", throttle="adaptive", merged=1,
                resources=8, block=8 if pat == "faces" else 16,
                grid=",".join(str(g) for g in grid),
                ranks_per_node=_SWEEP_RPN[pat], node_aware=1,
                pack=1, verify_pack=1, name=f"pack_{pat}_exec",
                **kw)


# large-message off-node points where chunked pipelining MUST win
# (strict invariant): the put chain is NIC-bound, so per-chunk injection
# overlaps the alpha that a monolithic put serializes. a2a at seq=128
# rides along as an informational row (strict=False): its per-chunk
# completion signals outweigh the alpha hiding there — chunking is not
# free, and the trajectory records that honestly.
_CHUNK_BYTES = 1024
_CHUNK_POINTS = [
    ("ring", (4,), 2, dict(seq_per_rank=64), "s64", True),
    ("ring", (4,), 2, dict(seq_per_rank=128), "s128", True),
    ("broadcast", (2, 4), 2, dict(tile=32), "t32", True),
    ("broadcast", (2, 4), 2, dict(tile=48), "t48", True),
    ("a2a", (4,), 2, dict(seq=128), "s128", False),
]
_CHUNK_CACHE = None


def _chunk_points():
    """Device-free chunked-vs-monolithic derived costs at the
    large-message off-node points (adaptive, R=16 so the chunk chain
    fits the descriptor slots — a chain longer than R throttles against
    itself, rpn=2)."""
    global _CHUNK_CACHE
    if _CHUNK_CACHE is not None:
        return _CHUNK_CACHE
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.patterns import simulate_pattern

    pts = _CHUNK_POINTS if not SMOKE else [
        p for p in _CHUNK_POINTS if p[4] in ("s64", "t32", "s128")]
    niter = 4
    out = []
    for pat, grid, rpn, kw, tag, strict in pts:
        mono = simulate_pattern(pat, niter, grid=grid, resources=16,
                                ranks_per_node=rpn, **kw) / niter
        chunked = simulate_pattern(pat, niter, grid=grid, resources=16,
                                   ranks_per_node=rpn,
                                   chunk_bytes=_CHUNK_BYTES, **kw) / niter
        out.append(dict(pattern=pat, tag=tag, strict=strict,
                        ranks_per_node=rpn, chunk_bytes=_CHUNK_BYTES,
                        mono=mono, chunked=chunked))
    _CHUNK_CACHE = out
    return out


def chunk():
    """Chunked-pipelined transport: schedule.chunk_puts splits each
    large off-node put into a chain of chunk descriptors (per-chunk NIC
    injection, first-chunk-only alpha), so injection of chunk k+1
    overlaps the tail of chunk k — derived rows per point, plus executor
    workers (ring chunked, broadcast chunked+multicast) verifying the
    chunked schedule bit-identical to the monolithic one in-process."""
    print(f"# chunk: chunked pipeline (chunk_bytes={_CHUNK_BYTES}) vs "
          "monolithic puts, adaptive R=16 rpn=2")
    for p in _chunk_points():
        for variant, derived in (("mono", p["mono"]),
                                 ("c%d" % p["chunk_bytes"], p["chunked"])):
            name = (f"chunk_{p['pattern']}_{p['tag']}"
                    f"_rpn{p['ranks_per_node']}_{variant}")
            print(f"{name},0.0,{derived:.2f}")
            RESULTS.append(dict(section="chunk", name=name,
                                us_per_call=0.0, derived=derived,
                                nstreams=1, double_buffer=False,
                                pattern=p["pattern"],
                                ranks_per_node=p["ranks_per_node"],
                                chunk_bytes=(0 if variant == "mono"
                                             else p["chunk_bytes"]),
                                node_aware=False, coalesce=False,
                                pack=False))
    _worker("chunk", pattern="ring", grid="4", block=64, mode="st",
            throttle="adaptive", merged=1, resources=8,
            ranks_per_node=2, chunk_bytes=_CHUNK_BYTES, verify_chunk=1,
            name="chunk_ring_exec")
    _worker("chunk", pattern="broadcast", grid="2,4", block=32, mode="st",
            throttle="adaptive", merged=1, resources=8,
            ranks_per_node=2, chunk_bytes=_CHUNK_BYTES, multicast=1,
            verify_chunk=1, name="chunk_broadcast_exec")


_BCAST_CACHE = None


def _broadcast_points():
    """Device-free multicast-vs-unicast-fanout derived costs on the
    (2, 4) row-broadcast grid (adaptive, R=8, rpn=2)."""
    global _BCAST_CACHE
    if _BCAST_CACHE is not None:
        return _BCAST_CACHE
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.patterns import simulate_pattern

    tiles = [32] if SMOKE else [16, 32, 48]
    niter = 4
    out = []
    for tile in tiles:
        m = simulate_pattern("broadcast", niter, grid=(2, 4), resources=8,
                             ranks_per_node=2, tile=tile,
                             multicast=True) / niter
        u = simulate_pattern("broadcast", niter, grid=(2, 4), resources=8,
                             ranks_per_node=2, tile=tile,
                             multicast=False) / niter
        out.append(dict(tile=tile, mcast=m, ucast=u))
    _BCAST_CACHE = out
    return out


def broadcast():
    """SUMMA-style row fanout: ONE multicast put descriptor (one NIC
    injection + one completion tree) vs cols-1 unicast puts — derived
    rows per tile size, plus an executor worker verifying the multicast
    program bit-identical to the unicast fanout in-process."""
    print("# broadcast: multicast descriptor vs unicast fanout "
          "((2,4) grid, adaptive R=8 rpn=2)")
    for p in _broadcast_points():
        for variant, derived in (("ucast", p["ucast"]),
                                 ("mcast", p["mcast"])):
            name = f"bcast_t{p['tile']}_rpn2_{variant}"
            print(f"{name},0.0,{derived:.2f}")
            RESULTS.append(dict(section="broadcast", name=name,
                                us_per_call=0.0, derived=derived,
                                nstreams=1, double_buffer=False,
                                pattern="broadcast", ranks_per_node=2,
                                chunk_bytes=0, node_aware=False,
                                coalesce=False, pack=False))
    _worker("broadcast", pattern="broadcast", grid="2,4", block=16,
            mode="st", throttle="adaptive", merged=1, resources=8,
            ranks_per_node=2, multicast=1, verify_multicast=1,
            name="broadcast_mcast_exec")
    _worker("broadcast", pattern="broadcast", grid="2,4", block=16,
            mode="host", throttle="none", merged=1,
            ranks_per_node=2, multicast=1, verify_multicast=1,
            name="broadcast_mcast_host")


_FUSED_GRIDS = {"faces": (2, 2, 2), "ring": (4,), "a2a": (4,),
                "broadcast": (2, 4)}
_FUSED_RPN = {"faces": 4, "ring": 2, "a2a": 2, "broadcast": 2}
_FUSED_KW = {"faces": dict(n=(4, 4, 4)), "ring": dict(seq_per_rank=16),
             "a2a": dict(seq=16), "broadcast": dict(tile=16)}
_FUSED_CACHE = None


def _fused_points():
    """Device-free fused-vs-compiled derived costs and host-dispatch
    counts per pattern (adaptive R=8, nstreams=2 so the segment planner
    has cross-stream structure to partition; niter=3 makes every
    pattern multi-epoch)."""
    global _FUSED_CACHE
    if _FUSED_CACHE is not None:
        return _FUSED_CACHE
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.patterns import pattern_programs
    from repro.core.throttle import (CostModel, host_dispatch_count,
                                     simulate_pipeline)

    niter = 3
    out = []
    for pat, grid in _FUSED_GRIDS.items():
        common = dict(grid=grid, throttle="adaptive", resources=8,
                      ranks_per_node=_FUSED_RPN[pat], nstreams=2,
                      **_FUSED_KW[pat])
        base = pattern_programs(pat, niter, **common)
        fus = pattern_programs(pat, niter, fused=True, **common)
        out.append(dict(
            pattern=pat,
            compiled=simulate_pipeline(base, CostModel()) / niter,
            fused=simulate_pipeline(fus, CostModel()) / niter,
            ops=sum(len(p.nodes) for p in base),
            dispatches=sum(host_dispatch_count(p) for p in fus),
            segments=sum(p.meta.get("segments", 0) for p in fus)))
    _FUSED_CACHE = out
    return out


def fused():
    """Device-resident progress engine: fused per-segment emission
    (core/engine.py run_fused) vs the compiled ST executor — derived
    rows and host-dispatch counts per pattern, plus executor workers
    running --exec fused with in-process bit-identity verification
    against run_compiled."""
    print("# fused: device-resident progress engine vs compiled ST "
          "(adaptive R=8, nstreams=2)")
    for p in _fused_points():
        for variant, derived in (("compiled", p["compiled"]),
                                 ("fused", p["fused"])):
            RESULTS.append(dict(
                section="fused", name=f"fused_{p['pattern']}_{variant}",
                us_per_call=0.0, derived=derived, nstreams=2,
                double_buffer=False, pattern=p["pattern"],
                ranks_per_node=_FUSED_RPN[p["pattern"]],
                node_aware=False, coalesce=False, pack=False,
                chunk_bytes=0, fused=(variant == "fused"),
                segments=p["segments"],
                host_dispatches=(p["dispatches"] if variant == "fused"
                                 else p["ops"])))
            print(f"fused_{p['pattern']}_{variant},0.0,{derived:.2f}")
        print(f"# fused {p['pattern']}: segments={p['segments']} "
              f"host_dispatches {p['ops']} -> {p['dispatches']}")
    _worker("fused", grid="2,2,2", block=4, exec="fused", nstreams=2,
            throttle="adaptive", merged=1, resources=8, verify_fused=1,
            name="fused_faces_exec")
    _worker("fused", pattern="broadcast", grid="2,4", block=16,
            exec="fused", nstreams=2, throttle="adaptive", merged=1,
            resources=8, ranks_per_node=2, multicast=1, verify_fused=1,
            name="fused_broadcast_exec")


# the tuned-config grid: one representative (pattern, topology, size)
# point per pattern. Size tokens ("b4") name the message size in the
# tuned-cache key, matching the worker's --block so run.py and
# `faces_worker --config auto` resolve the same cache entry.
_AUTOTUNE_SPECS = [
    ("faces", (2, 2, 2), 4, dict(n=(4, 4, 4)), 4),
    ("ring", (4,), 2, dict(seq_per_rank=32), 32),
    ("a2a", (4,), 2, dict(seq=16), 16),
    ("broadcast", (2, 4), 2, dict(tile=16), 16),
    ("serve", (4,), 2, dict(slots=4), 4),
]
TUNED_PATH = os.path.join(ROOT, "results", "tuned.json")
CALIBRATION_PATH = os.path.join(ROOT, "results", "calibration.json")
_AUTOTUNE_CACHE = None


def _autotune_points():
    """Per-pattern tuned-vs-default derived costs from the simulator-
    guided schedule search, persisted to the tuned cache
    (results/tuned.json) that `--config auto` consults. Scores use the
    SEED cost model on purpose: the trajectory gate diffs these rows
    across PRs, and fresh wall-clock calibration would make them flake —
    the calibrated comparison prints as informational lines instead.
    A pre-populated cache entry short-circuits the search (the CI warm
    path; AUTOTUNE_REFRESH=1 forces a re-search, AUTOTUNE_FULL=1 runs
    the untruncated space — the weekly job)."""
    global _AUTOTUNE_CACHE
    if _AUTOTUNE_CACHE is not None:
        return _AUTOTUNE_CACHE
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.autotune import (autotune, load_tuned, save_tuned,
                                    tuned_key, tuned_record)

    full = env_flag("AUTOTUNE_FULL")
    refresh = env_flag("AUTOTUNE_REFRESH")
    niter = 2
    cache = load_tuned(TUNED_PATH)
    points = []
    for pat, grid, rpn, kw, block in _AUTOTUNE_SPECS:
        size = f"b{block}"
        key = tuned_key(pat, grid, rpn, size)
        hit = None if (refresh or full) else cache.get(key)
        if hit is not None:
            points.append(dict(pattern=pat, size=size, block=block,
                               ranks_per_node=rpn, tuned=hit["derived"],
                               default=hit["default_derived"],
                               config=hit["config"], cached=True))
            continue
        r = autotune(pat, niter, grid=grid, ranks_per_node=rpn,
                     full=full, size=size, **kw)
        cache[key] = tuned_record(r)
        points.append(dict(pattern=pat, size=size, block=block,
                           ranks_per_node=rpn, tuned=r.best_derived,
                           default=r.default_derived,
                           config=r.best.to_dict(), cached=False))
    save_tuned(cache, TUNED_PATH)
    _AUTOTUNE_CACHE = points
    return points


def autotune():
    """Simulator-guided autotuner: tuned-vs-default derived latency per
    pattern (the searched schedule space: throttle R x nstreams x
    double_buffer x node_aware x pack x chunk_bytes x multicast), the
    winner cached in results/tuned.json — plus executor workers running
    `--config auto` through BOTH backends and verifying the tuned
    schedule bit-identical to the flag-default one in-process."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.autotune import ScheduleConfig
    from repro.core.calibrate import load_calibration

    print("# autotune: simulator-guided schedule search, tuned vs "
          "default derived per pattern (cache: results/tuned.json)")
    for p in _autotune_points():
        cfg = ScheduleConfig.from_dict(p["config"])
        src = "cached" if p["cached"] else "searched"
        print(f"# autotune {p['pattern']} {p['size']}: best={cfg.label()} "
              f"({src})")
        for variant, derived in (("default", p["default"]),
                                 ("tuned", p["tuned"])):
            name = f"autotune_{p['pattern']}_{p['size']}_{variant}"
            print(f"{name},0.0,{derived:.2f}")
            RESULTS.append(dict(section="autotune", name=name,
                                us_per_call=0.0, derived=derived,
                                nstreams=1, double_buffer=False,
                                pattern=p["pattern"], block=p["block"],
                                ranks_per_node=p["ranks_per_node"],
                                node_aware=False, coalesce=False,
                                pack=False, chunk_bytes=0,
                                tuned=(variant == "tuned")))
    if load_calibration(CALIBRATION_PATH):
        _autotune_calibrated_lines()
    else:
        print("# autotune: no calibration record "
              "(python -m repro.core.calibrate to fit one) — derived "
              "rows use seed constants")
    # both executors, tuned via the cache the points above just wrote:
    # the tuned schedule must stay bit-identical to the default one
    _worker("autotune", grid="2,2,2", block=4, mode="st",
            ranks_per_node=4, config="auto", tuned=TUNED_PATH,
            verify_tuned=1, name="autotune_faces_exec")
    _worker("autotune", grid="2,2,2", block=4, mode="host",
            ranks_per_node=4, config="auto", tuned=TUNED_PATH,
            verify_tuned=1, name="autotune_faces_host")
    _worker("autotune", pattern="broadcast", grid="2,4", block=16,
            mode="st", ranks_per_node=2, config="auto", tuned=TUNED_PATH,
            verify_tuned=1, name="autotune_broadcast_exec")
    _worker("autotune", pattern="broadcast", grid="2,4", block=16,
            mode="host", ranks_per_node=2, config="auto", tuned=TUNED_PATH,
            verify_tuned=1, name="autotune_broadcast_host")


def _autotune_calibrated_lines():
    """Informational (non-gated, non-trajectory) tuned-vs-default
    comparison under the MEASURED cost model: shows whether the seed-
    model winner still wins when links are priced from this machine's
    calibration. Printed as comments only — wall-clock calibration
    varies per machine, so gating or recording it would flake."""
    from repro.core.autotune import ScheduleConfig, score_config
    from repro.core.calibrate import calibrated_cost_model

    cm = calibrated_cost_model(CALIBRATION_PATH)
    specs = {(pat, f"b{block}"): (grid, rpn, kw)
             for pat, grid, rpn, kw, block in _AUTOTUNE_SPECS}
    for p in _autotune_points():
        grid, rpn, kw = specs[(p["pattern"], p["size"])]
        try:
            d = score_config(p["pattern"], ScheduleConfig(), 2, grid=grid,
                             ranks_per_node=rpn, cm=cm, **kw)
            t = score_config(p["pattern"],
                             ScheduleConfig.from_dict(p["config"]), 2,
                             grid=grid, ranks_per_node=rpn, cm=cm, **kw)
        except Exception as e:   # informational only — never gate on it
            print(f"# autotune calibrated {p['pattern']}: scoring failed "
                  f"({e})")
            continue
        print(f"# autotune calibrated {p['pattern']} {p['size']}: "
              f"tuned={t:.2f} default={d:.2f} "
              f"({'tuned wins' if t <= d else 'DEFAULT wins'} under "
              "measured constants)")


_SERVE_GRID = (4,)
_SERVE_RPN = 2
_SERVE_BUCKETS = [2, 4]
_SERVE_CACHE = None


def _serve_points():
    """Device-free st-vs-host derived costs of ONE serving decode epoch
    (KV mirror + MoE dispatch, core/serve_decode.py) per active-slot
    bucket: the scheduled adaptive ST program against the host-
    orchestrated baseline over the SAME epoch — the decode fast path's
    derived-latency claim, priced like fig12's."""
    global _SERVE_CACHE
    if _SERVE_CACHE is not None:
        return _SERVE_CACHE
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.patterns import pattern_programs
    from repro.core.throttle import CostModel, simulate_pipeline

    niter = 4
    out = []
    for b in _SERVE_BUCKETS:
        common = dict(grid=_SERVE_GRID, ranks_per_node=_SERVE_RPN,
                      slots=b)
        host = pattern_programs("serve", niter, throttle="none",
                                merged=False, **common)
        st = pattern_programs("serve", niter, throttle="adaptive",
                              resources=8, **common)
        out.append(dict(
            bucket=b,
            host=simulate_pipeline(host, CostModel(),
                                   host_orchestrated=True) / niter,
            st=simulate_pipeline(st, CostModel()) / niter))
    _SERVE_CACHE = out
    return out


@functools.cache
def _serve_traffic():
    """The serving smoke's summary (:func:`_serve_traffic_body`, run in
    a child process), or None when the child failed."""
    out = _child("serve", "serve_traffic")
    return None if out is None else json.loads(out.strip().splitlines()[-1])


def _serve_traffic_body():
    """Serving smoke on the tiny reduced arch: the same
    fixed-seed Poisson stream through a baseline fleet and an ST-routed
    fleet (2 replicas each, repro.launch.traffic), plus a fixed-request
    bit-identity comparison of the two decode paths on shared seeded
    params. Wall-clock only — the rows it feeds print derived=0.00 so
    the trajectory gate never prices container timing. Prints the
    summary as one JSON line."""
    import dataclasses
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core.autotune import ScheduleConfig
    from repro.launch.traffic import TrafficConfig, run_traffic
    from repro.models import init_params, model_specs
    from repro.serving import Request, ServingEngine
    from repro.sharding.rules import make_rules

    cfg = dataclasses.replace(
        get_config("granite-3-2b").reduced(), num_layers=2, d_model=64,
        num_heads=2, num_kv_heads=1, d_ff=128, vocab_size=256,
        head_dim=32, grad_accum=1, remat="none")
    rules = make_rules(cfg, None, None)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))

    def engines(st_mode, n):
        kw = {} if st_mode is None else dict(
            st_mode=st_mode, st_config=ScheduleConfig())
        return [ServingEngine(cfg, params, rules, batch_slots=2,
                              max_len=32, **kw) for _ in range(n)]

    tcfg = TrafficConfig(requests=8, rate=500.0, replicas=2,
                         batch_slots=2, max_len=32, prompt_len=(1, 4),
                         max_new=(1, 3), seed=7)
    out = {"base": run_traffic(tcfg, engines=engines(None, 2)),
           "st": run_traffic(dataclasses.replace(tcfg, st_mode="st"),
                             engines=engines("st", 2))}

    def tokens(st_mode):
        eng = engines(st_mode, 1)[0]
        for i in range(5):                  # > slots: slot churn
            eng.submit(Request(prompt=np.arange(1, 3 + i,
                                                dtype=np.int32),
                               max_new_tokens=3))
        eng.run_until_drained()
        return [r.out_tokens for r in eng.completed]

    out["tokens_base"] = tokens(None)
    out["tokens_st"] = tokens("st")
    print(json.dumps(out))


def serve():
    """ST-driven serving fast path: derived decode-epoch cost per
    active-slot bucket (scheduled ST program vs the host-orchestrated
    baseline), executor workers over the serve pattern (host baseline,
    adaptive ST, fused progress engine with in-process bit-identity),
    and the 2-replica Poisson traffic smoke — p50/p99
    latency and TTFT rows carry wall time in us_per_call with
    derived=0.00."""
    print("# serve: decode-time collectives on scheduled ST programs "
          f"(grid {_SERVE_GRID}, rpn={_SERVE_RPN}) + continuous-"
          "batching traffic smoke")
    for p in _serve_points():
        for variant, derived in (("host", p["host"]), ("st", p["st"])):
            name = f"serve_b{p['bucket']}_rpn{_SERVE_RPN}_{variant}"
            print(f"{name},0.0,{derived:.2f}")
            RESULTS.append(dict(section="serve", name=name,
                                us_per_call=0.0, derived=derived,
                                nstreams=1, double_buffer=False,
                                pattern="serve", bucket=p["bucket"],
                                ranks_per_node=_SERVE_RPN,
                                node_aware=False, coalesce=False,
                                pack=False, chunk_bytes=0))
    _worker("serve", pattern="serve", grid="4", block=4, mode="host",
            throttle="none", merged=1, name="serve_host_4r")
    _worker("serve", pattern="serve", grid="4", block=4, mode="st",
            throttle="adaptive", resources=8, merged=1,
            name="serve_st_adaptive_4r")
    _worker("serve", pattern="serve", grid="4", block=4, exec="fused",
            nstreams=2, throttle="adaptive", merged=1, resources=8,
            verify_fused=1, name="serve_fused_4r")
    t = _serve_traffic()
    if t is None:
        return
    for mode in ("base", "st"):
        s = t[mode]
        for metric, val in (("lat_p50", s["latency_p50_ms"]),
                            ("lat_p99", s["latency_p99_ms"]),
                            ("ttft_p99", s["ttft_p99_ms"])):
            name = f"serve_traffic_{mode}_{metric}"
            print(f"{name},{val * 1e3:.1f},0.00")
            RESULTS.append(dict(section="serve", name=name,
                                us_per_call=val * 1e3, derived=0.0,
                                nstreams=1, double_buffer=False,
                                pattern="serve", st_mode=s["st_mode"],
                                replicas=s["replicas"],
                                tokens_per_s=s["tokens_per_s"]))
        print(f"# serve traffic {mode}: {s['completed']}/"
              f"{s['requests']} requests on {s['replicas']} replicas, "
              f"{s['tokens_per_s']:.1f} tok/s, "
              f"ttft p50={s['ttft_p50_ms']:.0f}ms")


def roofline():
    print("# roofline: per-cell terms from results/dryrun "
          "(us_per_call = bound step time; derived = roofline fraction)")
    d = os.path.join(ROOT, "results", "dryrun")
    if not os.path.isdir(d):
        print("# (no dry-run results yet: run python -m repro.launch.dryrun"
              " --all)")
        return
    for name in sorted(os.listdir(d)):
        if not name.endswith(".json"):
            continue
        r = json.load(open(os.path.join(d, name)))
        if r.get("status") != "ok":
            continue
        rf = r["roofline"]
        print(f"roofline_{r['arch']}_{r['shape']}_{r['mesh']},"
              f"{rf['step_s']*1e6:.0f},{rf['roofline_fraction']:.4f}")


def throughput():
    out = _child("throughput", "throughput")
    if out is not None:
        print(out, end="", flush=True)


def _throughput_body():
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.data import SyntheticTokens
    from repro.models import init_params, model_specs
    from repro.optim import opt_init_specs
    from repro.sharding.rules import make_rules
    from repro.train.steps import make_train_step

    cfg = dataclasses.replace(get_config("granite-3-2b").reduced(),
                              remat="none")
    rules = make_rules(cfg, None, None)
    specs = model_specs(cfg)
    params = init_params(specs, jax.random.PRNGKey(0))
    opt = init_params(opt_init_specs(cfg, specs), jax.random.PRNGKey(1),
                      dtype=None)
    step = jax.jit(make_train_step(cfg, rules, moe_impl="dense"))
    ds = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=128,
                         global_batch=8)
    b = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}
    params, opt, _ = step(params, opt, b)   # compile
    t0 = time.perf_counter()
    n = 5
    for _ in range(n):
        params, opt, m = step(params, opt, b)
    jax.block_until_ready(m["loss"])
    dt = (time.perf_counter() - t0) / n
    toks = 8 * 128
    print(f"# throughput: tiny-config train on {jax.devices()[0].platform}"
          " (derived = tokens/s)")
    print(f"throughput_train_tiny,{dt*1e6:.0f},{toks/dt:.0f}")


def check_invariants():
    """Structural invariants on DERIVED costs, for EVERY registered
    pattern, from a device-free lower+schedule+simulate (no fake devices
    needed): the Fig. 13 throttle ordering, and the overlap rule — the
    multi-stream double-buffered schedule never costs more than the
    single-stream schedule it is bit-identical to."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.patterns import available_patterns, simulate_pattern

    size_overrides = {"faces": dict(n=(4, 4, 4))}
    eps = 1e-9
    checks = []
    print("# invariants: derived adaptive <= static <= application; "
          "overlapped(nstreams=2, double_buffer) <= single-stream")
    for pat in available_patterns():
        kw = size_overrides.get(pat, {})
        t = {pol: simulate_pattern(pat, 4, policy=pol, resources=8, **kw)
             for pol in ("adaptive", "static", "application")}
        ok = (t["adaptive"] <= t["static"] + eps
              and t["static"] <= t["application"] + eps)
        checks.append(dict(rule="throttle_order", pattern=pat, ok=ok, **t))
        print(f"# invariant {pat}: adaptive={t['adaptive']:.2f} "
              f"static={t['static']:.2f} application={t['application']:.2f}"
              f" -> {'OK' if ok else 'VIOLATED'}")
        overlapped = simulate_pattern(pat, 4, policy="adaptive",
                                      resources=8, nstreams=2,
                                      double_buffer=True, **kw)
        ok2 = overlapped <= t["adaptive"] + eps
        checks.append(dict(rule="overlap", pattern=pat, ok=ok2,
                           single=t["adaptive"], overlapped=overlapped,
                           nstreams=2, double_buffer=True))
        print(f"# invariant {pat}: overlapped={overlapped:.2f} <= "
              f"single={t['adaptive']:.2f} -> {'OK' if ok2 else 'VIOLATED'}")
    checks += check_topology_invariants()
    checks += check_chunk_invariants()
    checks += check_autotune_invariants()
    checks += check_fused_invariants()
    checks += check_serve_invariants()
    return checks


def check_serve_invariants():
    """Serving SLO gates: the scheduled ST decode epoch's derived cost
    never exceeds the host-orchestrated baseline at any slot bucket;
    the ST-routed engine serves BIT-IDENTICAL tokens to the baseline on
    shared seeded params (the transported ``outtok`` buffer is what the
    engine reads, so a delivery defect changes this); the traffic smoke
    drains its queue with every request completed and a finite bounded
    p99; and the serve-program meta is present on every ST replica —
    proof the decode collectives actually ran on the ST path."""
    import math

    eps = 1e-9
    checks = []
    print("# invariants: serve st derived <= host baseline per bucket; "
          "st tokens bit-identical; traffic drained, p99 bounded, "
          "ST meta present")
    for p in _serve_points():
        ok = p["st"] <= p["host"] + eps
        checks.append(dict(rule="serve_st_latency", pattern="serve",
                           ok=ok, bucket=p["bucket"], st=p["st"],
                           host=p["host"]))
        print(f"# invariant serve b{p['bucket']}: st={p['st']:.2f} <= "
              f"host={p['host']:.2f} -> {'OK' if ok else 'VIOLATED'}")
    t = _serve_traffic()
    if t is None:
        checks.append(dict(rule="serve_bit_identity", pattern="serve",
                           ok=False, requests=0))
        print("# invariant serve bit-identity: traffic child failed -> "
              "VIOLATED")
        return checks
    ok = bool(t["tokens_base"]) and t["tokens_st"] == t["tokens_base"]
    checks.append(dict(rule="serve_bit_identity", pattern="serve",
                       ok=ok, requests=len(t["tokens_base"])))
    print(f"# invariant serve bit-identity: st tokens == baseline over "
          f"{len(t['tokens_base'])} requests -> "
          f"{'OK' if ok else 'VIOLATED'}")
    for mode in ("base", "st"):
        s = t[mode]
        drained = (bool(s["queue_drained"])
                   and s["completed"] == s["requests"])
        p99 = s["latency_p99_ms"]
        bounded = math.isfinite(p99) and 0 < p99 < 120_000.0
        ok = drained and bounded
        checks.append(dict(rule="serve_slo", pattern="serve", ok=ok,
                           mode=mode, drained=drained,
                           latency_p99_ms=p99,
                           ttft_p99_ms=s["ttft_p99_ms"],
                           tokens_per_s=s["tokens_per_s"]))
        print(f"# invariant serve slo [{mode}]: drained={drained} "
              f"p99={p99:.0f}ms (<120000) -> "
              f"{'OK' if ok else 'VIOLATED'}")
    metas = [r.get("st") for r in t["st"]["per_replica"]]
    ok = all(m and m["pattern"] == "serve" and m["buckets"]
             and all(v["puts"] >= 1 for v in m["buckets"].values())
             for m in metas)
    checks.append(dict(rule="serve_st_meta", pattern="serve",
                       ok=bool(ok), replicas=len(metas)))
    print(f"# invariant serve st-meta: scheduled-program stats on "
          f"{len(metas)} replica(s) -> {'OK' if ok else 'VIOLATED'}")
    return checks


def check_fused_invariants():
    """Progress-engine invariants: the fused schedule's derived latency
    never exceeds the compiled executor's over the identical DAG
    (per-segment host dispatch can only remove host-timeline work), and
    the per-segment host-dispatch count is STRICTLY below the per-op
    count for every multi-epoch pattern — the host-overhead win the
    paper attributes to fully offloaded progress."""
    eps = 1e-9
    checks = []
    print("# invariants: fused <= compiled per pattern; per-segment "
          "host dispatches < per-op dispatches")
    for p in _fused_points():
        ok = p["fused"] <= p["compiled"] + eps
        checks.append(dict(rule="fused_latency", pattern=p["pattern"],
                           ok=ok, fused=p["fused"],
                           compiled=p["compiled"]))
        print(f"# invariant fused {p['pattern']}: "
              f"fused={p['fused']:.2f} <= compiled={p['compiled']:.2f} "
              f"-> {'OK' if ok else 'VIOLATED'}")
        ok2 = p["dispatches"] < p["ops"]
        checks.append(dict(rule="fused_dispatch", pattern=p["pattern"],
                           ok=ok2, host_dispatches=p["dispatches"],
                           ops=p["ops"], segments=p["segments"]))
        print(f"# invariant fused_dispatch {p['pattern']}: "
              f"{p['dispatches']} dispatch(es) < {p['ops']} op(s) -> "
              f"{'OK' if ok2 else 'VIOLATED'}")
    return checks


def check_autotune_invariants():
    """Autotuner invariant: for EVERY pattern the searched config's
    derived latency is no worse than the default config's — guaranteed
    by construction (the default is always candidate zero of the
    search), so a violation means the search or the cache is broken,
    not that the space is unlucky."""
    eps = 1e-9
    checks = []
    print("# invariants: tuned <= default per pattern (autotune grid)")
    for p in _autotune_points():
        ok = p["tuned"] <= p["default"] + eps
        checks.append(dict(rule="autotune", pattern=p["pattern"], ok=ok,
                           size=p["size"], tuned=p["tuned"],
                           default=p["default"], config=p["config"],
                           cached=p["cached"]))
        print(f"# invariant autotune {p['pattern']} {p['size']}: "
              f"tuned={p['tuned']:.2f} <= default={p['default']:.2f} -> "
              f"{'OK' if ok else 'VIOLATED'}")
    return checks


def check_chunk_invariants():
    """Chunked-pipeline and multicast invariants: at every strict
    large-message off-node point the chunked schedule's derived latency
    is STRICTLY below the monolithic one (per-chunk NIC injection hides
    the alpha a monolithic put serializes), and the multicast descriptor
    is strictly cheaper than its cols-1 unicast fanout (one injection +
    one completion tree vs cols-1 of each)."""
    eps = 1e-9
    checks = []
    print("# invariants: chunked < monolithic at strict points; "
          "multicast < unicast fanout")
    for p in _chunk_points():
        if p["strict"]:
            ok = p["chunked"] < p["mono"] - eps
            rule = "chunk_pipeline"
            rel = "<"
        else:
            ok = True          # informational point: recorded, not gated
            rule = "chunk_info"
            rel = "vs"
        checks.append(dict(rule=rule, pattern=p["pattern"], ok=ok,
                           tag=p["tag"], chunk_bytes=p["chunk_bytes"],
                           chunked=p["chunked"], mono=p["mono"]))
        print(f"# invariant {rule} {p['pattern']} {p['tag']}: "
              f"chunked={p['chunked']:.2f} {rel} mono={p['mono']:.2f} -> "
              f"{'OK' if ok else 'VIOLATED'}")
    for p in _broadcast_points():
        ok = p["mcast"] < p["ucast"] - eps
        checks.append(dict(rule="multicast", pattern="broadcast", ok=ok,
                           tile=p["tile"], mcast=p["mcast"],
                           ucast=p["ucast"]))
        print(f"# invariant multicast t{p['tile']}: "
              f"mcast={p['mcast']:.2f} < ucast={p['ucast']:.2f} -> "
              f"{'OK' if ok else 'VIOLATED'}")
    return checks


def check_topology_invariants():
    """Link-cost-model invariants over the sweep grid: derived cost
    monotone in payload bytes (the Fig. 10-12 latency-curve shape), an
    inter-node put strictly costlier than an intra-node put of equal
    size, a multi-node mapping never cheaper than single-node, and the
    node-aware ordering never costlier than the naive order."""
    from repro.core.throttle import CostModel

    eps = 1e-9
    checks = []
    cm = CostModel()
    print("# invariants: t_put(inter) > t_put(intra); derived monotone "
          "in bytes; multi-node >= single-node; node-aware <= naive")
    for nb in (64, 4096, 262144):
        ok = cm.t_put("inter", nb) > cm.t_put("intra", nb)
        checks.append(dict(rule="link_cost", pattern=f"{nb}B", ok=ok,
                           inter=cm.t_put("inter", nb),
                           intra=cm.t_put("intra", nb)))
        print(f"# invariant link_cost {nb}B: inter="
              f"{cm.t_put('inter', nb):.2f} > intra="
              f"{cm.t_put('intra', nb):.2f} -> {'OK' if ok else 'VIOLATED'}")
    points = _sweep_points()
    curves = {}
    for p in points:
        key = (p["pattern"], p["ranks_per_node"], p["node_aware"],
               p["coalesce"], p["pack"])
        curves.setdefault(key, []).append(p)
    for (pat, rpn, na, co, pk), pts in sorted(curves.items()):
        pts = sorted(pts, key=lambda p: p["bytes_per_epoch"])
        mono = all(a["derived"] <= b["derived"] + eps
                   for a, b in zip(pts, pts[1:]))
        checks.append(dict(rule="monotone_bytes", pattern=pat, ok=mono,
                           ranks_per_node=rpn, node_aware=na,
                           coalesce=co, pack=pk,
                           derived=[p["derived"] for p in pts]))
        curve = " -> ".join(f"{p['derived']:.1f}" for p in pts)
        print(f"# invariant monotone {pat} rpn={rpn} "
              f"{_mode_tag(na, co, pk)}: "
              f"{curve} -> {'OK' if mono else 'VIOLATED'}")
    by_cfg = {(p["pattern"], p["block"], p["ranks_per_node"],
               p["node_aware"], p["coalesce"], p["pack"]): p["derived"]
              for p in points}
    for (pat, block, rpn, na, co, pk), derived in sorted(by_cfg.items()):
        if pk or (na and not co):
            continue         # the pack points have their own rules below
        if rpn and not na:
            single = by_cfg[(pat, block, 0, False, False, False)]
            ok = derived >= single - eps
            checks.append(dict(rule="internode_geq", pattern=pat, ok=ok,
                               block=block, multi=derived, single=single))
            if not ok:
                print(f"# invariant internode {pat} b{block}: "
                      f"multi={derived:.2f} < single={single:.2f} "
                      "-> VIOLATED")
        if rpn and na:
            naive = by_cfg[(pat, block, rpn, False, False, False)]
            ok = derived <= naive + eps
            checks.append(dict(rule="node_aware", pattern=pat, ok=ok,
                               block=block, node_aware=derived,
                               naive=naive))
            print(f"# invariant node_aware {pat} b{block}: "
                  f"{derived:.2f} <= naive={naive:.2f} -> "
                  f"{'OK' if ok else 'VIOLATED'}")
    checks += check_pack_invariants(points, by_cfg, eps)
    return checks


# per-pattern packed-descriptor counts on the sweep topologies with
# throttle="none" (every put dependency-free): ring packs its K,V pair
# (2 -> 1 put/epoch), a2a packs partial+aux per shift (2(n-1) -> n-1),
# faces on the (2,2,2)/rpn=4 grid packs the 18 off-node surface puts
# into 4 same-permutation descriptors (+ 8 on-node singles = 12)
_PACK_EXPECT = {"faces": (26.0, 12.0), "ring": (2.0, 1.0),
                "a2a": (6.0, 3.0)}


def check_pack_invariants(points, by_cfg, eps):
    """Materialized-aggregation invariants over the sweep grid: the
    packed schedule's derived latency never exceeds its unpacked
    (coalesce=False) baseline at any point; packing is the identity on
    a single-node (all-intra) topology; and the derived put-descriptor
    count per multi-buffer epoch drops exactly as the group structure
    predicts (ring K,V -> 1, a2a partial+aux -> 1 per shift, faces
    same-permutation multi-face groups)."""
    from repro.core.patterns import pattern_programs

    checks = []
    print("# invariants: packed <= unpacked per pattern/link; packed "
          "descriptor counts (ring 2->1, a2a 2(n-1)->n-1 puts/epoch)")
    for (pat, block, rpn, na, co, pk), derived in sorted(by_cfg.items()):
        if not pk:
            continue
        base = by_cfg[(pat, block, rpn, na, co, False)]
        if rpn:
            ok = derived <= base + eps
            rule = "pack_latency"
            rel = "<="
        else:
            # intra link: nothing packs, so the cost must be IDENTICAL
            ok = abs(derived - base) <= eps
            rule = "pack_intra_identity"
            rel = "=="
        checks.append(dict(rule=rule, pattern=pat, ok=ok, block=block,
                           ranks_per_node=rpn, packed=derived,
                           unpacked=base))
        print(f"# invariant {rule} {pat} b{block} rpn={rpn}: "
              f"{derived:.2f} {rel} unpacked={base:.2f} -> "
              f"{'OK' if ok else 'VIOLATED'}")
    for pat, grid in _SWEEP_GRIDS.items():
        unpacked_ppe, packed_ppe = _PACK_EXPECT[pat]
        stats = {}
        for pk in (False, True):
            progs = pattern_programs(
                pat, 2, grid=grid, throttle="none",
                ranks_per_node=_SWEEP_RPN[pat], pack=pk,
                **_sweep_size_kw(pat, 4 if pat == "faces" else 16))
            stats[pk] = progs[0].stats()
        ok = (stats[False]["puts_per_epoch"] == unpacked_ppe
              and stats[True]["puts_per_epoch"] == packed_ppe
              and stats[True]["packed_puts"] > 0
              and stats[True]["put_buffers"] == stats[False]["puts"])
        checks.append(dict(
            rule="pack_descriptor_count", pattern=pat, ok=ok,
            unpacked_puts_per_epoch=stats[False]["puts_per_epoch"],
            packed_puts_per_epoch=stats[True]["puts_per_epoch"],
            expected=list(_PACK_EXPECT[pat]),
            packed_descriptors=stats[True]["packed_puts"]))
        print(f"# invariant pack_count {pat}: puts/epoch "
              f"{stats[False]['puts_per_epoch']:.0f} -> "
              f"{stats[True]['puts_per_epoch']:.0f} "
              f"(expect {unpacked_ppe:.0f} -> {packed_ppe:.0f}) -> "
              f"{'OK' if ok else 'VIOLATED'}")
    return checks


SECTIONS = {
    "fig12": fig12, "fig13": fig13, "fig14": fig14, "fig15": fig15,
    "fig16_17": fig16_17, "ring": ring, "a2a": a2a, "overlap": overlap,
    "sweep": sweep, "pack": pack, "chunk": chunk, "broadcast": broadcast,
    "fused": fused, "autotune": autotune, "serve": serve,
    "roofline": roofline, "throughput": throughput,
}


# section bodies that need JAX; each runs only in a child process
CHILD_BODIES = {"serve_traffic": _serve_traffic_body,
                "throughput": _throughput_body}


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", choices=sorted(CHILD_BODIES),
                    help=argparse.SUPPRESS)
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write rows/failures/invariants as one JSON file")
    ap.add_argument("--check-invariants", action="store_true",
                    help="assert adaptive <= static <= application and "
                         "overlapped <= single-stream on derived costs "
                         "for every ST pattern")
    ap.add_argument("--bench-id",
                    default=os.environ.get("BENCH_ID", "BENCH_10"),
                    help="basename of the repo-root perf-trajectory "
                         "record --json also writes (env: BENCH_ID)")
    args = ap.parse_args()
    if args.child:
        CHILD_BODIES[args.child]()
        return
    names = (args.only.split(",") if args.only else list(SECTIONS))
    print("name,us_per_call,derived")
    for n in names:
        SECTIONS[n]()
    checks = check_invariants() if args.check_invariants else []
    violated = [c["pattern"] for c in checks if not c["ok"]]

    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        # record the active calibration constants (None when derived
        # numbers used seed constants): check_trajectory warns when two
        # records were priced under different constants, because every
        # derived column rebaselines then
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro.core.calibrate import load_calibration
        cal = load_calibration(CALIBRATION_PATH)
        rec = {"sections": names, "rows": RESULTS, "failures": FAILURES,
               "invariants": checks,
               "calibration": cal["cost_model"] if cal else None,
               "env": {"niter": os.environ.get("BENCH_NITER", "10"),
                       "smoke": SMOKE}}
        with open(args.json, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"# wrote {args.json} ({len(RESULTS)} rows, "
              f"{len(FAILURES)} failures)")
        # the perf trajectory: a repo-root record future PRs diff derived
        # numbers against (CI uploads it as an artifact and
        # scripts/check_trajectory.py diffs it against the previous
        # PR's record) — a map from row name to derived latency plus the
        # full rows and invariant verdicts, so regressions show up as a
        # one-line diff instead of flying blind
        traj = os.path.join(ROOT, f"{args.bench_id}.json")
        with open(traj, "w") as f:
            json.dump({"bench_id": args.bench_id, "sections": names,
                       "derived": {r["name"]: r["derived"]
                                   for r in RESULTS},
                       "rows": RESULTS,
                       "invariants": checks,
                       "failures": FAILURES,
                       "calibration": rec["calibration"],
                       "env": rec["env"]}, f, indent=1)
        print(f"# wrote {traj}")

    if FAILURES:
        print(f"# {len(FAILURES)} worker(s) FAILED", file=sys.stderr)
    if violated:
        print(f"# invariant VIOLATED for: {', '.join(violated)}",
              file=sys.stderr)
    if FAILURES or violated:
        sys.exit(1)


if __name__ == "__main__":
    main()
