"""Device-resident progress engine: segment planner + fused executor.

  * segment partition (property tests, hypothesis degrading to the
    example-based shim): segments exactly partition the program, each is
    a CONSECUTIVE same-stream same-wave run, waves are monotone per
    stream, and ``heads`` names each segment's opening descriptor,
  * boundary coherence: a chunk chain never splits across segments (the
    planner lifts every chunk to the chain's maximum wave) and a packed
    group is one descriptor inside one segment — composition of
    pack+chunk included,
  * every cross-stream dependency edge lands on a segment BOUNDARY: the
    dependent op's wave is strictly later than the producer's, so no
    edge ever enters a segment mid-run,
  * static arenas: each segment's buffer/counter offsets are 64-byte
    aligned, distinct, and inside the declared arena footprint,
  * fused emission order: wave-major, topological, a permutation of the
    program,
  * per-segment host dispatch: ``host_dispatch_count`` is the head count
    for fused programs (strictly below the op count on every multi-epoch
    pattern) and the op count otherwise; the derived fused latency never
    exceeds the unfused schedule's,
  * the verifier accepts fused schedules (wave-boundary HB edges stay
    acyclic) with zero findings,
  * executor equivalence: the fused progress engine is bit-identical to
    run_compiled on every pattern — including packed, chunked, and
    multicast descriptors (multi-device, in a subprocess),
  * emission backend: chosen from the platform of the mesh's devices
    (Pallas on TPU, the traced reference on CPU, an error elsewhere).
"""
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # degrade to example-based sweeps
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import (CostModel, fused_order, host_dispatch_count,
                        pattern_programs, plan_segments, simulate_pattern)
from repro.core.engine import fusion_backend
from repro.core.verify import verify

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PATTERNS = ["faces", "ring", "a2a", "broadcast"]
SIZE_KW = {"faces": dict(n=(4, 4, 4))}
GRID = {"faces": (2, 2, 2), "ring": (4,), "a2a": (4,),
        "broadcast": (2, 4)}
RPN = {"faces": 4, "ring": 2, "a2a": 2, "broadcast": 2}   # two nodes each


def _prog(pat, niter=2, **kw):
    kw = dict(SIZE_KW.get(pat, {}), grid=GRID[pat],
              ranks_per_node=RPN[pat], **kw)
    progs = pattern_programs(pat, niter, throttle="adaptive", resources=8,
                             **kw)
    assert len(progs) == 1
    return progs[0]


def _fused(pat, niter=2, **kw):
    prog = _prog(pat, niter, fused=True, **kw)
    plan = prog.meta["segment_plan"]
    assert prog.meta["fused"] and prog.meta["segments"] == \
        len(plan.segments)
    return prog, plan


# ---------------------------------------------------------------------------
# segment partition (property tests; degrade to example sweeps)
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(pat=st.sampled_from(PATTERNS), nstreams=st.integers(1, 3),
       niter=st.integers(1, 3))
def test_segments_partition_the_program(pat, nstreams, niter):
    prog, plan = _fused(pat, niter, nstreams=nstreams,
                        double_buffer=nstreams > 1)
    covered = [oid for s in plan.segments for oid in s.op_ids]
    assert sorted(covered) == sorted(n.op_id for n in prog.nodes)
    assert len(covered) == len(set(covered))
    by_id = {n.op_id: n for n in prog.nodes}
    pos = {n.op_id: i for i, n in enumerate(prog.nodes)}
    for s in plan.segments:
        assert s.op_ids, "empty segment"
        assert all(by_id[o].stream == s.stream for o in s.op_ids)
        assert all(plan.wave_of[o] == s.wave for o in s.op_ids)
        # consecutive run in the stream's program order
        stream_ids = [n.op_id for n in prog.nodes if n.stream == s.stream]
        lo = stream_ids.index(s.op_ids[0])
        assert tuple(stream_ids[lo:lo + len(s.op_ids)]) == s.op_ids
        assert pos[s.op_ids[0]] == min(pos[o] for o in s.op_ids)
    assert plan.heads == frozenset(s.op_ids[0] for s in plan.segments)
    assert plan.waves == 1 + max(s.wave for s in plan.segments)


@settings(max_examples=12, deadline=None)
@given(pat=st.sampled_from(PATTERNS), nstreams=st.integers(1, 3))
def test_waves_monotone_per_stream_and_cross_deps_on_boundaries(
        pat, nstreams):
    prog, plan = _fused(pat, 2, nstreams=nstreams,
                        double_buffer=nstreams > 1)
    by_id = {n.op_id: n for n in prog.nodes}
    last = {}
    for n in prog.nodes:
        w = plan.wave_of[n.op_id]
        assert w >= last.get(n.stream, 0), (n.stream, n.op_id)
        last[n.stream] = w
        for d in n.deps:
            if by_id[d].stream != n.stream:
                # the edge meets a segment boundary, never mid-run: the
                # dependent's whole segment launches a strictly later wave
                assert plan.wave_of[d] < w, (d, n.op_id)


@settings(max_examples=8, deadline=None)
@given(pat=st.sampled_from(["ring", "a2a", "broadcast"]),
       chunk_bytes=st.sampled_from([64, 256]), nstreams=st.integers(1, 2))
def test_chunk_chains_never_split_across_segments(pat, chunk_bytes,
                                                  nstreams):
    # broadcast's default tile is below the chunk thresholds — size it up
    kw = {"broadcast": dict(tile=32)}.get(pat, {})
    prog, plan = _fused(pat, 2, nstreams=nstreams,
                        double_buffer=nstreams > 1, node_aware=True,
                        chunk_bytes=chunk_bytes, **kw)
    chains = {}
    for p in prog.puts():
        if p.chunk_count > 1 and p.chunk_head >= 0:
            chains.setdefault(p.chunk_head, []).append(p.op_id)
    assert chains, (pat, "no chunk chains — vacuous")
    seg_of = {oid: i for i, s in enumerate(plan.segments)
              for oid in s.op_ids}
    for head, members in chains.items():
        segs = {seg_of[m] for m in members}
        assert len(segs) == 1, (pat, head, segs)
        assert len({plan.wave_of[m] for m in members}) == 1


def test_packed_groups_stay_whole_with_chunking():
    """pack+chunk composition: every packed descriptor (and the chunk
    chain it may expand into) lives inside exactly one segment."""
    prog, plan = _fused("ring", 2, pack=True, node_aware=True,
                        chunk_bytes=64)
    packed = [p for p in prog.puts() if p.label
              and p.label.startswith("packed_put")]
    assert packed, "no packed descriptors — vacuous"
    seg_of = {oid: i for i, s in enumerate(plan.segments)
              for oid in s.op_ids}
    for p in packed:
        assert p.op_id in seg_of
        if p.chunk_count > 1:
            chain = [q.op_id for q in prog.puts()
                     if q.chunk_head == p.chunk_head]
            assert len({seg_of[m] for m in chain}) == 1


def test_segment_arenas_static_aligned_disjoint():
    prog, plan = _fused("faces", 2, nstreams=2, double_buffer=True)
    for s in plan.segments:
        assert s.arena, "segment with an empty arena"
        offs = sorted(s.arena.values())
        assert all(o % 64 == 0 for o in offs)
        assert len(offs) == len(set(offs))
        assert 0 <= offs[0] and offs[-1] < s.arena_nbytes


# ---------------------------------------------------------------------------
# fused emission order
# ---------------------------------------------------------------------------

def test_fused_order_is_wave_major_topological_permutation():
    for ns in (1, 2, 3):
        prog, plan = _fused("faces", 2, nstreams=ns,
                            double_buffer=ns > 1)
        order = fused_order(prog, plan)
        assert sorted(n.op_id for n in order) == \
            sorted(n.op_id for n in prog.nodes)
        waves = [plan.wave_of[n.op_id] for n in order]
        assert waves == sorted(waves)          # wave-major
        pos = {n.op_id: i for i, n in enumerate(order)}
        for n in prog.nodes:
            for d in n.deps:
                assert pos[d] < pos[n.op_id], (d, n.op_id)


# ---------------------------------------------------------------------------
# per-segment host dispatch + derived cost
# ---------------------------------------------------------------------------

def test_host_dispatch_per_segment_strictly_below_per_op():
    for pat in PATTERNS:
        fused_prog, plan = _fused(pat, 3, nstreams=2, double_buffer=True)
        base = _prog(pat, 3, nstreams=2, double_buffer=True)
        assert host_dispatch_count(base) == len(base.nodes)
        assert host_dispatch_count(fused_prog) == len(plan.heads)
        assert len(plan.heads) < len(fused_prog.nodes), pat


def test_fused_derived_cost_not_worse_any_pattern():
    for pat in PATTERNS:
        kw = dict(SIZE_KW.get(pat, {}), grid=GRID[pat],
                  ranks_per_node=RPN[pat])
        base = simulate_pattern(pat, 3, policy="adaptive", resources=8,
                                cm=CostModel(), **kw)
        fu = simulate_pattern(pat, 3, policy="adaptive", resources=8,
                              fused=True, cm=CostModel(), **kw)
        assert fu <= base + 1e-9, (pat, fu, base)


# ---------------------------------------------------------------------------
# the verifier accepts fused schedules (wave HB edges stay acyclic)
# ---------------------------------------------------------------------------

def test_verifier_clean_on_fused_schedules():
    for pat in PATTERNS:
        prog, _ = _fused(pat, 2, nstreams=2, double_buffer=True,
                         node_aware=True)
        rep = verify(prog)
        assert rep.ok and not rep.findings, (pat, rep.findings[:3])
        assert rep.checked, pat


@pytest.mark.parametrize("platform,backend", [("tpu", "pallas"),
                                              ("cpu", "traced"),
                                              ("gpu", None)])
def test_fusion_backend_follows_mesh_platform(platform, backend):
    """The backend comes from the devices the stream's mesh spans, never
    the process's default backend, and no platform silently falls back
    to the traced path."""
    mesh = types.SimpleNamespace(
        devices=np.array([types.SimpleNamespace(platform=platform)]))
    if backend is None:
        with pytest.raises(NotImplementedError, match=platform):
            fusion_backend(mesh)
    else:
        assert fusion_backend(mesh) == backend


# ---------------------------------------------------------------------------
# executor equivalence: fused progress engine vs run_compiled,
# bit-identical on every pattern incl. pack + chunk + multicast
# ---------------------------------------------------------------------------

EQUIV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from repro.core import STStream, get_pattern
    from repro.launch.mesh import make_mesh

    CASES = [
        ("faces", (2, 2, 2), ("x", "y", "z"), 4,
         dict(n=(3, 3, 3)), dict(double_buffer=True),
         dict(nstreams=2), ["acc", "res", "src", "it"], ["src"]),
        ("ring", (4,), ("data",), 2,
         dict(batch=1, seq_per_rank=4, heads=2, head_dim=8), dict(),
         dict(pack=True, node_aware=True, chunk_bytes=64), ["out"],
         ["q", "k", "v"]),
        ("a2a", (4,), ("model",), 2,
         dict(batch=1, seq=8, d_model=16, expert_ff=16, experts=8,
              top_k=2), dict(),
         dict(pack=True, node_aware=True), ["out", "aux"],
         ["x", "router", "wg", "wu", "wd"]),
        ("broadcast", (2, 4), ("row", "col"), 2,
         dict(tile=8), dict(multicast=True),
         dict(node_aware=True, chunk_bytes=64), ["ctile", "it"],
         ["abase", "b"]),
    ]
    niter = 2
    for pat_name, grid, axes, rpn, kw, build_kw, sched_kw, outputs, \\
            seeds in CASES:
        pat = get_pattern(pat_name)
        mesh = make_mesh(grid, axes)

        def run(mode):
            stream = STStream(mesh, axes)
            win, _ = pat.build(stream, niter, merged=True,
                               ranks_per_node=rpn, **kw, **build_kw)
            state = stream.allocate()
            rng = np.random.RandomState(0)
            for b in seeds:
                k = win.qual(b)
                val = rng.rand(*state[k].shape).astype(
                    np.asarray(state[k]).dtype) * 0.3
                state[k] = jax.device_put(val, state[k].sharding)
            state = stream.synchronize(state, mode=mode,
                                       throttle="adaptive", resources=8,
                                       donate=False, **sched_kw)
            if mode == "fused":
                progs = stream.scheduled_programs(fused=True, **dict(
                    sched_kw, throttle="adaptive", resources=8))
                assert sum(p.meta.get("segments", 0) for p in progs), \\
                    (pat_name, "no segments — vacuous")
                # a CPU mesh runs the traced reference emission
                assert {p.meta["fusion_backend"] for p in progs} \\
                    == {"traced"}, pat_name
            return {b: np.asarray(state[win.qual(b)]) for b in outputs}

        ref = run("st")
        got = run("fused")
        for b in outputs:
            assert (got[b] == ref[b]).all(), \\
                (pat_name, b, np.abs(got[b] - ref[b]).max())
            assert np.asarray(got[b]).any(), (pat_name, b, "vacuous")
        print(f"OK fused {pat_name}")
""")


@pytest.mark.slow
def test_fused_bit_identical_all_patterns():
    """The fused progress engine reproduces run_compiled bit-for-bit on
    every pattern output — multi-stream double-buffered faces, packed +
    chunked ring, packed a2a, and multicast + chunked broadcast."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", EQUIV_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.count("OK") == 4
