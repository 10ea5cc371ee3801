"""Device-resident progress engine: the shared emitter + fused executor.

This module is the single descriptor-emission implementation of stage 3.
:func:`emit_node` (with its :class:`_EmitCtx` trace state and the
completion-signal / ppermute / arrival-mask helpers) used to live inside
``backends.py``; it now lives here so all three executor paths —
``run_compiled``, ``run_host`` (thin consumers in
:mod:`repro.core.backends`), and the fused :func:`run_fused` below —
emit every descriptor kind through ONE implementation. The fourth
consumer, the cost simulator in :mod:`repro.core.throttle`, walks the
same scheduled DAG without emitting.

:func:`run_fused` is the paper family's fully offloaded progress engine
(ROADMAP item 1, the CPU-Free-MPI co-design direction): the segment
planner (:func:`repro.core.schedule.plan_segments`) has partitioned the
scheduled program into per-stream SEGMENTS — maximal same-stream runs
with no cross-stream dependency edge entering mid-run, each with a
static device arena layout — and the engine lowers EACH segment into
one fused emission unit. Device-resident counters run the
post/start/put/complete/wait protocol inside the unit; the host's only
job is launching segments in wave order. Host involvement therefore
scales with the SEGMENT count, not the descriptor count — the
host-overhead win behind the paper's off-node P2P gap — and the
simulator charges ``t_dispatch`` per segment accordingly.

Emission backend selection (:func:`fusion_backend`, from the platform
of the devices the stream's mesh spans; recorded per program in
``prog.meta["fusion_backend"]``):

  * ``"pallas"`` — TPU: the segment's device-resident counter bumps
    run as ``pallas_call`` kernels against the counter arena (the
    first rung of the mega-kernel ladder; payload collectives stay
    traced ``ppermute`` — they must cross ranks, which a single-core
    kernel cannot).
  * ``"traced"`` — CPU, the reference the tests compare against: the
    fused units are traced wave-major (segment-contiguous) into ONE
    jitted shard_map launch. Bit-identical to ``run_compiled`` by
    construction: the same :func:`emit_node` emits every descriptor,
    dependency ties are value-neutral ``optimization_barrier`` edges,
    and all value-carrying effects thread through the state buffers.
    (Launching each segment as its OWN jit executable would change
    XLA's fusion context per boundary and perturb float reductions at
    the ulp level — so the executor keeps one executable and realizes
    the per-segment structure in emission order, arena metadata, and
    the simulator's per-segment host-dispatch accounting.)

Any other platform has no fused backend and raises.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.compat import shard_map
from repro.core.window import is_counter_name
from repro.runtime import spans
from repro.kernels.halo_pack.ref import (chunk_gather, chunk_scatter,
                                         pack_flat, unpack_flat)


def _tie(x, dep):
    """Make x depend on dep without changing its value (dataflow edge)."""
    if dep is None:
        return x
    x, _ = jax.lax.optimization_barrier((x, dep))
    return x


def fusion_backend(mesh) -> str:
    """Emission backend of the fused executor for a stream on ``mesh``,
    decided by the platform of the devices the mesh spans: ``"pallas"``
    on TPU, ``"traced"`` on CPU (the reference path). No other platform
    has a fused backend."""
    platform = mesh.devices.flat[0].platform
    if platform == "tpu":
        return "pallas"
    if platform == "cpu":
        return "traced"
    raise NotImplementedError(
        f"fused executor: no emission backend for platform {platform!r}")


def _pallas_bump(sig, upd):
    """Device-resident counter bump as a Pallas kernel: the segment's
    merged post/completion counter update runs ON the device arena
    instead of as traced elementwise HLO. Only reached when
    :func:`fusion_backend` is ``"pallas"`` (TPU meshes); value-identical
    to ``sig + upd`` — the engine's bit-identity guarantee does not
    depend on which backend executed the bump."""

    def kernel(sig_ref, upd_ref, out_ref):
        out_ref[...] = sig_ref[...] + upd_ref[...]

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(sig.shape, sig.dtype))(sig, upd)


class _EmitCtx:
    """Trace-local emission state: a completion/effect token per emitted
    op_id (what dependency edges tie to) and the post-counter snapshot
    each "start" takes, keyed by (window, epoch) so epochs of the same
    window in flight on different streams never clobber each other.

    ``backend`` selects how counter bumps execute: "traced" (plain HLO,
    the legacy executors) or "pallas" (device-resident arena kernel,
    the fused engine on TPU). Both produce identical values."""

    def __init__(self, backend: str = "traced"):
        self.tokens: Dict[int, Any] = {}
        self.trig: Dict[tuple, Any] = {}
        self.backend = backend

    def bump(self, sig, upd):
        if self.backend == "pallas":
            return _pallas_bump(sig, upd)
        return sig + upd


def _ppermute(stream, x, direction):
    return jax.lax.ppermute(x, stream.grid_axes,
                            stream.perm_for(tuple(direction)))


def _local_rank(stream):
    """Linear rank index inside shard_map — same strides as perm_for's
    linearization (stream.rank_strides is the single definition)."""
    idx = 0
    for a, s in zip(stream.grid_axes, stream.rank_strides()):
        idx = idx + jax.lax.axis_index(a) * s
    return idx


def _arrival_mask(stream, direction):
    """1 where this rank RECEIVES a payload sent in ``direction`` —
    non-periodic boundary ranks have no source and must not see a
    completion bump. Memoized on the stream: the mask depends only on
    the grid and direction, and rebuilding it per emitted put made
    trace time scale with put count (packed puts make it hot — every
    packed completion signal consults its group's mask)."""
    cache = getattr(stream, "_arrival_mask_cache", None)
    if cache is None:
        cache = stream._arrival_mask_cache = {}
    key = tuple(direction)
    mask = cache.get(key)
    if mask is None:
        recv = np.zeros((stream.num_ranks,), np.int32)
        for _, dst in stream.perm_for(key):
            recv[dst] = 1
        mask = cache[key] = recv
    return mask


def _emit_completion_signal(stream, node, st, arrival_token):
    """§3.2 chained completion signal of a put descriptor. A multicast
    put's chained signal is the completion TREE: one signal op whose
    leaves bump each branch target's slot (``ch.slots``); unicast puts
    have the single (slot, direction) leaf."""
    ch = node.chained
    branches = ch.slots or ((ch.slot, node.direction),)
    if ch.wire:
        # a second triggered put bumping the TARGET's comp counter over
        # the wire, triggered by the payload's arrival
        one = _tie(jnp.ones((1, 1), jnp.int32), arrival_token)
        sig_buf = st[ch.counter]
        for slot, d in branches:
            sig = _ppermute(stream, one, d)
            sig_buf = sig_buf.at[:, slot].add(sig[:, 0])
        st[ch.counter] = sig_buf
    else:
        # merged/local bump: the arrived payload IS the completion event
        one = _tie(jnp.ones((1,), jnp.int32), arrival_token)
        sig_buf = st[ch.counter]
        for slot, d in branches:
            bump = one
            if not stream.periodic:
                # a boundary rank with no source in this direction
                # received only the zero-fill, not a payload: no
                # completion lands
                mask = jnp.asarray(_arrival_mask(stream, d))
                bump = bump * mask[_local_rank(stream)]
            sig_buf = sig_buf.at[:, slot].add(bump)
        st[ch.counter] = sig_buf
    return st


def scope_name(node) -> str:
    """``st.<kind>.<label>``: the name scope a descriptor's operations
    carry in the HLO metadata (``op_name``), so a device trace can tell
    the kernels and puts of a program apart. A ``/`` of the label (a
    chunk's ``#c0/4``) would open a nested scope, and becomes ``_``."""
    label = node.label.replace("/", "_").replace(" ", "")
    return f"st.{node.kind}.{label}" if label else f"st.{node.kind}"


def emit_node(stream, node, st, ctx, *, with_chained=True):
    """Apply one descriptor's state effect. Shared by every executor.

    Every node leaves a tiny effect token in ``ctx.tokens`` so dependency
    edges from ANY node kind (cross-stream conflict edges, throttle
    edges) can be tied as dataflow. The node's operations are emitted
    under the name scope :func:`scope_name` gives (metadata only)."""
    with jax.named_scope(scope_name(node)):
        return _emit(stream, node, st, ctx, with_chained)


def _emit(stream, node, st, ctx, with_chained):
    if node.kind == "kernel":
        args = [st[r] for r in node.reads]
        if args:
            for dep in node.deps:
                args[0] = _tie(args[0], ctx.tokens.get(dep))
        outs = node.fn(*args)
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        for w, o in zip(node.writes, outs):
            st[w] = o
        if not args:
            # write-only kernel: thread its dep edges through the outputs
            for dep in node.deps:
                for w in node.writes:
                    st[w] = _tie(st[w], ctx.tokens.get(dep))
        if node.writes:
            ctx.tokens[node.op_id] = st[node.writes[0]].ravel()[:1]
    elif node.kind == "signal" and node.role == "post":
        sig = st[node.counter]
        for dep in node.deps:
            sig = _tie(sig, ctx.tokens.get(dep))
        if node.fused:
            # merged signal kernel (paper §5.4): one update for all peers
            upd = jnp.zeros_like(sig)
            for slot, d in node.slots:
                arrived = _ppermute(stream, jnp.ones((1, 1), jnp.int32), d)
                upd = upd.at[:, slot].add(arrived[:, 0])
            sig = ctx.bump(sig, upd)
        else:
            arrived = _ppermute(stream, jnp.ones((1, 1), jnp.int32),
                                node.direction)
            sig = sig.at[:, node.slot].add(arrived[:, 0])
        st[node.counter] = sig
        ctx.tokens[node.op_id] = sig.ravel()[:1]
    elif node.kind == "start":
        # origin-side wait for exposure signals: the epoch's puts are
        # armed by (tied to) the post counter as of this point
        snap = st[node.counter]
        for dep in node.deps:
            snap = _tie(snap, ctx.tokens.get(dep))
        ctx.trig[(node.window, node.epoch)] = snap
        ctx.tokens[node.op_id] = snap.ravel()[:1]
    elif node.kind == "put":
        packed = len(node.srcs) > 1
        chunked = node.chunk_count > 1
        if chunked:
            # one CHUNK of a pipelined chain (schedule.chunk_puts):
            # gather only this chunk's element slice of the logical flat
            # payload (the group concat for packed puts) — the staging
            # slices of different chunks trace independently, so
            # pack(k+1) overlaps wire(k) overlaps unpack(k-1) with no
            # artificial barriers between chunks of different puts
            parts = ([st[s] for s in node.srcs] if packed
                     else [st[node.src]])
            payload = chunk_gather(parts, node.chunk_offset,
                                   node.chunk_elems)
        elif packed:
            # packed multi-buffer descriptor (schedule.pack_puts): pack
            # the group's payloads into ONE contiguous staging buffer,
            # ride ONE collective (every member shares the same rank
            # permutation, so one ppermute moves the whole group), and
            # unpack into the destination buffers on arrival — a pure
            # byte reshuffle, bit-identical to the unpacked puts
            payload = pack_flat([st[s] for s in node.srcs])
        else:
            payload = st[node.src]
        payload = _tie(payload, ctx.trig.get((node.window, node.epoch)))
        for dep in node.deps:
            payload = _tie(payload, ctx.tokens.get(dep))
        if node.mcast_dirs:
            # multicast descriptor: the ONE traced payload fans out over
            # every branch permutation (the executor analogue of switch
            # replication) and lands in its branch's dst buffer; the
            # single chained signal below is the completion tree
            token = None
            for d, dname in zip(node.mcast_dirs, node.dsts):
                arrived = _ppermute(stream, payload, d)
                if chunked:
                    st[dname], = chunk_scatter(arrived, [st[dname]],
                                               node.chunk_offset,
                                               node.chunk_elems)
                else:
                    st[dname] = arrived
                tok = arrived.ravel()[:1]
                token = tok if token is None else _tie(token, tok)
        else:
            arrived = _ppermute(stream, payload, node.direction)
            if chunked:
                dnames = node.dsts if packed else (node.dst,)
                updated = chunk_scatter(arrived, [st[d] for d in dnames],
                                        node.chunk_offset,
                                        node.chunk_elems)
                for dname, new in zip(dnames, updated):
                    st[dname] = new
            elif packed:
                for dst, part in zip(
                        node.dsts,
                        unpack_flat(arrived, [st[d] for d in node.dsts])):
                    st[dst] = part
            else:
                st[node.dst] = arrived
            token = arrived.ravel()[:1]
        ctx.tokens[node.op_id] = token
        if with_chained and node.chained is not None:
            st = _emit_completion_signal(stream, node, st, token)
    elif node.kind == "complete":
        pass        # epoch-close marker: deps were precomputed by passes
    elif node.kind == "wait":
        # wait kernel: all subsequent reads of the window's (this
        # phase's) data buffers depend on the completion counter. The
        # fence set comes from lowering (node.writes); prefix-matching is
        # the fallback for hand-built programs.
        dep = st[node.counter]
        for d in node.deps:
            dep = _tie(dep, ctx.tokens.get(d))
        fence = node.writes or tuple(
            k for k in st
            if k.startswith(node.window + ".") and not is_counter_name(k))
        for k in fence:
            st[k] = _tie(st[k], dep)
        ctx.tokens[node.op_id] = dep.ravel()[:1]
    else:
        raise ValueError(f"cannot emit node kind {node.kind!r}")
    return st


# ---------------------------------------------------------------------------
# fused executor: one emission unit per planned segment
# ---------------------------------------------------------------------------

def fused_order(prog, plan):
    """Wave-major, segment-contiguous emission order: segments sorted by
    (wave, stream), each segment's descriptor run emitted whole. A valid
    topological order of the scheduled DAG: every cross-stream
    dependency edge points to a strictly earlier wave (the planner's
    boundary invariant), and per-stream program order is preserved —
    same-stream segments appear in increasing wave, ops inside a segment
    in program order."""
    by_id = {n.op_id: n for n in prog.nodes}
    return [by_id[oid] for seg in plan.segments for oid in seg.op_ids]


def fused_executable(stream, prog, keys, donate=True):
    """The jitted fused program for ``prog`` over the state ``keys``
    (sorted), cached on the stream. The planner's segments become the
    emission units: descriptors are emitted wave-major
    (:func:`fused_order`), each segment's run traced contiguously, with
    counter bumps routed through the backend :func:`fusion_backend`
    picks for the stream's mesh; the choice is recorded in
    ``prog.meta["fusion_backend"]``. ONE executable covers the whole
    program — the same launch shape as ``run_compiled``, which is what
    makes the two executors bit-identical on every pattern/knob
    combination — while the host-involvement model (what the simulator
    charges and what the bench JSON reports) is per SEGMENT: the
    device-resident counters sequence everything inside a wave, and the
    host's only remaining job is advancing waves.

    Programs scheduled without ``fused=True`` are planned lazily here."""
    return _fused_lookup(stream, prog, keys, donate)[0]


def _fused_lookup(stream, prog, keys, donate):
    """:func:`fused_executable`, and whether it had to be built."""
    plan = prog.meta.get("segment_plan")
    if plan is None:
        from repro.core.schedule import plan_segments
        plan = plan_segments(prog)
    backend = fusion_backend(stream.mesh)
    prog.meta["fusion_backend"] = backend
    cache = getattr(stream, "_fused_cache", None)
    if cache is None:
        cache = stream._fused_cache = {}
    ck = (prog.key(), keys, donate, backend)
    jfn = cache.get(ck)
    miss = jfn is None
    if miss:
        spec = stream.state_spec()
        order = fused_order(prog, plan)

        def fused_fn(*vals):
            st = dict(zip(keys, vals))
            ctx = _EmitCtx(backend=backend)
            for node in order:
                st = emit_node(stream, node, st, ctx)
            return tuple(st[k] for k in keys)

        sharded = shard_map(
            fused_fn, mesh=stream.mesh,
            in_specs=(spec,) * len(keys), out_specs=(spec,) * len(keys))
        jfn = cache[ck] = jax.jit(
            sharded,
            donate_argnums=tuple(range(len(keys))) if donate else ())
    return jfn, miss


def launch(jfn, args, miss):
    """Call an executor's jitted function under the span ``st.launch``,
    until it returns. The first call of a function the executor has just
    built (``miss``) compiles it, and is recorded as ``st.compile``
    instead."""
    if miss:
        with spans.span("st.compile"):
            return jfn(*args)
    with spans.span("st.launch"):
        return jfn(*args)


def run_fused(stream, prog, state, donate=True):
    """Execute a fused-scheduled program through the progress engine
    (:func:`fused_executable`); returns the new state. The cache key and
    lookup are the span ``st.lookup``, the call :func:`launch`'s."""
    with spans.span("st.lookup"):
        keys = tuple(sorted(state.keys()))
        jfn, miss = _fused_lookup(stream, prog, keys, donate)
    out = launch(jfn, [state[k] for k in keys], miss)
    return dict(zip(keys, out))
