"""Stage 3 — legacy executors: thin consumers of the shared emitter.

FOUR consumers walk the SAME :class:`TriggeredProgram` the schedule
passes produced: the two executors here, the device-resident progress
engine (:func:`repro.core.engine.run_fused` — one fused emission unit
per planned segment, host involvement per SEGMENT not per op), and the
cost simulator in :mod:`repro.core.throttle`. The descriptor-emission
implementation itself — ``emit_node``, its ``_EmitCtx`` trace state,
and the completion-signal helpers — lives in :mod:`repro.core.engine`;
this module only decides WHEN emission happens and what the host does
between emissions:

  * :func:`run_compiled` (Fig. 9b, mode="st"): the whole program (all
    iterations) is traced into ONE jitted shard_map call — the TPU
    analogue of the GPU SEC executing enqueued descriptors with NIC
    triggered ops, zero host round-trips. Dependency edges become
    dataflow (optimization_barrier) ties, so trigger/completion ordering
    is faithful inside the single compiled program.

  * :func:`run_host` (Fig. 9a, mode="host"): the CPU-orchestrated
    standard active-RMA baseline — one jitted dispatch per descriptor,
    host blocking at every epoch boundary (start/complete/wait). Wire
    completion signals dispatch separately from their payload put, like
    the MPI runtime's completion handling; dependency edges are NOT
    re-emitted — the serialized dispatch order must satisfy them, and
    :func:`_assert_dispatch_order` proves it does before the first
    dispatch (a forward edge raises with a ``verify.find_cycle``
    witness instead of being silently ignored).

Packed multi-buffer descriptors (schedule.pack_puts) are ONE node and
therefore one emission unit in both executors: run_compiled traces
pack -> single ppermute -> unpack (fewer collectives and barrier ties
in the HLO), run_host issues one dispatch for the whole group — the
host-dispatch saving behind the paper's off-node P2P gap.

Chunked-pipelined puts (schedule.chunk_puts) emit one unit PER CHUNK:
run_compiled traces each chunk's gather -> ppermute -> scatter with
only real dependency edges between them (chunks of different puts
interleave freely in the HLO), run_host dispatches each chunk as its
own descriptor. Multicast puts emit one unit fanning the single traced
payload over every branch permutation, with ONE chained completion
tree (slots-based) standing for all branches.

Signals and completions are REAL counter buffers updated by chained tiny
puts (paper §3.1–3.2), so tests can assert the epoch protocol.
"""
from __future__ import annotations

import jax

from repro.core.compat import shard_map
# the shared emitter stack moved to repro.core.engine in the progress-
# engine refactor; the names stay importable here for existing callers
from repro.core.engine import (_arrival_mask, _emit_completion_signal,  # noqa: F401
                               _EmitCtx, _local_rank, _ppermute, _tie,
                               emit_node, launch)
from repro.core.schedule import stream_interleaved_order
from repro.runtime import spans


# ---------------------------------------------------------------------------
# compiled ST executor (Fig. 9b)
# ---------------------------------------------------------------------------

def run_compiled(stream, prog, state, donate=True):
    with spans.span("st.lookup"):
        keys = tuple(sorted(state.keys()))
        jfn, miss = _compiled_lookup(stream, prog, keys, donate)
    out = launch(jfn, [state[k] for k in keys], miss)
    return dict(zip(keys, out))


def _compiled_lookup(stream, prog, keys, donate):
    """The cached jitted program of ``prog`` over the state ``keys``, and
    whether it had to be built."""
    cache = getattr(stream, "_compiled_cache", None)
    if cache is None:
        cache = stream._compiled_cache = {}
    ck = (prog.key(), keys, donate)
    jfn = cache.get(ck)
    miss = jfn is None
    if miss:
        spec = stream.state_spec()
        # multi-stream schedules trace in a stream-interleaved topological
        # order (program order within a stream; cross-stream ordering only
        # where a real dependency edge ties it) so epoch e+1's post/put
        # traffic interleaves epoch e's compute in the emitted program
        order = stream_interleaved_order(prog)

        def seg_fn(*vals):
            st = dict(zip(keys, vals))
            ctx = _EmitCtx()
            for node in order:
                st = emit_node(stream, node, st, ctx)
            return tuple(st[k] for k in keys)

        sharded = shard_map(
            seg_fn, mesh=stream.mesh,
            in_specs=(spec,) * len(keys), out_specs=(spec,) * len(keys))
        jfn = cache[ck] = jax.jit(
            sharded,
            donate_argnums=tuple(range(len(keys))) if donate else ())
    return jfn, miss


# ---------------------------------------------------------------------------
# host-orchestrated executor (Fig. 9a baseline)
# ---------------------------------------------------------------------------

_BLOCKING = ("start", "complete", "wait")


def _assert_dispatch_order(prog):
    """Prove the serialized dispatch order satisfies every dependency
    edge before dispatching anything.

    run_host never re-emits dep edges — correctness rests entirely on
    ``prog.nodes`` order respecting them. A schedule whose edge points
    FORWARD (a node depending on an op dispatched later — e.g. a
    multi-stream program handed to the host path without re-ordering)
    used to be silently accepted and executed wrong. Here it raises,
    with a witness cycle from :func:`repro.core.verify.find_cycle` over
    the waiting-for graph (each node waits for its unemitted deps AND
    its dispatch predecessor — the same construction
    ``stream_interleaved_order`` uses for its stuck witness)."""
    pos = {n.op_id: i for i, n in enumerate(prog.nodes)}
    violated = [(n, d) for n in prog.nodes for d in n.deps
                if d in pos and pos[d] > pos[n.op_id]]
    if not violated:
        return
    from repro.core.verify import find_cycle

    nodes = {n.op_id: n for n in prog.nodes}

    def waiting_for(op_id):
        succ = [d for d in nodes[op_id].deps if d in nodes]
        i = pos[op_id]
        if i > 0:
            succ.append(prog.nodes[i - 1].op_id)
        return succ

    cyc = find_cycle(nodes, waiting_for)
    witness = " -> ".join(f"{nodes[i].kind}#{i}" for i in (cyc or []))
    n, d = violated[0]
    raise ValueError(
        f"run_host: dependency edge out of dispatch order — "
        f"{n.kind}#{n.op_id} ({n.label or n.window}) depends on op {d} "
        f"dispatched only later; the serialized host order would "
        f"silently ignore the edge. Re-schedule for the host path "
        f"(nstreams=1) or use the st/fused executors. "
        f"Witness cycle: {witness or 'forward edge'}")


def run_host(stream, prog, state):
    _assert_dispatch_order(prog)
    for node in prog.nodes:
        if node.kind == "put" and node.chained is not None \
                and node.chained.wire:
            # baseline RMA: payload dispatch, then the completion signal
            # as its own dispatch (the MPI runtime's completion handling)
            state = _dispatch_host(stream, node, state, unit="put")
            state = _dispatch_host(stream, node, state, unit="chained")
        elif node.kind in ("start", "complete"):
            pass        # markers: no state effect, just the host block
        else:
            state = _dispatch_host(stream, node, state, unit="node")
        if node.kind in _BLOCKING:
            # the host block the cost model charges t_sync for must
            # fence EVERY buffer of the state tree, not just one leaf
            with spans.span("st.block"):
                jax.block_until_ready(state)
    return state


def _dispatch_host(stream, node, state, unit):
    with spans.span("st.lookup"):
        keys = tuple(sorted(state.keys()))
        jfn, miss = _host_lookup(stream, node, keys, unit)
    out = launch(jfn, [state[k] for k in keys], miss)
    return dict(zip(keys, out))


def _host_lookup(stream, node, keys, unit):
    """The cached one-descriptor executable of ``node``'s ``unit``, and
    whether it had to be built."""
    cache = getattr(stream, "_host_cache", None)
    if cache is None:
        cache = stream._host_cache = {}
    # deps/epochs excluded: host ordering is the serialized dispatch
    # itself (proven by _assert_dispatch_order), so one executable per
    # structural op serves all iterations
    ck = (unit, node.structural_key(with_deps=False), keys)
    jfn = cache.get(ck)
    miss = jfn is None
    if miss:
        spec = stream.state_spec()

        def one_fn(*vals):
            st = dict(zip(keys, vals))
            ctx = _EmitCtx()
            if unit == "chained":
                # arrival token: any buffer the put delivered into (a
                # multicast/packed put has dsts and no single dst)
                landed = node.dst or node.dsts[-1]
                st = _emit_completion_signal(
                    stream, node, st, st[landed].ravel()[:1])
            else:
                # deps tie through ctx.tokens, which is empty per dispatch:
                # host ordering comes from the serialized dispatches
                st = emit_node(stream, node, st, ctx,
                               with_chained=(unit == "node"))
            return tuple(st[k] for k in keys)

        sharded = shard_map(
            one_fn, mesh=stream.mesh,
            in_specs=(spec,) * len(keys), out_specs=(spec,) * len(keys))
        jfn = cache[ck] = jax.jit(sharded)
    return jfn, miss
