"""STStream — the stream-triggered deferred execution queue (paper §2, §4).

The host *enqueues* operations (post / start / put / complete / wait /
kernel launches) and returns immediately; nothing executes until
``synchronize``. Execution is a three-stage compiler pipeline over the
triggered-op IR (repro.core.triggered):

    enqueue API --(1) lower.py--> TriggeredProgram DAG
                --(2) schedule.py passes--> scheduled DAG (+dep edges)
                --(3) backends.py / engine.py / throttle.py--> one of
                      four emitters

Stage-3 emitters all consume the SAME scheduled DAG:

  * mode="st"   (Fig. 9b): the WHOLE queue (all iterations) is traced into
    ONE jitted shard_map program — the TPU analogue of the GPU SEC
    executing enqueued descriptors with NIC triggered ops, zero host
    round-trips. ``synchronize`` is the single host sync at the end.

  * mode="host" (Fig. 9a): each descriptor runs as its own jitted call
    with host blocking at every epoch boundary — the CPU-orchestrated
    standard active-RMA baseline.

  * mode="fused": the device-resident progress engine
    (core/engine.py) — the schedule is planned into per-stream
    segments and each segment launches as ONE fused emission unit;
    host involvement scales with the segment count, not the
    descriptor count.

  * the cost simulator (core/throttle.py) walks the identical schedule,
    so benchmarks' "derived" column cannot drift from what executes.

Throttling (paper §5.2) constrains put issue through a finite ResourcePool:
  * "application": the app inserts host_sync() points (program splits)
  * "static":  epoch e puts depend on ALL epoch e-1 completions
  * "adaptive": put i depends only on completion of put i-R (sliding window)
These are schedule passes (dependency-edge transforms), not emission-time
branches.

Each ``synchronize`` is the program span ``st.synchronize``
(:mod:`repro.runtime.spans`), holding ``st.schedule`` (the queue's
cache key and the scheduled-program lookup, lowering on a miss), then
per scheduled program the executor's ``st.lookup`` (its cache key and
lookup), ``st.launch`` (the jitted call until it returns; ``st.compile``
in its place for the first call of a newly built function) and
``st.block`` (``block_until_ready``). The host executor records
``st.lookup`` and ``st.launch`` per dispatch and ``st.block`` per epoch
boundary.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import backends
from repro.core.lower import lower_segment, split_segments
from repro.core.schedule import schedule
from repro.core.triggered import TriggeredProgram
from repro.core.window import STWindow
from repro.runtime import spans


@dataclass
class _Op:
    """Raw enqueue-API record; lowered onto the triggered-op IR."""
    kind: str
    window: Optional[STWindow] = None
    fn: Optional[Callable] = None
    # monotonic per-stream identity of fn, assigned at launch(): id(fn)
    # can be reused by a fresh closure after the old one is collected,
    # which would silently hit stale _sched_cache/_compiled_cache entries
    fn_token: int = -1
    reads: Tuple[str, ...] = ()
    writes: Tuple[str, ...] = ()
    put: Optional[dict] = None
    phase: int = 0            # ping/pong parity (double-buffered windows)
    label: str = ""

    def cache_key(self):
        put = (tuple(sorted(self.put.items())) if self.put else None)
        return (self.kind, self.fn_token, self.reads, self.writes, put,
                self.window.name if self.window else None, self.phase,
                self.label)


class STStream:
    """Deferred op queue over a process-grid mesh.

    ``mesh=None`` (with an explicit ``grid_shape``) builds a device-free
    stream whose programs can be lowered, scheduled, and simulated but
    not executed — used by the cost model and schedule unit tests.
    """

    def __init__(self, mesh: Optional[Mesh], grid_axes: Sequence[str],
                 periodic: bool = True,
                 grid_shape: Optional[Sequence[int]] = None):
        self.mesh = mesh
        self.grid_axes = tuple(grid_axes)
        if mesh is not None:
            self.grid_shape = tuple(mesh.shape[a] for a in self.grid_axes)
        else:
            if grid_shape is None:
                raise ValueError("grid_shape is required when mesh is None")
            self.grid_shape = tuple(grid_shape)
        self.num_ranks = int(np.prod(self.grid_shape))
        self.periodic = periodic
        self.pattern = ""          # set by pattern builders; flows into
        #                            program meta / #stats / JSON records
        self.program: List[_Op] = []
        self.windows: Dict[str, STWindow] = {}
        self._perm_cache: Dict[tuple, list] = {}
        self._sched_cache: Dict[tuple, List[TriggeredProgram]] = {}
        # fn identity tokens: keyed by the function OBJECT (a strong ref,
        # so a collected closure can never alias a live token) and drawn
        # from a never-reset monotonic counter
        self._fn_tokens: Dict[Callable, int] = {}
        self._fn_token_counter = itertools.count()

    # -- window management --------------------------------------------------
    def create_window(self, name, buffers, group, topology=None,
                      double_buffer=False, db_names=()) -> STWindow:
        win = STWindow(name=name, buffers=buffers, group=list(group),
                       topology=topology, double_buffer=double_buffer,
                       db_names=tuple(db_names))
        self.windows[name] = win
        return win

    def allocate(self) -> Dict[str, jnp.ndarray]:
        state = {}
        for win in self.windows.values():
            state.update(win.allocate(self.num_ranks))
        if self.mesh is not None:
            spec = self.state_spec()
            state = {k: jax.device_put(
                v, NamedSharding(self.mesh, spec)) for k, v in state.items()}
        return state

    def state_spec(self) -> P:
        return P(self.grid_axes)

    # -- enqueue API (returns immediately: deferred execution) ---------------
    def launch(self, fn, reads, writes, label="kernel"):
        tok = self._fn_tokens.get(fn)
        if tok is None:
            tok = self._fn_tokens[fn] = next(self._fn_token_counter)
        self.program.append(_Op("kernel", fn=fn, fn_token=tok,
                                reads=tuple(reads), writes=tuple(writes),
                                label=label))

    def post(self, win: STWindow, phase: int = 0):
        self.program.append(_Op("post", window=win, phase=phase))

    def start(self, win: STWindow, mode: str = "MPIX_MODE_STREAM",
              phase: int = 0):
        self.program.append(_Op("start", window=win, phase=phase,
                                label=mode))

    def put(self, win: STWindow, src: str, dst: str, direction,
            phase: int = 0):
        self.program.append(_Op("put", window=win, phase=phase,
                                put=dict(src=src, dst=dst,
                                         direction=tuple(direction))))

    def put_multicast(self, win: STWindow, src: str, dsts, directions,
                      phase: int = 0):
        """One-to-many put: ONE source payload fans out to the rank in
        each of ``directions``, landing in the matching buffer of
        ``dsts`` — lowered to a single multicast descriptor with one
        completion tree (counted as one signal at the source), versus
        ``len(directions)`` unicast puts."""
        if len(dsts) != len(directions):
            raise ValueError("put_multicast: dsts and directions must "
                             "pair up per branch")
        self.program.append(_Op(
            "put", window=win, phase=phase,
            put=dict(src=src, dsts=tuple(dsts),
                     directions=tuple(tuple(d) for d in directions))))

    def complete(self, win: STWindow, phase: int = 0):
        self.program.append(_Op("complete", window=win, phase=phase))

    def wait(self, win: STWindow, phase: int = 0):
        self.program.append(_Op("wait", window=win, phase=phase))

    def host_sync(self):
        """Application-level throttling point (paper §5.2.1)."""
        self.program.append(_Op("hostsync"))

    def clear(self):
        self.program = []
        self.pattern = ""       # a rebuild may enqueue a different pattern
        self._sched_cache.clear()
        # release the closure refs; the token COUNTER is never reset, so
        # a closure created after clear() can never alias a stale
        # _sched_cache/_compiled_cache entry even if id() is reused
        self._fn_tokens.clear()
        for cache in ("_compiled_cache", "_host_cache", "_fused_cache"):
            if hasattr(self, cache):
                getattr(self, cache).clear()

    # -- neighbor permutation -------------------------------------------------
    def rank_strides(self) -> tuple:
        """Row-major strides of the grid-coordinate -> linear-rank map.
        The SINGLE definition of rank linearization: perm_for and the
        executors' traced axis_index lookups both derive from it."""
        strides, acc = [], 1
        for n in reversed(self.grid_shape):
            strides.append(acc)
            acc *= n
        return tuple(reversed(strides))

    def perm_for(self, direction: tuple) -> list:
        if direction in self._perm_cache:
            return self._perm_cache[direction]
        dims = self.grid_shape
        nd = len(dims)
        d = tuple(direction) + (0,) * (nd - len(direction))
        strides = self.rank_strides()

        def lin(coord):
            return sum((c % n) * s
                       for c, n, s in zip(coord, dims, strides))

        pairs = []
        for src in np.ndindex(*dims):
            dst = tuple((src[i] + d[i]) % dims[i] for i in range(nd))
            if not self.periodic:
                ok = all(0 <= src[i] + d[i] < dims[i] for i in range(nd))
                if not ok:
                    continue
            pairs.append((lin(src), lin(dst)))
        self._perm_cache[direction] = pairs
        return pairs

    def opposite_index(self, win: STWindow, direction) -> int:
        """Kept for callers predating per-pattern topologies; the
        direction algebra now lives on the window."""
        return win.opposite_index(direction)

    # -- compile pipeline: lower (1) + schedule (2) ---------------------------
    def scheduled_programs(self, *, throttle: str = "adaptive",
                           resources: int = 64, merged: bool = True,
                           ordered: bool = False, nstreams: int = 1,
                           node_aware: bool = False,
                           coalesce: bool = False,
                           pack: bool = False,
                           chunk_bytes: int = 0,
                           fused: bool = False,
                           config=None) -> List[TriggeredProgram]:
        """Lower the op queue and run the schedule passes; one scheduled
        descriptor DAG per host_sync-delimited segment. Cached per
        (queue, options) so repeated synchronize calls reuse programs
        (and therefore compiled executables).

        ``config`` (a :class:`repro.core.autotune.ScheduleConfig` or its
        dict form) expands into the schedule-pass knobs above BEFORE the
        cache key is computed, so a tuned config and its spelled-out
        kwargs share one cache entry. Build-time knobs the config may
        carry (double_buffer, multicast) are ignored here — the queue is
        already built; rebuild via ``pattern_programs(config=...)`` to
        apply those. The string ``"auto"`` is rejected: a raw stream
        does not know its (pattern, topology, size) cache key — resolve
        it with ``repro.core.autotune.tuned_config`` or
        ``pattern_programs(config="auto")`` instead."""
        if config is not None:
            from repro.core.autotune import ScheduleConfig
            if isinstance(config, str):
                raise ValueError(
                    "scheduled_programs(config='auto') is ambiguous on a "
                    "raw stream (no pattern/topology/size key); resolve "
                    "it via repro.core.autotune.tuned_config or "
                    "pattern_programs(config='auto')")
            if isinstance(config, dict):
                config = ScheduleConfig.from_dict(config)
            return self.scheduled_programs(**config.sched_kwargs())
        key = (tuple(op.cache_key() for op in self.program),
               throttle, resources, merged, ordered, nstreams,
               node_aware, coalesce, pack, chunk_bytes, fused)
        progs = self._sched_cache.get(key)
        if progs is None:
            progs = [
                schedule(lower_segment(self, seg), throttle=throttle,
                         resources=resources, merged=merged,
                         ordered=ordered, nstreams=nstreams,
                         node_aware=node_aware, coalesce=coalesce,
                         pack=pack, chunk_bytes=chunk_bytes, fused=fused)
                for seg in split_segments(self.program)]
            self._sched_cache[key] = progs
        return progs

    # -- execution: emit (3) ---------------------------------------------------
    def synchronize(self, state, mode: str = "st", throttle: str = "adaptive",
                    resources: int = 64, merged: bool = True,
                    donate: bool = True, ordered: bool = False,
                    nstreams: int = 1, node_aware: bool = False,
                    coalesce: bool = False, pack: bool = False,
                    chunk_bytes: int = 0, fused: bool = False,
                    config=None):
        """Execute the enqueued program; returns the new state.

        mode="st": one compiled program, single host sync (this call).
        mode="host": per-descriptor dispatch, blocking at epoch boundaries.
        mode="fused": the device-resident progress engine — one fused
        emission unit per planned segment (``fused=True`` scheduling is
        implied; the segment planner runs over the finished schedule).
        ``pack`` materializes off-node aggregation groups as packed
        multi-buffer put descriptors (schedule.pack_puts);
        ``chunk_bytes`` splits larger off-node puts into pipelined chunk
        chains (schedule.chunk_puts). ``config`` expands a tuned
        :class:`~repro.core.autotune.ScheduleConfig` into the schedule
        knobs (see :meth:`scheduled_programs`).
        """
        if self.mesh is None:
            raise ValueError("cannot execute a device-free stream "
                             "(constructed with mesh=None)")
        fused = fused or mode == "fused"
        with spans.span("st.synchronize"):
            with spans.span("st.schedule"):
                progs = self.scheduled_programs(
                    throttle=throttle, resources=resources, merged=merged,
                    ordered=ordered, nstreams=nstreams,
                    node_aware=node_aware, coalesce=coalesce, pack=pack,
                    chunk_bytes=chunk_bytes, fused=fused, config=config)
            for prog in progs:
                if mode == "fused":
                    from repro.core import engine
                    state = engine.run_fused(self, prog, state,
                                             donate=donate)
                elif mode == "st":
                    state = backends.run_compiled(self, prog, state,
                                                  donate=donate)
                else:
                    state = backends.run_host(self, prog, state)
                # application-level sync between segments: a full host
                # block must fence EVERY buffer, not just the first leaf
                with spans.span("st.block"):
                    jax.block_until_ready(state)
        return state


def counters_expected(niter: int, npeers: int):
    """After n iterations of post/complete, every signal slot == n."""
    return niter * np.ones((npeers,), np.int32)
