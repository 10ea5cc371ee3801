"""Per-layer metrics from the program's own spans.

The program records its host work as spans in an in-memory ring
(``repro.runtime.spans``): the ST executor's steps inside each
``synchronize``, the serving engine's step and each request's wait in
its queue, the decode router's dispatch. A reader takes the ring of the
run's own process (:func:`ring`) and hands it, with the run, to one of
the pure functions below. Each reads only the spans that started at or
after the window opened (``run.window_start``, on the same clock), and
gives None where the run is of another kind, where the spans it reads
are not there (a checkout whose program records none), or where the
ring dropped a span that started in the window.

A span is ``(start_ns, end_ns, name, parent_index, key)``, as
``repro.runtime.spans.Snapshot`` holds them. Medians keep the few
seconds a profiler capture perturbs out of the reading; the one mean,
of the queue's wait, counts only requests submitted before the
window's middle, where the serve profiler has not yet opened.
"""
from __future__ import annotations

import statistics
from typing import Dict, Iterable, List, Optional


def ring():
    """The span ring of this process, or None where the program records
    no spans."""
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return spans.snapshot()


def window(run, snap, kind: str) -> Optional[List[int]]:
    """Indices of the finished spans that started at or after
    ``run.window_start`` (``time.monotonic()`` seconds), or None where
    the run is not of ``kind``, there is no ring, or the ring dropped a
    span that may have started in the window. The ring drops the oldest
    first, so a dropped span started before the oldest kept one was
    added: a span is added when it opens, a recorded one when it ends."""
    if snap is None or run.record.get("kind") != kind:
        return None
    t0 = run.window_start * 1e9
    spans = snap.spans
    if snap.dropped and (not spans or (spans[0][1] or spans[0][0]) >= t0):
        return None
    return [i for i, s in enumerate(spans) if s[0] >= t0
            and s[1] is not None]


def children(snap, idx: Iterable[int]) -> Dict[int, List[int]]:
    """The spans among ``idx`` under each of their parents."""
    out: Dict[int, List[int]] = {}
    for i in idx:
        p = snap.spans[i][3]
        if p is not None:
            out.setdefault(p, []).append(i)
    return out


def duration(snap, i: int) -> int:
    s = snap.spans[i]
    return s[1] - s[0]


def named(snap, idx: Iterable[int], name: str,
          parent: Optional[str] = None) -> List[int]:
    """The spans among ``idx`` called ``name``, and nested directly in a
    span called ``parent`` where that is given."""
    sp = snap.spans
    return [i for i in idx if sp[i][2] == name and (
        parent is None
        or (sp[i][3] is not None and sp[sp[i][3]][2] == parent))]


def child_sums(snap, idx: List[int], parent: str,
               names: Iterable[str]) -> List[int]:
    """For each span called ``parent`` among ``idx``, the summed
    duration (ns) of its direct children called one of ``names``."""
    names = set(names)
    kids = children(snap, idx)
    return [sum(duration(snap, k) for k in kids.get(i, ())
                if snap.spans[k][2] in names)
            for i in named(snap, idx, parent)]


def median(values_ns: List[float], per_ns: float) -> Optional[float]:
    """The median of ``values_ns`` in units of ``per_ns`` nanoseconds."""
    if not values_ns:
        return None
    return statistics.median(values_ns) / per_ns


def median_of(run, snap, kind: str, name: str, parent: Optional[str],
              per_ns: float) -> Optional[float]:
    """Median duration of the spans ``name`` directly under ``parent``."""
    idx = window(run, snap, kind)
    if idx is None:
        return None
    return median([duration(snap, i)
                   for i in named(snap, idx, name, parent)], per_ns)


# ---------------------------------------------------------------------------
# the metrics
# ---------------------------------------------------------------------------

def faces_sync_prep_us(run, snap) -> Optional[float]:
    """Median over ``synchronize`` calls of the host's preparation:
    ``st.schedule`` (the queue's key, the scheduled-program lookup) and
    ``st.lookup`` (the executor's key and lookup), in us."""
    idx = window(run, snap, "faces")
    if idx is None:
        return None
    return median(child_sums(snap, idx, "st.synchronize",
                             ("st.schedule", "st.lookup")), 1e3)


def faces_sync_launch_us(run, snap) -> Optional[float]:
    """Median of ``st.launch`` (the jitted call until it returns) inside
    ``synchronize``, in us."""
    return median_of(run, snap, "faces", "st.launch", "st.synchronize", 1e3)


def router_stage_ms(run, snap) -> Optional[float]:
    """Median of ``router.stage``: padding, the payloads' ``device_put``,
    the counter resets, in ms."""
    return median_of(run, snap, "serve", "router.stage", "router.dispatch",
                     1e6)


def router_sync_ms(run, snap) -> Optional[float]:
    """Median of the serve program's ``st.synchronize`` inside
    ``router.dispatch``, in ms."""
    return median_of(run, snap, "serve", "st.synchronize", "router.dispatch",
                     1e6)


def router_readback_ms(run, snap) -> Optional[float]:
    """Median of ``router.readback``: the committed buffers' copies to
    the host, in ms."""
    return median_of(run, snap, "serve", "router.readback",
                     "router.dispatch", 1e6)


def engine_host_ms_per_step(run, snap) -> Optional[float]:
    """Median, over the engine steps that decode and admit nothing (no
    ``engine.prefill``), of ``engine.step`` less its ``engine.ids`` (the
    wait for the decode and the ids' copy) and ``router.dispatch``: the
    engine's own host work of a step, in ms."""
    idx = window(run, snap, "serve")
    if idx is None:
        return None
    sp = snap.spans
    kids = children(snap, idx)
    out = []
    for i in named(snap, idx, "engine.step"):
        mine = kids.get(i, [])
        names = {sp[k][2] for k in mine}
        admitted = any(sp[g][2] == "engine.prefill"
                       for k in mine if sp[k][2] == "engine.admit"
                       for g in kids.get(k, ()))
        if admitted or "engine.decode" not in names:
            continue
        out.append(duration(snap, i) - sum(
            duration(snap, k) for k in mine
            if sp[k][2] in ("engine.ids", "router.dispatch")))
    return median(out, 1e6)


def admission_queue_mean_ms(run, snap) -> Optional[float]:
    """Mean of ``engine.queued`` (submission to dequeue) over the
    requests submitted before the window's middle, in ms. The window
    runs from its open to the end of the last span in it. Most requests
    find a free slot at the next step and wait microseconds; a mean
    holds the few that wait a second or more for a slot, which a median
    would not see."""
    idx = window(run, snap, "serve")
    if not idx:
        return None
    sp = snap.spans
    mid = 0.5 * (run.window_start * 1e9 + max(sp[i][1] for i in idx))
    waits = [duration(snap, i) for i in named(snap, idx, "engine.queued")
             if sp[i][0] < mid]
    return statistics.fmean(waits) / 1e6 if waits else None
