"""The readers of the program's spans (bench/program_spans.py and the
seven metrics that use it) on hand-made rings: what each reads, the
window's filter, the dropped-span None, runs of the other kind; and on
a ring recorded on the chip."""
import gzip
import json
import os
import statistics
from types import SimpleNamespace

import pytest

from bench import program_spans as PS
from bench import spec
from repro.runtime.spans import Snapshot

MS = 1_000_000
US = 1_000
T0 = 10.0                                    # window_start, seconds
W = int(T0 * 1e9)                            # the same, in ns

FACES = ("faces_sync_prep_us", "faces_sync_launch_us")
SERVE = ("router_stage_ms", "router_sync_ms", "router_readback_ms",
         "engine_host_ms_per_step", "admission_queue_mean_ms")


class Ring:
    """Builds a snapshot: spans opened in order, each nested in the span
    whose index is given."""

    def __init__(self):
        self.spans = []

    def add(self, name, start, dur, parent=None, key=None):
        self.spans.append((start, start + dur, name, parent, key))
        return len(self.spans) - 1

    def snap(self, dropped=0):
        return Snapshot(list(self.spans), dropped)


def _run(kind):
    return SimpleNamespace(record={"kind": kind}, window_start=T0)


def _sync(r, t, parts, parent=None):
    """One synchronize at ``t``: (schedule, lookup, launch, block) in ns."""
    s = r.add("st.synchronize", t, sum(parts) + 10, parent)
    for name, d in zip(("st.schedule", "st.lookup", "st.launch",
                        "st.block"), parts):
        r.add(name, t, d, s)
        t += d
    return t + 10


def faces_ring():
    r = Ring()
    # before the window opens: a slow first call that must not count
    _sync(r, W - 50 * MS, (900 * US, 4000 * US, 2000 * US, 30 * MS))
    t = W + 1000
    for prep, launch in ((100, 500), (300, 700), (200, 600)):
        t = _sync(r, t, (prep * US // 4, 3 * prep * US // 4, launch * US,
                         30 * MS))
    return r


def test_faces_readers_read_the_calls_in_the_window():
    snap = faces_ring().snap()
    assert PS.faces_sync_prep_us(_run("faces"), snap) == 200.0
    assert PS.faces_sync_launch_us(_run("faces"), snap) == 600.0


def test_faces_readers_refuse_other_runs_and_no_ring():
    snap = faces_ring().snap()
    for f in (PS.faces_sync_prep_us, PS.faces_sync_launch_us):
        assert f(_run("serve"), snap) is None
        assert f(_run("faces"), None) is None
        # a serve ring holds no Faces call at the top
        assert f(_run("faces"), Ring().snap()) is None


def test_a_dropped_span_in_the_window_gives_none():
    r = faces_ring()
    kept = Ring()
    kept.spans = [s for s in r.spans if s[0] >= W]
    # the oldest kept span opened in the window: what the ring dropped
    # may have opened there too
    assert PS.faces_sync_prep_us(_run("faces"), kept.snap(dropped=3)) \
        is None
    # the oldest kept span opened before the window: nothing dropped
    # belongs to it
    assert PS.faces_sync_prep_us(_run("faces"), r.snap(dropped=3)) == 200.0


def test_open_spans_are_left_out():
    r = faces_ring()
    r.spans.append((W + 500 * MS, None, "st.launch", 0, None))
    assert PS.faces_sync_launch_us(_run("faces"), r.snap()) == 600.0


def serve_ring():
    """Four engine steps in the window: one admits (a prefill), one has
    nothing to decode, two decode. One request's wait began before the
    window; of the three submitted in it, two come before its middle."""
    r = Ring()
    r.add("engine.queued", W - 5 * MS, 900 * MS, key=0)
    r.add("engine.queued", W + 1 * MS, 100 * MS, key=1)
    r.add("engine.queued", W + 2 * MS, 300 * MS, key=2)
    r.add("engine.queued", W + 700 * MS, 50 * MS, key=3)

    def step(t, n, admit=False, decode=True, stage=1, sync=4, back=2):
        s = r.add("engine.step", t, 40 * MS, key=n)
        a = r.add("engine.admit", t, 10 * MS if admit else MS // 10, s)
        if admit:
            r.add("engine.prefill", t, 5 * MS, a, key=(1,))
            r.add("engine.cache_merge", t + 5 * MS, MS, a)
        if not decode:
            return
        u = t + 10 * MS
        r.add("engine.decode", u, MS, s)
        r.add("engine.ids", u + MS, 20 * MS, s)
        r.add("engine.extract", u + 21 * MS, MS, s)
        d = r.add("router.dispatch", u + 22 * MS, (stage + sync + back) * MS,
                  s)
        r.add("router.stage", u + 22 * MS, stage * MS, d)
        y = r.add("st.synchronize", u + 22 * MS + stage * MS, sync * MS, d)
        r.add("st.launch", u + 22 * MS + stage * MS, sync * MS, y)
        r.add("router.readback", u + (22 + stage + sync) * MS, back * MS, d)
        r.add("engine.commit", u + (22 + stage + sync + back) * MS, MS, s)

    step(W - 300 * MS, 0, stage=50, sync=50, back=50)    # before the window
    step(W + 100 * MS, 1, admit=True)
    step(W + 200 * MS, 2, stage=2, sync=6, back=4)
    step(W + 300 * MS, 3, decode=False)
    step(W + 400 * MS, 4, stage=3, sync=8, back=6)
    # a synchronize outside any dispatch is not the router's
    r.add("st.synchronize", W + 500 * MS, 90 * MS)
    return r


def test_router_readers_read_the_dispatches_in_the_window():
    snap = serve_ring().snap()
    run = _run("serve")
    assert PS.router_stage_ms(run, snap) == 2.0      # of 1, 2, 3
    assert PS.router_sync_ms(run, snap) == 6.0       # of 4, 6, 8
    assert PS.router_readback_ms(run, snap) == 4.0   # of 2, 4, 6


def test_engine_host_time_skips_admitting_and_idle_steps():
    snap = serve_ring().snap()
    # steps 2 and 4: 40 ms less the ids' 20 and the dispatch's 12 or 17
    assert PS.engine_host_ms_per_step(_run("serve"), snap) == \
        pytest.approx((8 + 3) / 2)


def test_admission_wait_counts_requests_before_the_middle():
    snap = serve_ring().snap()
    # the window runs to the end of its last span (W + 750 ms), so its
    # middle is at W + 375 ms: requests 1 and 2, not 0 (before the
    # window) nor 3 (after the middle)
    assert PS.admission_queue_mean_ms(_run("serve"), snap) == 200.0


def test_admission_wait_is_a_mean_that_sees_the_few_who_wait():
    r = Ring()
    # nine requests find a slot at once, one waits 1.5 s for one
    for k in range(9):
        r.add("engine.queued", W + k * MS, 20 * US, key=k)
    r.add("engine.queued", W + 9 * MS, 1500 * MS, key=9)
    r.add("engine.step", W + 3000 * MS, 40 * MS, key=0)
    got = PS.admission_queue_mean_ms(_run("serve"), r.snap())
    assert got == pytest.approx((9 * 0.02 + 1500) / 10)


def test_a_recorded_wait_first_in_the_ring_bounds_what_was_dropped():
    """``engine.queued`` is added at dequeue, after spans that opened
    later than the wait began: the oldest kept row of the ring may
    start before the window though a span the ring dropped started in
    it."""
    r = Ring()
    r.add("engine.queued", W - 5 * MS, 10 * MS, key=0)     # added at W + 5
    r.add("engine.step", W + 6 * MS, 40 * MS, key=1)
    r.add("engine.queued", W + 7 * MS, 100 * MS, key=1)
    assert PS.admission_queue_mean_ms(_run("serve"), r.snap(dropped=2)) \
        is None
    assert PS.admission_queue_mean_ms(_run("serve"), r.snap()) == 100.0


def test_serve_readers_refuse_other_runs():
    snap = serve_ring().snap()
    for name in SERVE:
        assert getattr(PS, name)(_run("faces"), snap) is None
        assert getattr(PS, name)(_run("serve"), None) is None
        assert getattr(PS, name)(_run("serve"), Ring().snap()) is None


@pytest.mark.parametrize("name", FACES + SERVE)
def test_each_reader_reads_its_process_ring(name, monkeypatch):
    kind = "faces" if name in FACES else "serve"
    snap = (faces_ring() if kind == "faces" else serve_ring()).snap()
    monkeypatch.setattr(PS, "ring", lambda: snap)
    reader = spec.load_reader(name)
    assert reader.read(_run(kind)) == getattr(PS, name)(_run(kind), snap)
    assert reader.read(_run(kind)) is not None
    monkeypatch.setattr(PS, "ring", lambda: None)
    assert reader.read(_run(kind)) is None


def test_every_program_span_metric_is_in_the_benchmark():
    bm = spec.load_benchmark()
    entries = {m["name"]: m for m in bm["per_layer"]}
    for name in FACES + SERVE:
        m = entries[name]
        assert m["source"] == "program_span"
        assert m["workloads"] == (["faces-512.2x2"] if name in FACES
                                  else ["granite-3-2b.chat-st"])


def test_ring_is_none_without_the_program(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_spans(name, *a, **k):
        if name == "repro.runtime" and a and a[2] and "spans" in a[2]:
            raise ImportError("cannot import name 'spans'")
        return real(name, *a, **k)
    monkeypatch.setattr(builtins, "__import__", no_spans)
    assert PS.ring() is None


# ---------------------------------------------------------------------------
# a ring recorded on the chip: the first seconds of a faces-512.2x2 and a
# granite-3-2b.chat-st window on a TPU v5e, with the harness's own clock
# over the whole window of the same run
# ---------------------------------------------------------------------------

CHIP_RING = os.path.join(spec.ROOT, "bench", "testdata", "ring_chip.json.gz")


@pytest.fixture(scope="module")
def chip_rings():
    with gzip.open(CHIP_RING, "rt") as f:
        d = json.load(f)
    out = {}
    for kind, r in d.items():
        spans = [(s, e, n, p, tuple(k) if isinstance(k, list) else k)
                 for s, e, n, p, k in r["spans"]]
        out[kind] = (SimpleNamespace(record={"kind": kind},
                                     window_start=r["window_start"]),
                     Snapshot(spans, 0), r)
    return out


def _plain(run, snap):
    """The chip ring read straight from its rows, with none of the
    readers' helpers: each metric's values in ns, as its docstring
    defines them."""
    t0 = run.window_start * 1e9
    rows = [(i, s) for i, s in enumerate(snap.spans) if s[0] >= t0]
    kids = {}
    for i, s in rows:
        kids.setdefault(s[3], []).append(i)

    def name(i):
        return snap.spans[i][2]

    def dur(i):
        return snap.spans[i][1] - snap.spans[i][0]

    def under(child, parent):
        return [dur(i) for i, s in rows if s[2] == child
                and s[3] is not None and name(s[3]) == parent]
    out = {"faces_sync_prep_us": [
               sum(dur(k) for k in kids.get(i, ())
                   if name(k) in ("st.schedule", "st.lookup"))
               for i, s in rows if s[2] == "st.synchronize"],
           "faces_sync_launch_us": under("st.launch", "st.synchronize"),
           "router_stage_ms": under("router.stage", "router.dispatch"),
           "router_sync_ms": under("st.synchronize", "router.dispatch"),
           "router_readback_ms": under("router.readback",
                                       "router.dispatch"),
           "engine_host_ms_per_step": []}
    for i, s in rows:
        if s[2] != "engine.step":
            continue
        mine = kids.get(i, [])
        grand = [name(g) for k in mine for g in kids.get(k, ())]
        if "engine.decode" in map(name, mine) and \
                "engine.prefill" not in grand:
            out["engine_host_ms_per_step"].append(dur(i) - sum(
                dur(k) for k in mine
                if name(k) in ("engine.ids", "router.dispatch")))
    if rows:
        mid = (t0 + max(s[1] for _, s in rows)) / 2
        out["admission_queue_mean_ms"] = [
            dur(i) for i, s in rows
            if s[2] == "engine.queued" and s[0] < mid]
    return out


@pytest.mark.parametrize("name", FACES + SERVE)
def test_readers_on_the_chip_ring(name, chip_rings):
    kind = "faces" if name in FACES else "serve"
    run, snap, _ = chip_rings[kind]
    values = _plain(run, snap)[name]
    # the serve piece's first half holds four requests' waits
    assert len(values) >= 4
    per_ns = 1e3 if name.endswith("_us") else 1e6
    want = (sum(values) / len(values) if name.endswith("_mean_ms")
            else statistics.median(values)) / per_ns
    v = getattr(PS, name)(run, snap)
    assert v == pytest.approx(want)
    assert v > 0


def test_chip_ring_spans_account_for_their_parents(chip_rings):
    """A Faces call's four steps make up all but a sliver of it, and the
    router's three make up its dispatch."""
    for kind, parent, kids in (
            ("faces", "st.synchronize", ("st.schedule", "st.lookup",
                                         "st.launch", "st.block")),
            ("serve", "router.dispatch", ("router.stage", "st.synchronize",
                                          "router.readback"))):
        run, snap, _ = chip_rings[kind]
        idx = PS.window(run, snap, kind)
        parts = PS.child_sums(snap, idx, parent, kids)
        whole = [PS.duration(snap, i) for i in PS.named(snap, idx, parent)]
        assert len(parts) == len(whole) > 10
        assert 0.95 < sum(parts) / sum(whole) <= 1.0


def test_chip_ring_spans_account_for_the_harness_clock(chip_rings):
    """The spans' means come within 5% of what the harness timed on its
    own clock over the whole window of the same run: a Faces call
    (window time over calls) and a router dispatch (its wrapper's
    ``perf_counter``)."""
    for kind, parent, kids, h in (
            ("faces", "st.synchronize", ("st.schedule", "st.lookup",
                                         "st.launch", "st.block"),
             lambda r: r["window_s"] / r["window_calls"]),
            ("serve", "router.dispatch", ("router.stage", "st.synchronize",
                                          "router.readback"),
             lambda r: r["router_dispatch_s"] / r["router_dispatches"])):
        run, snap, rec = chip_rings[kind]
        parts = PS.child_sums(snap, PS.window(run, snap, kind), parent,
                              kids)
        mean_s = sum(parts) / len(parts) / 1e9
        assert 0.95 < mean_s / h(rec["harness"]) < 1.05


def test_chip_ring_faces_prep_is_under_the_call(chip_rings):
    run, snap, _ = chip_rings["faces"]
    prep = PS.faces_sync_prep_us(run, snap)
    launch = PS.faces_sync_launch_us(run, snap)
    calls = PS.median_of(run, snap, "faces", "st.synchronize", None, 1e3)
    assert prep + launch < calls
