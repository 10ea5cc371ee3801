"""Program spans (repro.runtime.spans): nesting, parents and self time,
recorded spans, the ring's bound; the spans the ST executor and the
serving engine record; and the same names, nested, in a profiler
capture."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.runtime import spans
from repro.runtime.spans import Recorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _names(snap, idx):
    return [snap.spans[i][2] for i in idx]


def _kids(snap, i):
    return [j for j, s in enumerate(snap.spans) if s[3] == i]


def _since(snap, t0_ns):
    return [i for i, s in enumerate(snap.spans) if s[0] >= t0_ns]


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

def test_nesting_parents_and_keys():
    rec = Recorder()
    with rec.span("a", key=7):
        with rec.span("b"):
            with rec.span("c", key=(1, 2)):
                pass
        with rec.span("d"):
            pass
    with rec.span("e"):
        pass
    snap = rec.snapshot()
    assert [s[2] for s in snap.spans] == ["a", "b", "c", "d", "e"]
    assert [s[3] for s in snap.spans] == [None, 0, 1, 0, None]
    assert [s[4] for s in snap.spans] == [7, None, (1, 2), None, None]
    for s, e, _, p, _ in snap.spans:
        assert s <= e
        if p is not None:
            ps, pe = snap.spans[p][:2]
            assert ps <= s and e <= pe
    assert snap.dropped == 0


def test_self_time_is_the_span_less_its_children():
    rec = Recorder()
    with rec.span("outer"):
        time.sleep(0.01)
        with rec.span("inner"):
            time.sleep(0.02)
        with rec.span("inner"):
            time.sleep(0.02)
    snap = rec.snapshot()
    outer = snap.spans[0][1] - snap.spans[0][0]
    inner = sum(s[1] - s[0] for s in snap.spans[1:])
    assert snap.self_ns(0) == outer - inner
    assert 0.009e9 < snap.self_ns(0) < outer
    assert snap.self_ns(1) == snap.spans[1][1] - snap.spans[1][0]


def test_an_open_span_has_no_end():
    rec = Recorder()
    with rec.span("open"):
        snap = rec.snapshot()
    assert snap.spans[0][1] is None
    assert rec.snapshot().spans[0][1] is not None


def test_a_recorded_span_does_not_nest():
    rec = Recorder()
    with rec.span("step"):
        rec.record("queued", 100, 250, key=1)
        with rec.span("inner"):
            pass
    rec.record("queued", 120, 300, key=2)
    snap = rec.snapshot()
    assert [(s[2], s[3], s[4]) for s in snap.spans] == [
        ("step", None, None), ("queued", None, 1), ("inner", 0, None),
        ("queued", None, 2)]
    assert snap.spans[1][:2] == (100, 250)
    assert snap.spans[3][:2] == (120, 300)
    assert snap.self_ns(0) == (snap.spans[0][1] - snap.spans[0][0]) - (
        snap.spans[2][1] - snap.spans[2][0])


def test_ring_is_bounded_and_counts_what_it_dropped():
    rec = Recorder(capacity=4)
    for k in range(10):
        with rec.span("s", key=k):
            pass
    snap = rec.snapshot()
    assert [s[4] for s in snap.spans] == [6, 7, 8, 9]
    assert snap.dropped == 6


def test_a_dropped_parent_leaves_no_index():
    rec = Recorder(capacity=3)
    with rec.span("parent"):
        for k in range(3):
            with rec.span("child", key=k):
                pass
    snap = rec.snapshot()
    assert [s[2] for s in snap.spans] == ["child"] * 3
    assert all(s[3] is None for s in snap.spans)
    assert snap.dropped == 1


def test_each_thread_nests_in_its_own_spans():
    rec = Recorder()
    inside = threading.Event()
    done = threading.Event()

    def other():
        inside.wait()
        with rec.span("other"):
            pass
        done.set()
    t = threading.Thread(target=other)
    t.start()
    with rec.span("main"):
        inside.set()
        done.wait()
    t.join()
    snap = rec.snapshot()
    assert [(s[2], s[3]) for s in snap.spans] == [("main", None),
                                                  ("other", None)]


def test_module_functions_share_one_recorder():
    t0 = time.monotonic_ns()
    with spans.span("test.outer"):
        with spans.span("test.inner"):
            pass
    spans.record("test.recorded", t0, t0 + 5, key="k")
    snap = spans.snapshot()
    idx = _since(snap, t0)
    assert _names(snap, idx)[-3:] == ["test.outer", "test.inner",
                                      "test.recorded"]
    assert snap.spans[idx[-2]][3] == idx[-3]
    assert snap.spans[idx[-1]][3:] == (None, "k")


# ---------------------------------------------------------------------------
# the ST executor: a Faces synchronize on four virtual CPU devices
# ---------------------------------------------------------------------------

_FACES = r"""
import json, time
import jax
from repro.core import STStream, halo
from repro.launch.mesh import make_mesh
from repro.runtime import spans

mesh = make_mesh((2, 2, 1), ("x", "y", "z"))
stream = STStream(mesh, ("x", "y", "z"))
halo.build_faces_program(stream, (4, 4, 4), 2)
state = stream.allocate()
calls = []
for _ in range(3):
    t0 = time.monotonic_ns()
    state = stream.synchronize(state, mode="st")
    snap = spans.snapshot()
    idx = [i for i, s in enumerate(snap.spans) if s[0] >= t0]
    calls.append([[snap.spans[i][2],
                   None if snap.spans[i][3] is None
                   else snap.spans[snap.spans[i][3]][2],
                   snap.spans[i][0], snap.spans[i][1]] for i in idx])
print(json.dumps({"calls": calls, "devices": jax.device_count()}))
"""


@pytest.fixture(scope="module")
def faces_calls():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"))
    r = subprocess.run([sys.executable, "-c", _FACES], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_faces_synchronize_records_its_steps_in_order(faces_calls):
    assert faces_calls["devices"] == 4
    first, *rest = [[s[:2] for s in call] for call in faces_calls["calls"]]
    top = ["st.synchronize", None]
    assert first == [top, ["st.schedule", "st.synchronize"],
                     ["st.lookup", "st.synchronize"],
                     ["st.compile", "st.synchronize"],
                     ["st.block", "st.synchronize"]]
    for call in rest:
        assert call == [top, ["st.schedule", "st.synchronize"],
                        ["st.lookup", "st.synchronize"],
                        ["st.launch", "st.synchronize"],
                        ["st.block", "st.synchronize"]]


def test_faces_steps_follow_one_another_inside_their_call(faces_calls):
    for (_, _, start, end), *steps in faces_calls["calls"]:
        t = start
        for _, _, s, e in steps:
            assert t <= s <= e <= end
            t = e


# ---------------------------------------------------------------------------
# the serving engine on the st route
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_engine():
    import dataclasses

    import jax
    from repro.configs import get_config
    from repro.models import init_params, model_specs
    from repro.serving import ServingEngine
    from repro.sharding.rules import make_rules

    cfg = dataclasses.replace(
        get_config("granite-3-2b").reduced(), num_layers=2, d_model=64,
        num_heads=2, num_kv_heads=1, d_ff=128, vocab_size=256,
        head_dim=32, grad_accum=1, remat="none")
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    rules = make_rules(cfg, None, None)

    def make():
        return ServingEngine(cfg, params, rules, batch_slots=2, max_len=32,
                             st_mode="st")
    return make


def _serve(make, prompts):
    from repro.serving import Request
    eng = make()
    t0 = time.monotonic_ns()
    reqs = [Request(prompt=np.asarray(p, np.int32), max_new_tokens=3)
            for p in prompts]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return eng, reqs, spans.snapshot(), t0


def test_engine_records_each_request_and_step(tiny_engine):
    eng, reqs, snap, t0 = _serve(tiny_engine, [(1, 2), (3, 4, 5), (6, 7)])
    idx = _since(snap, t0)
    queued = [snap.spans[i] for i in idx if snap.spans[i][2] ==
              "engine.queued"]
    assert sorted(s[4] for s in queued) == sorted(r.req_id for r in reqs)
    for r in reqs:
        s = next(q for q in queued if q[4] == r.req_id)
        assert s[3] is None and s[1] is not None
        assert r.submitted_at <= r.dequeued_at <= r.admitted_at
        assert abs(s[0] / 1e9 - r.submitted_at) < 1e-3
        assert abs(s[1] / 1e9 - r.dequeued_at) < 1e-3
    steps = [i for i in idx if snap.spans[i][2] == "engine.step"]
    assert [snap.spans[i][4] for i in steps] == list(range(eng.steps))
    decoding = [i for i in steps if "engine.decode" in
                _names(snap, _kids(snap, i))]
    assert len(decoding) == eng.decode_steps
    for i in decoding:
        assert _names(snap, _kids(snap, i)) == [
            "engine.admit", "engine.decode", "engine.ids",
            "engine.extract", "router.dispatch", "engine.commit"]
        d = next(k for k in _kids(snap, i)
                 if snap.spans[k][2] == "router.dispatch")
        assert _names(snap, _kids(snap, d)) == [
            "router.stage", "st.synchronize", "router.readback"]
    prefills = [i for i in idx if snap.spans[i][2] == "engine.prefill"]
    assert sum(len(snap.spans[i][4]) for i in prefills) == len(reqs)
    assert all(snap.spans[snap.spans[i][3]][2] == "engine.admit"
               for i in prefills)
    merges = [i for i in idx if snap.spans[i][2] == "engine.cache_merge"]
    assert len(merges) == len(prefills) == eng.prefill_dispatches


def test_engine_queued_holds_the_wait_for_a_slot(tiny_engine):
    """Two slots, three requests: the third waits out whole steps for a
    slot, and its ``engine.queued`` holds them; a request put on the
    queue without ``submit`` has no stamp and records no wait."""
    from repro.serving import Request
    eng, reqs, snap, t0 = _serve(tiny_engine, [(1, 2), (3, 4), (5, 6)])
    idx = _since(snap, t0)
    steps = [snap.spans[i] for i in idx if snap.spans[i][2] ==
             "engine.step"]
    queued = {snap.spans[i][4]: snap.spans[i] for i in idx
              if snap.spans[i][2] == "engine.queued"}

    def whole_steps(q):
        return sum(q[0] <= s[0] and s[1] <= q[1] for s in steps)
    # the first two take their three tokens (the prefill's, then one a
    # decode) in steps 0 and 1; the third is admitted in step 2
    assert [whole_steps(queued[r.req_id]) for r in reqs] == [0, 0, 2]
    assert eng.steps == len(steps)

    eng.queue.append(Request(prompt=np.asarray([7, 8], np.int32),
                             max_new_tokens=1))
    t1 = time.monotonic_ns()
    eng.run_until_drained()
    after = spans.snapshot()
    assert "engine.queued" not in _names(after, _since(after, t1))
    assert eng.completed[-1].dequeued_at is not None


def test_construction_stamps_no_time():
    from repro.serving import Request
    r = Request(prompt=np.asarray([1, 2], np.int32))
    assert r.submitted_at is None and r.dequeued_at is None


# ---------------------------------------------------------------------------
# the profiler sees the same names, nested
# ---------------------------------------------------------------------------

def test_profiler_capture_holds_the_spans_nested(tiny_engine, tmp_path):
    import jax
    from bench import trace as T
    from repro.core import STStream, halo
    from repro.launch.mesh import make_mesh

    stream = STStream(make_mesh((1, 1, 1), ("x", "y", "z")),
                      ("x", "y", "z"))
    halo.build_faces_program(stream, (4, 4, 4), 2)
    state = stream.synchronize(stream.allocate(), mode="st")
    eng = tiny_engine()
    from repro.serving import Request
    eng.submit(Request(prompt=np.asarray([1, 2, 3], np.int32),
                       max_new_tokens=3))
    eng.step()                           # the compile stays out of it
    eng.run_until_drained()
    jax.profiler.start_trace(str(tmp_path))
    try:
        stream.synchronize(state, mode="st")
        eng.submit(Request(prompt=np.asarray([4, 5, 6], np.int32),
                           max_new_tokens=3))
        eng.step()
    finally:
        jax.profiler.stop_trace()
    names = {"st.synchronize", "st.schedule", "st.lookup", "st.launch",
             "st.block", "engine.step", "engine.admit",
             "engine.prefill", "engine.cache_merge", "engine.decode",
             "engine.ids", "engine.extract", "engine.commit",
             "router.dispatch", "router.stage", "router.readback"}
    tr = T.from_xplane(T.newest_xplane(str(tmp_path)),
                       names | {"engine.queued"})
    got = {s[2] for s in tr.spans}
    # a request's wait is added to the ring at dequeue, not annotated
    assert got == names

    def inside(child, parent):
        return any(p[0] <= c[0] and c[1] <= p[1]
                   for c in tr.spans if c[2] == child
                   for p in tr.spans if p[2] == parent)
    for child in ("st.schedule", "st.lookup", "st.launch", "st.block"):
        assert inside(child, "st.synchronize")
    for child in ("engine.prefill", "engine.cache_merge"):
        assert inside(child, "engine.admit")
    for child in ("engine.admit", "engine.decode", "engine.ids",
                  "engine.extract", "router.dispatch", "engine.commit"):
        assert inside(child, "engine.step")
    for child in ("router.stage", "st.synchronize", "router.readback"):
        assert inside(child, "router.dispatch")
