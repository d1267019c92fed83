"""The recording tracer under threads and the garbage collector: per-thread
nesting and span ids, no row lost or torn when threads record at once,
the ring's drop-oldest under several writers, rows that leave no object
for the collector to walk, lanes let go at ``clear()``, ``python.gc``
spans from the ``gc.callbacks`` hook, and program spans in the JAX
profiler's own trace."""
import gc
import glob
import sys
import threading

import numpy as np
import pytest

from repro.obs import Tracer

N_ROUNDS = 2000


def _nest(tr: Tracer, tag: int, start: threading.Barrier) -> None:
    start.wait(timeout=30)
    for i in range(N_ROUNDS):
        with tr.span("outer", tag=tag, i=i):
            with tr.span("inner", tag=tag, i=i):
                tr.point("mark", tag=tag, i=i)


def _run_threads(target, n: int, *args) -> None:
    start = threading.Barrier(n)
    threads = [threading.Thread(target=target, args=(*args, start))
               for _ in range(n)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)           # switch threads as often as it can
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)


def test_threads_nest_spans_of_their_own_and_lose_no_row():
    tr = Tracer(capacity=1 << 16)
    tags = iter(range(4))
    lock = threading.Lock()

    def work(start):
        with lock:
            tag = next(tags)
        _nest(tr, tag, start)

    _run_threads(work, 4)
    recs = tr.records()
    assert tr.dropped == 0 and len(recs) == 4 * 3 * N_ROUNDS
    by_id = {r.span_id: r for r in recs if r.kind == "span"}
    assert len(by_id) == 4 * 2 * N_ROUNDS          # every span id distinct
    lanes = {}
    for r in recs:
        # no row torn between threads: its attributes are its own thread's
        lanes.setdefault(r.attrs["tag"], set()).add(r.thread)
        assert r.track == r.thread                 # the thread's lane
        if r.name == "outer":
            assert (r.depth, r.parent) == (0, 0)
            continue
        up = by_id[r.parent]
        assert (up.thread, up.attrs) == (r.thread, r.attrs)
        assert up.t0 <= r.t0 and r.t1 <= up.t1
        assert (r.name, up.name, r.depth) in {("inner", "outer", 1),
                                              ("mark", "inner", 2)}
    assert sorted(len(t) for t in lanes.values()) == [1] * 4
    assert len(set.union(*lanes.values())) == 4
    # each thread's rows come out in the order it completed them
    for tag in range(4):
        mine = [(r.name, r.attrs["i"]) for r in recs if r.attrs["tag"] == tag]
        assert mine == [(name, i) for i in range(N_ROUNDS)
                        for name in ("mark", "inner", "outer")]


def test_the_ring_drops_each_threads_oldest_rows():
    tr = Tracer(capacity=8)

    def work(start):
        start.wait(timeout=30)
        name = threading.current_thread().name
        for i in range(20):
            tr.point(name, i=i)

    _run_threads(work, 3)
    recs = tr.records()
    assert tr.dropped == 3 * 12 and len(recs) == 3 * 8
    for name in {r.name for r in recs}:
        assert [r.attrs["i"] for r in recs if r.name == name] == \
            list(range(12, 20))
    tr.clear()
    assert tr.records() == [] and tr.dropped == 0
    tr.point("after")
    assert [r.name for r in tr.records()] == ["after"]


def test_a_span_open_across_clear_is_recorded_when_it_closes():
    tr = Tracer()
    tr.point("before")
    with tr.span("open"):
        tr.clear()
    assert [r.name for r in tr.records()] == ["open"]


def test_recorded_rows_leave_no_object_for_the_collector():
    tr = Tracer(capacity=1 << 16)
    n_rows = 20_000

    def record(n):
        for i in range(n):
            with tr.span("service.decide", B=8, path="model") as sp:
                sp.attrs["compiled"] = i % 2 == 0
            tr.point("backlog.put", id=i)

    record(1)                # this thread's lane, the names, the schemas

    was_enabled = gc.isenabled()
    gc.disable()
    try:
        before = len(gc.get_objects())
        young = gc.get_count()[0]
        record(n_rows // 2)
        # kept objects the collector counts, tracked or not, would bring
        # the next young collection closer by one each
        young = gc.get_count()[0] - young
        after = len(gc.get_objects())
    finally:
        if was_enabled:
            gc.enable()
    assert after <= before
    assert young < 100
    recs = tr.records()
    assert len(recs) == n_rows + 2
    assert recs[-2].attrs == {"B": 8, "path": "model", "compiled": False}
    assert recs[-1].attrs == {"id": n_rows // 2 - 1}


def test_collections_are_spans_on_the_thread_that_ran_them():
    tr = Tracer()
    was_enabled = gc.isenabled()
    gc.disable()                                   # only the two below
    gc.callbacks.append(tr.on_gc)
    try:
        with tr.span("work"):
            gc.collect()

        def other():
            gc.collect(0)

        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        gc.callbacks.remove(tr.on_gc)
        if was_enabled:
            gc.enable()
    recs = tr.records()
    (work,) = [r for r in recs if r.name == "work"]
    gcs = [r for r in recs if r.name == "python.gc"]
    assert [r.attrs["generation"] for r in gcs] == [2, 0]
    full, young = gcs
    assert (full.thread, full.parent, full.depth) == (work.thread,
                                                      work.span_id, 1)
    assert work.t0 <= full.t0 <= full.t1 <= work.t1
    assert young.thread != work.thread and young.parent == 0
    assert full.span_id and young.span_id and full.span_id != young.span_id


def test_program_spans_are_host_events_in_the_profilers_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData
    tr = Tracer()

    def other():
        with tr.span("obs.test.thread"):
            tr.point("obs.test.point")

    with jax.profiler.trace(str(tmp_path)):
        with tr.span("obs.test.outer"):
            with tr.span("obs.test.inner"):
                t = threading.Thread(target=other)
                t.start()
                t.join(timeout=30)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [(e.name, e.start_ns, e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU" for line in plane.lines
            for e in line.events if e.name.startswith("obs.test.")]
    names = sorted(name for name, _, _ in host)
    assert names == ["obs.test.inner", "obs.test.outer", "obs.test.thread"]
    at = {name: (s, s + d) for name, s, d in host}
    assert at["obs.test.outer"][0] <= at["obs.test.inner"][0]
    assert at["obs.test.inner"][1] <= at["obs.test.outer"][1]
    # the same spans are in memory, points too
    assert {r.name for r in tr.records()} == {
        "obs.test.outer", "obs.test.inner", "obs.test.thread",
        "obs.test.point"}


@pytest.mark.parametrize("record", ["span", "point", "sample"])
def test_a_rows_track_can_still_be_given(record):
    tr = Tracer()
    if record == "span":
        with tr.span("x", track=3):
            pass
    else:
        getattr(tr, record)("x", track=3, v=1)
    (r,) = tr.records()
    assert (r.track, r.thread) == (3, 0)


def test_clear_lets_go_of_the_lanes_of_threads_that_have_ended():
    tr = Tracer()
    t = threading.Thread(target=tr.point, args=("gone",))
    t.start()
    t.join(timeout=30)
    tr.point("here")
    assert len(tr._lanes) == 2
    tr.clear()
    assert len(tr._lanes) == 1                 # this thread's, still alive
    tr.point("again")
    assert [r.name for r in tr.records()] == ["again"]


@pytest.mark.parametrize("attrs", [
    {},
    {"id": 7, "ok": True, "ratio": 0.25, "path": "model"},
    {"big": 2 ** 60},
    {"a": 1, "b": 2, "c": 3, "d": 4, "e": 5},
    {"n": np.int64(3)},
    {"none": None, "shards": [1, 2]},
], ids=["none", "scalars", "big_int", "five", "numpy", "objects"])
def test_attributes_come_back_as_recorded(attrs):
    tr = Tracer(capacity=4)
    for i in range(6):               # wraps: kept dicts are overwritten too
        tr.point("p", **attrs)
        with tr.span("s", **attrs) as sp:
            sp.attrs["i"] = i
    recs = tr.records()
    assert [r.attrs for r in recs if r.name == "p"] == [attrs] * 2
    assert [r.attrs for r in recs if r.name == "s"] == [
        dict(attrs, i=i) for i in (4, 5)]
    for r in recs:
        assert [type(v) for v in r.attrs.values()] == [
            type(v) for v in (attrs if r.name == "p"
                              else dict(attrs, i=0)).values()]
