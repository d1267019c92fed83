"""The readers of the program's served-path spans, points and counters,
on runs made up here: records from the program's own tracer on a fake
clock, and a device trace built by hand; and the device reader on a trace
recorded on one TPU v5e with the program's spans in it, a 0.05 s window
of the nn serving cell (``data/serve_nn_spans.xplane.pb.gz``). A run of a
program that records none of them reads nothing, and raises nothing."""
import gzip
import os
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from bench import devtrace, spec

NEW = ["backlog_wait_ms.p95", "plane_self_ms.p50", "decide_dispatch_ms.p50",
       "decide_download_ms.p50", "downloads_per_decide", "gc_pause_ms.sum",
       "idle_unattributed_pct.serve"]
WINDOW = (10.0, 20.0)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _run(records=(), trace=None):
    return SimpleNamespace(records=list(records), window=WINDOW,
                           trace=trace)


def _served(waits, batches):
    """Records of a plane: a put and a get per wait (ids 0...), then one
    batch per (t0, flush, decide parts, gc) entry of ``batches``."""
    from repro.obs import Tracer
    clock = Clock()
    tr = Tracer(clock=clock)
    for i, (t, w) in enumerate(waits):
        clock.t = t
        tr.point("backlog.put", id=i)
        if w is not None:
            clock.t = t + w
            tr.point("backlog.get", id=i)
    for t0, (f0, f1), (d0, d1, d2), gc, t1 in batches:
        clock.t = t0
        with tr.span("plane.batch"):
            clock.t = f0
            with tr.span("microbatch.flush"):
                clock.t = d0
                with tr.span("service.decide"):
                    with tr.span("decide.dispatch"):
                        clock.t = d1
                    with tr.span("decide.download", transfers=4):
                        clock.t = d2
                clock.t = f1
            if gc is not None:
                tr.on_gc("start", {"generation": 0})
                clock.t = gc
                tr.on_gc("stop", {"generation": 0})
            clock.t = t1
    return tr.records()


# (put time, wait): one put before the window, one never taken
WAITS = [(9.5, 0.1), (10.0, 0.002), (11.0, 0.004), (12.0, 0.010),
         (13.0, None), (19.0, 0.001)]
# t0, flush (t0, t1), decide (t0, dispatch end, download end), gc end, t1
BATCHES = [(9.0, (9.1, 9.2), (9.1, 9.15, 9.2), None, 9.3),
           (11.0, (11.002, 11.008), (11.002, 11.003, 11.007), 11.009,
            11.010),
           (12.0, (12.001, 12.005), (12.001, 12.002, 12.004), None, 12.006),
           (13.0, (13.003, 13.004), (13.003, 13.0035, 13.004), 13.0045,
            13.0050)]


def test_backlog_wait_pairs_puts_and_gets_by_id():
    got = spec.reader("backlog_wait_ms.p95")(_run(_served(WAITS, [])))
    want = np.quantile([0.002, 0.004, 0.010, 0.001], 0.95) * 1e3
    assert got == pytest.approx(want, rel=1e-9)


def test_plane_self_time_leaves_out_what_children_cover():
    got = spec.reader("plane_self_ms.p50")(_run(_served([], BATCHES)))
    # 10 ms - 6 (flush) - 1 (gc); 6 - 4; 5 - 1 - 0.5
    assert got == pytest.approx(np.median([3.0, 2.0, 3.5]), rel=1e-6)


@pytest.mark.parametrize("name,want", [
    ("decide_dispatch_ms.p50", np.median([1.0, 1.0, 0.5])),
    ("decide_download_ms.p50", np.median([4.0, 2.0, 0.5])),
    ("downloads_per_decide", 4.0),
    ("gc_pause_ms.sum", 1.0 + 0.5)])
def test_decide_parts_and_collections_in_the_window(name, want):
    got = spec.reader(name)(_run(_served([], BATCHES)))
    assert got == pytest.approx(want, rel=1e-6)


def _trace(host):
    """A window of 10 s with the device busy in [1, 2) and [5, 6), and
    ``host`` events [(name, start, end)]."""
    return devtrace.Trace(
        window=(0.0, 10.0),
        busy={"/device:TPU:0": np.array([[1.0, 2.0], [5.0, 6.0]])},
        op_s={}, modules={}, host_names=[n for n, _, _ in host],
        host=np.array([[s, e] for _, s, e in host]).reshape(-1, 2))


def test_idle_unattributed_counts_idle_time_outside_program_spans():
    trace = _trace([("plane.idle", 0.0, 1.5), ("plane.batch", 1.5, 3.0),
                    ("np.asarray(jax.Array)", 3.0, 4.0),
                    ("decide.download", 4.5, 5.5), ("python.gc", 7.0, 7.5),
                    ("service.decide", 6.0, 6.5)])
    got = spec.reader("idle_unattributed_pct.serve")(_run(trace=trace))
    # idle 8 s; covered by program spans: [0, 1), [2, 3), [4.5, 5),
    # [6, 6.5), [7, 7.5) = 3.5 s, so 4.5 s of the 8 are unexplained
    assert got == pytest.approx(100.0 * 4.5 / 8.0, rel=1e-9)


def _parent_records():
    """Rows as a tracer without span ids writes them."""
    row = dict(t1=11.0, track=0, depth=0, attrs={"id": 1})
    return [SimpleNamespace(kind="point", name="frontend.submit", t0=11.0,
                            **row),
            SimpleNamespace(kind="span", name="service.decide", t0=11.0,
                            **row)]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_reads_nothing(name):
    trace = _trace([("np.asarray(jax.Array)", 3.0, 4.0)])
    assert spec.reader(name)(_run(_parent_records(), trace)) is None
    assert spec.reader(name)(_run()) is None


def test_every_new_metric_moves_the_decision_tail():
    per_layer = {m["name"]: m for m in spec.load_spec()["per_layer"]}
    for name in NEW:
        assert per_layer[name]["moves"] == "decision_p95_ms"
        assert "workloads" not in per_layer[name]


SPANS_TRACE = os.path.join(os.path.dirname(__file__), "data",
                           "serve_nn_spans.xplane.pb.gz")
# what the reduction read off this trace when it was recorded
SPANS_WINDOW_S = 0.049086769000000016
SPANS_BUSY_S = 0.00099279299999952
SPANS_UNATTRIBUTED_PCT = 2.0082348774824257


@pytest.fixture(scope="module")
def spans_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("spans") / "run.xplane.pb"
    with gzip.open(SPANS_TRACE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return devtrace.load(str(path))


def test_the_recorded_trace_holds_the_program_spans(spans_trace):
    assert spans_trace.window_s == pytest.approx(SPANS_WINDOW_S, rel=1e-9)
    assert devtrace.busy_s(spans_trace) == pytest.approx(SPANS_BUSY_S,
                                                         rel=1e-9)
    names = set(spans_trace.host_names)
    assert {"plane.idle", "plane.batch", "microbatch.flush",
            "service.decide", "decide.dispatch", "decide.download"} <= names
    # points stay in the tracer's memory
    assert not names & {"backlog.put", "backlog.get", "frontend.submit"}
    # the longest idle gaps now fall inside the program's own spans
    assert {name for name, _ in devtrace.idle_gaps(spans_trace)} == {
        "plane.batch"}


def test_idle_unattributed_on_the_recorded_trace(spans_trace):
    got = spec.reader("idle_unattributed_pct.serve")(_run(trace=spans_trace))
    assert got == pytest.approx(SPANS_UNATTRIBUTED_PCT, rel=1e-9)
