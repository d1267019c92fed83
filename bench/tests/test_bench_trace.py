"""The reduction from a profiler trace to device time, idle share and the
breakdown, pinned on a small trace recorded on one TPU v5e: a 0.05 s
window of the nn serving cell (``data/serve_nn_tiny.xplane.pb.gz``)."""
import gzip
import os
import shutil

import numpy as np
import pytest

from bench import devtrace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "serve_nn_tiny.xplane.pb.gz")
# what the reduction read off this trace when it was recorded; the window
# is the 0.05 s of requests and the lead before the first one falls due,
# and the longest idle gap is that lead, in which the host sleeps
WINDOW_S = 0.100360959
BUSY_S = 0.001183827999999179
FUSED_S = 0.001188006000000047
LONGEST_GAP_S = 0.050697835000000004
LONGEST_GAP_HOST = "host: nothing traced"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "run.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return devtrace.load(str(path))


def test_window_comes_from_the_annotation(trace):
    assert trace.window_s == pytest.approx(WINDOW_S, rel=1e-9)
    assert list(trace.busy) == ["/device:TPU:0"]


def test_busy_and_idle_share(trace):
    assert devtrace.busy_s(trace) == pytest.approx(BUSY_S, rel=1e-9)
    assert devtrace.idle_share(trace) == pytest.approx(
        1 - BUSY_S / WINDOW_S, rel=1e-9)


def test_program_device_time(trace):
    assert devtrace.module_s(trace, "jit_fused") == pytest.approx(
        FUSED_S, rel=1e-9)
    assert devtrace.module_s(trace, "no_such_program") is None


def test_breakdown(trace):
    ops = devtrace.top_ops(trace)
    assert len(ops) == 10 and ops[0][0] == "while"
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = devtrace.idle_gaps(trace)
    assert len(gaps) == 10
    assert sum(s for _, s in gaps) <= trace.window_s - BUSY_S + 1e-12
    assert gaps[0][1] == pytest.approx(LONGEST_GAP_S, rel=1e-9)
    assert gaps[0][0] == LONGEST_GAP_HOST


def test_union_of_intervals():
    iv = np.array([[0.0, 1.0], [0.5, 2.0], [3.0, 4.0], [3.5, 3.6],
                   [5.0, 5.0], [4.0, 4.5]])
    np.testing.assert_array_equal(
        devtrace.union(iv), [[0.0, 2.0], [3.0, 4.5], [5.0, 5.0]])
    assert devtrace.union(np.zeros((0, 2))).shape == (0, 2)


def test_op_kind():
    name = ("%and_or_fusion.117 = (pred[8]{0}, u32[8]{0}) fusion(u32[8]{0} "
            "%get-tuple-element.3060), kind=kLoop")
    assert devtrace.op_kind(name) == "and_or_fusion"
    assert devtrace.op_kind("%while.3 = (s32[]) while(...)") == "while"
    assert devtrace.op_kind("copy-done") == "copy-done"


def test_a_trace_without_the_window_is_refused(tmp_path):
    other = tmp_path / "empty.xplane.pb"
    other.write_bytes(b"")
    with pytest.raises(Exception):
        devtrace.load(str(other))
