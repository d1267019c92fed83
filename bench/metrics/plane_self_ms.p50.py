"""Median self time of the ``plane.batch`` spans begun in the window: a
batch's duration minus the part of it that its child spans cover (the
micro-batcher's flush, a collection), which leaves the plane's own Python
per batch: draining the backlog and resolving the futures."""
import numpy as np

from bench import devtrace


def read(run):
    lo, hi = run.window
    batches = [r for r in run.records if r.kind == "span"
               and r.name == "plane.batch" and lo <= r.t0 < hi]
    if not batches:
        return None
    kids = {b.span_id: [] for b in batches}
    for r in run.records:
        if r.kind == "span" and r.parent in kids:
            kids[r.parent].append((r.t0, r.t1))
    own = []
    for b in batches:
        iv = np.clip(np.asarray(kids[b.span_id], float).reshape(-1, 2),
                     b.t0, b.t1)
        u = devtrace.union(iv)
        own.append(b.t1 - b.t0 - float(np.sum(u[:, 1] - u[:, 0])))
    return float(np.median(own)) * 1e3
