"""95th percentile of the wait in the serving plane's backlog, from the
program's own points: a request's ``backlog.put`` to the ``backlog.get``
of the same id, over the requests put in the window."""
import numpy as np


def read(run):
    lo, hi = run.window
    put, got = {}, {}
    for r in run.records:
        if r.kind != "point":
            continue
        if r.name == "backlog.put" and lo <= r.t0 < hi:
            put[r.attrs["id"]] = r.t0
        elif r.name == "backlog.get":
            got[r.attrs["id"]] = r.t0
    waits = [got[i] - t for i, t in put.items() if i in got]
    return float(np.quantile(waits, 0.95)) * 1e3 if waits else None
