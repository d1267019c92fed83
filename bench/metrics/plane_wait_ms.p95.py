"""95th percentile of the wait in the serving plane's backlog: from the
harness's ``submit`` call to the micro-batcher's ``frontend.submit`` point
for the same request id."""
import numpy as np


def read(run):
    t = {r.attrs["id"]: r.t0 for r in run.records
         if r.kind == "point" and r.name == "frontend.submit"}
    waits = [t[rid] - s for rid, s in zip(run.rid, run.submit_t) if rid in t]
    return float(np.quantile(waits, 0.95)) * 1e3 if waits else None
