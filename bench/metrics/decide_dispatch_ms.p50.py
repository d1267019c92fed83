"""Median duration of the ``decide.dispatch`` spans that began in the
window: padding the batch, moving it to the device and
launching the compiled call."""
import numpy as np


def read(run):
    lo, hi = run.window
    d = [r.t1 - r.t0 for r in run.records
         if r.kind == "span" and r.name == "decide.dispatch"
         and lo <= r.t0 < hi]
    return float(np.median(d)) * 1e3 if d else None
