"""Median duration of the ``service.decide`` spans that began in the
window: one compiled call with its inputs stacked and its result
downloaded."""
import numpy as np


def read(run):
    lo, hi = run.window
    d = [r.t1 - r.t0 for r in run.records
         if r.kind == "span" and r.name == "service.decide" and lo <= r.t0 < hi]
    return float(np.median(d)) * 1e3 if d else None
