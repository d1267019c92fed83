"""Median duration of the ``decide.download`` spans that began in the
window: the wait for the device and every output brought to the
host."""
import numpy as np


def read(run):
    lo, hi = run.window
    d = [r.t1 - r.t0 for r in run.records
         if r.kind == "span" and r.name == "decide.download"
         and lo <= r.t0 < hi]
    return float(np.median(d)) * 1e3 if d else None
