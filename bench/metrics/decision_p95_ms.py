"""95th percentile, over every request due in the window, of the time from
when it fell due to when its future resolved; a request never answered
counts as missing the percentile."""
import numpy as np


def read(run):
    lat = np.sort(np.where(run.answered, run.done_t - run.due_abs, np.inf))
    if not lat.size:
        return None
    pos = 0.95 * (lat.size - 1)          # numpy's "linear" quantile
    lo, hi = lat[int(np.floor(pos))], lat[int(np.ceil(pos))]
    if not np.isfinite(hi):
        return None
    return float(lo + (hi - lo) * (pos - np.floor(pos))) * 1e3
