"""Decisions answered inside the window, divided by the window."""
import numpy as np


def read(run):
    done = run.answered & (run.done_t <= run.window[1])
    return float(np.sum(done)) / run.seconds
