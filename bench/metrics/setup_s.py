"""Seconds from process start to the window opening: the pool of plans,
the model, compiling or loading every executable, one pass of each plan."""


def read(run):
    return run.setup_s
