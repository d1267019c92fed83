"""Queries per compiled decide call over the window: the counters
``decide_queries`` over ``decide_calls``."""


def read(run):
    q0, q1 = run.counters["decide_queries"]
    c0, c1 = run.counters["decide_calls"]
    return (q1 - q0) / (c1 - c0) if c1 > c0 else None
