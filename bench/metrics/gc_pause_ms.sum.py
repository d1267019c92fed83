"""Milliseconds the program spent in Python's garbage collector: the
``python.gc`` spans that began in the window, summed. None where the
program recorded no collection."""


def read(run):
    lo, hi = run.window
    d = [r.t1 - r.t0 for r in run.records
         if r.kind == "span" and r.name == "python.gc" and lo <= r.t0 < hi]
    return sum(d) * 1e3 if d else None
