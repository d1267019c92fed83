"""Arrays brought from the device to the host per compiled decide call:
the ``transfers`` of the ``decide.download`` spans that began in the
window, over their number."""


def read(run):
    lo, hi = run.window
    n = [r.attrs["transfers"] for r in run.records
         if r.kind == "span" and r.name == "decide.download"
         and lo <= r.t0 < hi]
    return sum(n) / len(n) if n else None
