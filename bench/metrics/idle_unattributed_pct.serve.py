"""Share of the device's idle time in the marked window during which no
program span was open on any host thread, in percent: the idle time the
program's spans cannot explain. Program spans are the host events of the
profiler's trace that carry the names below; averaged over the device
planes. None where the trace holds no device plane or no program span."""
import numpy as np

from bench import devtrace

PROGRAM_SPANS = frozenset({
    "plane.idle", "plane.batch", "microbatch.flush", "service.decide",
    "decide.dispatch", "decide.download", "python.gc"})


def _seconds(iv) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0]))


def read(run):
    tr = run.trace
    if tr is None or not tr.busy:
        return None
    spans = tr.host[[i for i, n in enumerate(tr.host_names)
                     if n in PROGRAM_SPANS]]
    if not len(spans):
        return None
    shares = []
    for busy in tr.busy.values():
        idle = tr.window_s - _seconds(busy)
        covered = _seconds(devtrace.union(np.concatenate([busy, spans])))
        shares.append((tr.window_s - covered) / idle)
    return 100.0 * sum(shares) / len(shares)
