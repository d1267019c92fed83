"""Share of the window in which no operation ran on the device, from the
profiler trace: 1 minus the union of device op intervals over the window,
in percent."""
from bench import devtrace


def read(run):
    idle = devtrace.idle_share(run.trace) if run.trace else None
    return None if idle is None else 100.0 * idle
