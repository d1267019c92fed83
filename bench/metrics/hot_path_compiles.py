"""Executables the service compiled during the window
(``service.stats["compiles"]``); every shape is warmed before it opens."""


def read(run):
    c0, c1 = run.counters["compiles"]
    return c1 - c0
