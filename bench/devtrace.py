"""The JAX profiler's trace, reduced to what the per-layer metrics read.

A traced run marks the first ``WINDOW_S`` seconds of its measured window
with a host annotation named ``WINDOW``: the profiler keeps a bounded
number of device events, and at several hundred thousand ops a second a
whole window overflows it. The marked window's bounds are read back from
the trace, so device and host events and the window share the profiler's
clock. Device time is
the union of the events on each TPU plane's ``XLA Ops`` line, averaged
over the chips used; a program's device time is the sum of its events on
the ``XLA Modules`` line. Only events inside the window count, clipped to
it.
"""
from __future__ import annotations

import array
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "bench.window"
WINDOW_S = 5.0
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU"
HOST_PLANE = "/host:CPU"
_OP_KIND = re.compile(r"^%?(.*?)(\.\d+)*$")


def profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host events: TraceMe and ours only
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False       # op names need no HLO in the trace
    return opts


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one profile under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def op_kind(name: str) -> str:
    """``%and_or_fusion.117 = (...) fusion(...)`` -> ``and_or_fusion``."""
    return _OP_KIND.match(name.split(" = ", 1)[0]).group(1)


@dataclasses.dataclass
class Trace:
    """Intervals are (n, 2) arrays of [start, end) seconds on the
    profiler's clock, clipped to the window."""
    window: Tuple[float, float]
    busy: Dict[str, np.ndarray]             # per device: union of ops
    op_s: Dict[str, Dict[str, float]]       # per device: seconds by op kind
    modules: Dict[str, Dict[str, float]]    # per device: seconds by program
    host_names: List[str]                   # host events in the window
    host: np.ndarray                        # and their intervals

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def union(iv: np.ndarray) -> np.ndarray:
    """Disjoint, sorted union of (n, 2) intervals."""
    if not len(iv):
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    last = np.r_[np.flatnonzero(new)[1:] - 1, len(iv) - 1]
    return np.stack([starts, reach[last]], 1)


def _line(line, lo: float, hi: float, key=None):
    """The events of ``line`` inside [lo, hi), clipped to it: (their names,
    or with ``key`` their seconds summed by ``key(name)``; an (n, 2) array
    of their intervals)."""
    names, ends = [], array.array("d")
    per: Dict[str, float] = {}
    kind: Dict[str, str] = {}
    for e in line.events:
        s = e.start_ns * 1e-9
        t = s + e.duration_ns * 1e-9
        if t <= lo or s >= hi:
            continue
        s, t = max(s, lo), min(t, hi)
        ends.append(s)
        ends.append(t)
        if key is None:
            names.append(e.name)
            continue
        k = kind.get(e.name)
        if k is None:
            k = kind[e.name] = key(e.name)
        per[k] = per.get(k, 0.0) + (t - s)
    iv = np.frombuffer(ends, np.float64).reshape(-1, 2)
    return (names if key is None else per), iv


def load(path: str, window_name: str = WINDOW) -> Trace:
    """Read an ``.xplane.pb`` (a file, or a directory that holds one)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    planes = list(ProfileData.from_file(path).planes)
    host_lines = [line for plane in planes if plane.name == HOST_PLANE
                  for line in plane.lines]
    windows = [((e.start_ns) * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
               for line in host_lines for e in line.events
               if e.name == window_name]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {window_name!r} annotation in the "
                           f"trace, found {len(windows)}")
    lo, hi = window = windows[0]
    busy, op_s, modules = {}, {}, {}
    for plane in planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                op_s[plane.name], iv = _line(line, lo, hi, op_kind)
                busy[plane.name] = union(iv)
            elif line.name == MODULES_LINE:
                modules[plane.name], _ = _line(line, lo, hi, str)
    host_names, host = [], []
    for line in host_lines:
        names, iv = _line(line, lo, hi)
        keep = [i for i, n in enumerate(names) if n != window_name]
        host_names.extend(names[i] for i in keep)
        host.append(iv[keep])
    return Trace(window=window, busy=busy, op_s=op_s, modules=modules,
                 host_names=host_names,
                 host=np.concatenate(host) if host else np.zeros((0, 2)))


def busy_s(trace: Trace) -> Optional[float]:
    """Seconds of the window in which an op ran, averaged over the device
    planes; None where the trace holds no device plane."""
    if not trace.busy:
        return None
    per = [float(np.sum(u[:, 1] - u[:, 0])) for u in trace.busy.values()]
    return sum(per) / len(per)


def idle_share(trace: Trace) -> Optional[float]:
    b = busy_s(trace)
    return None if b is None else 1.0 - b / trace.window_s


def module_s(trace: Trace, prefix: str) -> Optional[float]:
    """Device seconds in the window of programs whose name starts with
    ``prefix``, averaged over the device planes; None where none ran."""
    per = [sum(s for n, s in mods.items() if n.startswith(prefix))
           for mods in trace.modules.values()
           if any(n.startswith(prefix) for n in mods)]
    return sum(per) / len(per) if per else None


def _top(per_device: Dict[str, Dict[str, float]], n: int) -> List[List]:
    tot: Dict[str, float] = {}
    for d in per_device.values():
        for k, v in d.items():
            tot[k] = tot.get(k, 0.0) + v
    k = max(len(per_device), 1)
    return [[name, s / k] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """[op kind, device seconds] of the ``n`` kinds of op that took most
    device time in the window, averaged over the device planes."""
    return _top(trace.op_s, n)


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """[what the host was doing, seconds] of the ``n`` longest device
    idle gaps in the window, on the first device plane. A gap is named by
    the host event that overlaps it most; "host: nothing traced" where no
    host event does."""
    if not trace.busy:
        return []
    lo, hi = trace.window
    u = trace.busy[sorted(trace.busy)[0]]
    edges = np.r_[lo, u.ravel(), hi].reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")[:n]]
    out = []
    for s, e in gaps:
        ov = (np.minimum(trace.host[:, 1], e)
              - np.maximum(trace.host[:, 0], s)) if len(trace.host) else []
        best = int(np.argmax(ov)) if len(ov) else -1
        name = (trace.host_names[best] if best >= 0 and ov[best] > 0
                else "host: nothing traced")
        out.append([name, float(e - s)])
    return out
