"""Perfetto / Chrome ``trace_event`` export of the tracer's ring buffer.

``trace_events`` maps ``Record`` rows to the Trace Event JSON format both
the Perfetto UI (ui.perfetto.dev) and ``chrome://tracing`` load natively:

  * spans    -> ``"ph": "X"`` complete events (``ts`` + ``dur`` in µs),
  * points   -> ``"ph": "i"`` instant events,
  * counters -> ``"ph": "C"`` counter samples — one series per key in the
    record's values dict, which is how the fused replay's per-shard pool
    occupancy renders as a per-shard timeline;
  * each used track additionally gets a ``"ph": "M"`` thread_name metadata
    row, so lanes read "shard 3", not "tid 4".

Events are sorted by ``ts`` within each (pid, tid) lane — the monotonicity
the schema test pins and the UI assumes. ``write_trace`` wraps them in the
``{"traceEvents": [...]}`` envelope.

Device-side helpers: ``fence(x)`` is ``jax.block_until_ready`` with the
tree passed back (put a kernel launch's outputs through it *inside* its
span, so the span measures device completion, not dispatch); and
``device_profile(dir)`` optionally nests a ``jax.profiler.trace`` capture
for device-side detail next to the host-side spans.
"""
from __future__ import annotations

import contextlib
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence

from repro.obs.trace import Record

__all__ = ["device_profile", "fence", "trace_events", "write_trace"]

_PH = {"span": "X", "point": "i", "counter": "C"}


def fence(x):
    """Block until every array in ``x`` is device-complete; returns ``x``.
    Wrap kernel outputs inside their span so the span closes at device
    completion (async dispatch would otherwise end it at launch)."""
    import jax
    return jax.block_until_ready(x)


@contextlib.contextmanager
def device_profile(log_dir: Optional[str]):
    """Optionally capture a ``jax.profiler.trace`` alongside the host spans
    (``None`` disables). A profiler that cannot start raises, and so does
    the body: a trace that was asked for is never silently missing."""
    if not log_dir:
        yield
        return
    import jax
    with jax.profiler.trace(log_dir):
        yield


def _json_safe(v):
    try:
        json.dumps(v)
        return v
    except TypeError:
        return str(v)


def trace_events(records: Iterable[Record], pid: int = 0,
                 track_names: Optional[Dict[int, str]] = None,
                 time_offset_s: Optional[float] = None) -> List[Dict]:
    """Trace Event rows from tracer records, ts-sorted within each lane.

    ``ts`` is microseconds relative to the earliest record (or to
    ``time_offset_s``), so traces from fake clocks and perf counters both
    start near zero.
    """
    recs = sorted(records, key=lambda r: (r.track, r.t0, r.t1))
    if not recs:
        return []
    t0 = (min(r.t0 for r in recs) if time_offset_s is None
          else float(time_offset_s))
    us = lambda t: round((t - t0) * 1e6, 3)
    events: List[Dict] = []
    used_tracks = sorted({r.track for r in recs})
    names = track_names or {}
    for track in used_tracks:
        events.append({
            "ph": "M", "name": "thread_name", "pid": pid, "tid": track,
            "ts": 0,
            "args": {"name": names.get(track, f"track {track}")},
        })
    for r in recs:
        if r.kind == "counter":
            events.append({
                "ph": "C", "name": r.name, "pid": pid, "tid": r.track,
                "ts": us(r.t0),
                "args": {k: _json_safe(v) for k, v in r.attrs.items()},
            })
        elif r.kind == "point":
            events.append({
                "ph": "i", "name": r.name, "pid": pid, "tid": r.track,
                "ts": us(r.t0), "s": "t",
                "args": {k: _json_safe(v) for k, v in r.attrs.items()},
            })
        else:
            events.append({
                "ph": "X", "name": r.name, "pid": pid, "tid": r.track,
                "ts": us(r.t0), "dur": max(us(r.t1) - us(r.t0), 0.0),
                "args": {k: _json_safe(v) for k, v in r.attrs.items()},
            })
    return events


def write_trace(path: str, records: Iterable[Record], pid: int = 0,
                track_names: Optional[Dict[int, str]] = None) -> int:
    """Write the Perfetto-loadable envelope; returns the event count."""
    events = trace_events(records, pid=pid, track_names=track_names)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return len(events)
