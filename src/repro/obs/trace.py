"""Span tracer: request-lifecycle timing with near-zero disabled overhead.

The serving plane is instrumented *always* — every seam calls
``obs.tracer.span(...)`` unconditionally — and the cost is decided by which
tracer is installed:

  * ``Tracer`` records spans, instant points and counter samples. Each
    thread records into a lane of its own: its own stack of open spans, so
    nesting (``depth``, and ``parent``, the id of the span open around a
    row) is per thread, and its own ring of rows, held as columns of
    numbers with interned names, so threads never share a write and a
    recorded row keeps no Python object alive: the collector has none
    to walk or count, and the heap does not grow with the rows. A ring
    holds ``capacity`` rows, so the tracer's memory is ``capacity`` rows
    per thread that has recorded since the last ``clear()``.
    ``records()`` builds ``Record`` rows when it is read. The clock is
    injectable, so drivers and tests can run the whole plane on simulated
    time and get deterministic span timings.
  * Every span of a ``Tracer`` also opens a ``jax.profiler.TraceAnnotation``
    of its name on its thread: while a profiler session runs, each program
    span is a host event on the profiler's clock, beside the device ops.
    With no session running it costs next to nothing. Points and counter
    samples stay in memory only.
  * ``Tracer.on_gc`` is a ``gc.callbacks`` hook that records each garbage
    collection as a ``python.gc`` span (attribute ``generation``) on the
    thread that ran it.
  * ``NullTracer`` is the disabled twin: ``span()`` hands back one shared
    context manager whose ``__enter__``/``__exit__`` do nothing and
    allocate nothing — the instrumented hot paths pay one attribute lookup
    and one no-op call. What that costs on the chip is in the benchmark's
    ledger (``PERF_LEDGER.jsonl``), measured with tracing off.

The served path (``repro.serve``) records these names:

  * ``backlog.put`` / ``backlog.get`` points (``id``): a request admitted
    to the plane's backlog, and taken from it by a worker;
  * ``plane.idle`` span: a worker waiting on the backlog;
  * ``plane.batch`` span (``n``): one batch through the plane, from its
    first request in hand to its last future resolved;
  * ``frontend.submit`` point (``id``) and ``microbatch.flush`` span: the
    micro-batcher;
  * ``service.decide`` span, and inside it ``decide.dispatch`` (pad,
    host-to-device, launch) and ``decide.download`` (``transfers``: the
    device wait and the call's one packed output brought to the host);
  * ``python.gc`` spans, while a plane with a recording tracer runs.

Records are plain host-side rows; nothing here changes device state or
the decision kernels, which is what keeps a traced replay
decision-identical to an untraced one (tests/test_obs.py).
"""
from __future__ import annotations

import array
import dataclasses
import itertools
import threading
import time
from typing import Callable, Dict, List, Optional

__all__ = ["NULL_TRACER", "NullTracer", "Record", "Tracer"]

_KINDS = ("span", "point", "counter")
_SPAN, _POINT, _COUNTER = range(3)
GC_SPAN = "python.gc"


@dataclasses.dataclass(slots=True)
class Record:
    """One row: a completed span, an instant point, or a counter sample
    (``kind`` in {"span", "point", "counter"})."""
    kind: str
    name: str
    t0: float                 # clock seconds (span start / event time)
    t1: float                 # span end; == t0 for points and counters
    track: int                # export lane (default: the thread's lane)
    depth: int                # spans open around it on its thread
    attrs: Dict               # span attributes / counter values
    thread: int = 0           # lane of the thread that recorded it
    span_id: int = 0          # a span's own id (0 for points, counters)
    parent: int = 0           # id of the span open around it (0: none)


# Attributes are kept as numbers too: a row's ``schema`` is the interned
# tuple of its (key, type) pairs, and its values fill the first columns
# of ``_VALUES``. A row whose attributes do not fit keeps its dict aside.
_N_VALUES = 4
_VALUES = tuple(f"v{j}" for j in range(_N_VALUES))
_EXACT = 2 ** 53                        # ints a float64 holds exactly
_ASIDE = -1                             # schema of a row kept as a dict

# (column, array typecode)
_COLUMNS = (("seq", "q"), ("kind", "b"), ("name", "i"), ("t0", "d"),
            ("t1", "d"), ("track", "q"), ("depth", "i"), ("thread", "i"),
            ("span_id", "q"), ("parent", "q"), ("schema", "i")) + tuple(
                (v, "d") for v in _VALUES)


class _Ring:
    """A ring of rows held as columns, written by one thread at a time:
    ``size`` slots grown by doubling up to ``capacity``, then the oldest
    row is overwritten. Rows are readable while ``epoch`` is the
    tracer's."""

    __slots__ = ("epoch", "n", "size", "dropped", "aside", "values") + tuple(
        c for c, _ in _COLUMNS)

    def __init__(self, epoch: int, capacity: int):
        self.epoch = epoch
        self.n = 0                            # rows written since reset
        self.dropped = 0
        self.size = min(capacity, 1024)
        for col, code in _COLUMNS:
            setattr(self, col, array.array(code, bytes(
                self.size * array.array(code).itemsize)))
        self.values = [getattr(self, v) for v in _VALUES]
        self.aside: Dict[int, Dict] = {}      # slot -> attributes dict

    def _grow(self, capacity: int) -> None:
        more = min(self.size, capacity - self.size)
        for col, code in _COLUMNS:
            getattr(self, col).frombytes(
                bytes(more * array.array(code).itemsize))
        self.size += more

    def wrap(self, n: int, capacity: int) -> int:
        """The slot of row ``n`` once ``size`` slots are taken: grows, or
        counts the row it overwrites."""
        if self.size < capacity:
            self._grow(capacity)
            return n
        self.dropped += 1
        return n % capacity

    def columns(self, capacity: int) -> List:
        """Every column, and the slots, its rows oldest first."""
        n = self.n
        slots = array.array("q", range(min(n, capacity)))
        cols = [getattr(self, c) for c, _ in _COLUMNS] + [slots]
        if n <= capacity:
            return [col[:n] for col in cols]
        at = n % capacity
        return [col[at:] + col[:at] for col in cols]


class _Lane:
    """One thread's recording state: its index, the thread, its stack of
    open span ids and its ring."""

    __slots__ = ("index", "owner", "stack", "ring")

    def __init__(self, index: int, epoch: int, capacity: int):
        self.index = index
        self.owner = threading.current_thread()
        self.stack: List[int] = []
        self.ring = _Ring(epoch, capacity)


class _SpanCtx:
    """Context manager for one live span; ``__enter__`` returns the
    ``Record`` so callers can attach attributes discovered mid-span."""

    __slots__ = ("_tracer", "_lane", "_rec", "_name", "_note")

    def __init__(self, tracer: "Tracer", lane: _Lane, rec: Record,
                 name: int, note):
        self._tracer = tracer
        self._lane = lane
        self._rec = rec
        self._name = name
        self._note = note

    def __enter__(self) -> Record:
        return self._rec

    def __exit__(self, *exc) -> bool:
        tr = self._tracer
        rec = self._rec
        rec.t1 = tr.clock()
        lane = self._lane
        lane.stack.pop()
        tr._write(lane.ring, _SPAN, self._name, rec.t0, rec.t1, rec.track,
                  rec.depth, lane.index, rec.span_id, rec.parent, rec.attrs)
        self._note.__exit__(None, None, None)
        return False


class _NullCtx:
    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NULL_CTX = _NullCtx()


class Tracer:
    """Recording tracer: a ring of ``capacity`` rows for each thread that
    records (the lanes of threads that have ended are let go at
    ``clear()``), an injectable clock, and program spans mirrored into
    the JAX profiler."""

    enabled = True

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 capacity: int = 65536):
        from jax.profiler import TraceAnnotation
        assert capacity >= 1
        self.clock = clock
        self.capacity = int(capacity)
        self._annotation = TraceAnnotation
        self._epoch = 0                      # bumped by clear()
        self._seq = itertools.count()        # completion order of rows
        self._span_ids = itertools.count(1)
        self._lane_ids = itertools.count()
        self._local = threading.local()
        self._lanes: List[_Lane] = []
        # re-entrant: a collection that starts while a thread holds it
        # records on that thread (``on_gc``)
        self._lanes_lock = threading.RLock()
        self._names: List[str] = []
        self._name_ids: Dict[str, int] = {}
        self._names_lock = threading.Lock()
        self._schemas: List[tuple] = []
        self._schema_ids: Dict[tuple, int] = {}
        # the empty schema is 0; the hook's are interned before any
        # collection, as one that starts while a thread holds the lock
        # must not wait for it
        self._intern((), self._schema_ids, self._schemas)
        self._intern((("generation", int),), self._schema_ids, self._schemas)
        self._gc_name = self._intern(GC_SPAN)
        self._gc_ring = _Ring(0, self.capacity)
        self._gc_open = None                 # the collection under way

    # ------------------------------------------------------------ recording --
    def _intern(self, name: str, ids: Optional[Dict] = None,
                table: Optional[List] = None) -> int:
        ids = self._name_ids if ids is None else ids
        i = ids.get(name)
        if i is None:
            table = self._names if table is None else table
            with self._names_lock:
                i = ids.get(name)
                if i is None:
                    i = len(table)
                    table.append(name)
                    ids[name] = i
        return i

    def _encode(self, attrs: Dict, ring: _Ring, i: int) -> int:
        """Write ``attrs`` into slot ``i``'s value columns; its schema id,
        or ``_ASIDE`` where they do not fit and the dict is kept."""
        if len(attrs) > _N_VALUES:
            ring.aside[i] = attrs
            return _ASIDE
        schema = []
        values = ring.values
        for j, (key, v) in enumerate(attrs.items()):
            cast = type(v)
            if cast is str:
                v = self._intern(v)
            elif cast is int:
                if not -_EXACT <= v <= _EXACT:
                    ring.aside[i] = attrs
                    return _ASIDE
            elif cast is not bool and cast is not float:
                ring.aside[i] = attrs
                return _ASIDE
            values[j][i] = v
            schema.append((key, cast))
        return self._intern(tuple(schema), self._schema_ids, self._schemas)

    def _lane(self) -> _Lane:
        try:
            return self._local.lane
        except AttributeError:
            pass
        with self._lanes_lock:
            lane = _Lane(next(self._lane_ids), self._epoch, self.capacity)
            # a collection run while the lane was built may have made this
            # thread's lane first (``on_gc``); the first one made is kept
            kept = self._local.__dict__.setdefault("lane", lane)
            if kept is lane:
                self._lanes.append(lane)
        return kept

    def _write(self, ring: _Ring, kind: int, name: int, t0: float,
               t1: float, track: int, depth: int, thread: int,
               span_id: int, parent: int, attrs: Optional[Dict]) -> None:
        if ring.epoch != self._epoch:
            ring.__init__(self._epoch, self.capacity)
        i = ring.n
        ring.n = i + 1
        if i >= ring.size:
            i = ring.wrap(i, self.capacity)
        ring.seq[i] = next(self._seq)
        ring.kind[i] = kind
        ring.name[i] = name
        ring.t0[i] = t0
        ring.t1[i] = t1
        ring.track[i] = track
        ring.depth[i] = depth
        ring.thread[i] = thread
        ring.span_id[i] = span_id
        ring.parent[i] = parent
        if ring.aside:
            ring.aside.pop(i, None)
        ring.schema[i] = self._encode(attrs, ring, i) if attrs else 0

    def span(self, name: str, track: Optional[int] = None,
             **attrs) -> _SpanCtx:
        """Open a span; closes (and records) when the ``with`` exits."""
        note = self._annotation(name)
        note.__enter__()
        lane = self._lane()
        stack = lane.stack
        sid = next(self._span_ids)
        rec = Record("span", name, self.clock(), 0.0,
                     lane.index if track is None else track, len(stack),
                     attrs, lane.index, sid, stack[-1] if stack else 0)
        stack.append(sid)
        return _SpanCtx(self, lane, rec, self._intern(name), note)

    def _instant(self, kind: int, name: str, track: Optional[int],
                 attrs: Dict) -> None:
        t = self.clock()
        lane = self._lane()
        stack = lane.stack
        self._write(lane.ring, kind, self._intern(name), t, t,
                    lane.index if track is None else track, len(stack),
                    lane.index, 0, stack[-1] if stack else 0, attrs)

    def point(self, name: str, track: Optional[int] = None,
              **attrs) -> None:
        """Record an instant event (lease grant, expiry, completion...)."""
        self._instant(_POINT, name, track, attrs)

    def sample(self, name: str, track: Optional[int] = None,
               **values) -> None:
        """Record a counter sample (pool occupancy, queue depth...);
        ``values`` become the per-series counter values in the export."""
        self._instant(_COUNTER, name, track, values)

    def on_gc(self, phase: str, info: Dict) -> None:
        """A ``gc.callbacks`` hook: each collection becomes a ``python.gc``
        span on the thread that ran it. Collections never overlap, so one
        slot holds the one under way; its rows go to a ring of their own,
        as a collection can start while its thread is writing a row."""
        if phase == "start":
            note = self._annotation(GC_SPAN)
            note.__enter__()
            lane = self._lane()
            stack = lane.stack
            self._gc_open = (self.clock(), lane, len(stack),
                             stack[-1] if stack else 0, note)
            return
        if self._gc_open is None:            # installed mid-collection
            return
        t0, lane, depth, parent, note = self._gc_open
        self._gc_open = None
        self._write(self._gc_ring, _SPAN, self._gc_name, t0, self.clock(),
                    lane.index, depth, lane.index, next(self._span_ids),
                    parent, {"generation": info["generation"]})
        note.__exit__(None, None, None)

    # ------------------------------------------------------------- reading --
    def _rings(self) -> List[_Ring]:
        rings = [lane.ring for lane in list(self._lanes)] + [self._gc_ring]
        return [r for r in rings if r.epoch == self._epoch]

    @property
    def dropped(self) -> int:
        """Rows the rings overwrote since the last ``clear()``."""
        return sum(r.dropped for r in self._rings())

    def records(self) -> List[Record]:
        """Rows of every thread, in the order they were completed (a span
        when it closed), the oldest first."""
        names, schemas = self._names, self._schemas
        rows = []
        for ring in self._rings():
            for (seq, kind, name, t0, t1, track, depth, thread, span_id,
                 parent, schema, *values, slot) in zip(
                     *ring.columns(self.capacity)):
                if schema == _ASIDE:
                    attrs = ring.aside[slot]
                else:
                    attrs = {key: names[int(v)] if cast is str else cast(v)
                             for (key, cast), v in zip(schemas[schema],
                                                       values)}
                rows.append((seq, Record(
                    _KINDS[kind], names[name], t0, t1, track, depth, attrs,
                    thread, span_id, parent)))
        rows.sort(key=lambda row: row[0])
        return [rec for _, rec in rows]

    def spans(self) -> List[Record]:
        return [r for r in self.records() if r.kind == "span"]

    def clear(self) -> None:
        """Forget every row recorded so far, and the lanes of threads that
        have ended; spans open now are recorded when they close. Each ring
        empties itself at its next write."""
        with self._lanes_lock:
            self._epoch += 1
            self._lanes = [lane for lane in self._lanes
                           if lane.owner.is_alive()]


class NullTracer:
    """The disabled plane: every call is a no-op, nothing allocates."""

    enabled = False
    clock = staticmethod(time.perf_counter)
    dropped = 0

    def span(self, name: str, track: Optional[int] = None,
             **attrs) -> _NullCtx:
        return _NULL_CTX

    def point(self, name: str, track: Optional[int] = None,
              **attrs) -> None:
        pass

    def sample(self, name: str, track: Optional[int] = None,
               **values) -> None:
        pass

    def records(self) -> List[Record]:
        return []

    def spans(self) -> List[Record]:
        return []

    def clear(self) -> None:
        pass


NULL_TRACER = NullTracer()
