"""Serving-plane observability: spans, metrics, Perfetto export, provenance.

One ``Obs`` bundle threads through every serving layer (service, batcher,
frontend, router, simulator, fused replay):

  * ``tracer``   — request-lifecycle span tracing with an injectable clock,
    per-thread rings of rows, and every span mirrored into the JAX
    profiler's trace (``obs.trace``);
  * ``metrics``  — counters / gauges / log-bucketed latency histograms
    that merge across the K shards (``obs.metrics``);
  * ``recorder`` — sampled ``AllocationRequest -> AllocationDecision``
    provenance rows to JSONL (``obs.flight``);
  * ``profile_dir`` — optional ``jax.profiler.trace`` capture directory
    for device-side detail (``obs.export.device_profile``).

The plane is *always on*: every seam calls into its ``Obs`` bundle
unconditionally, and ``NULL_OBS`` (the default everywhere) resolves every
call to a shared no-op. What the served path costs on the chip with
tracing off is measured by the chip benchmark and kept in its ledger
(``PERF_LEDGER.jsonl``); a traced replay is decision-identical to an
untraced one (tests/test_obs.py).

The served path (``repro.serve.plane``, ``repro.serve.service``) records
``backlog.put`` / ``backlog.get`` points by request id, ``plane.idle`` and
``plane.batch`` spans, ``frontend.submit`` and ``microbatch.flush`` in the
micro-batcher, ``service.decide`` split into ``decide.dispatch`` and
``decide.download`` (``transfers``), and ``python.gc`` spans while a plane
with a recording tracer runs (``obs.trace`` lists what each measures).

The streaming AOT serving plane adds its own instrument family (all
created dynamically — instruments exist the first time a layer touches
them):

  * ``aot.warmup`` spans (scope=service/fabric/replay) wrap each warmup
    pass, with one ``aot.compile`` point per pinned executable;
  * ``decision_cold_start_s`` histogram — per-executable lower+compile+warm
    cost, plus ``aot_precompiled`` / ``aot_cold_start_s`` stack totals;
  * ``backlog_saturations`` counter on the bounded admission queue
    (``repro.serve.plane.Backlog``) — a saturated plane is visible in
    metrics, not just in producer latency;
  * ``decision_compile_s`` vs ``decision_latency_s`` split is per-thread
    under the plane's workers (``ReplicaState`` compile-stall tracking), so
    the SLO-gated latency series never mixes in another thread's compile.

    from repro.obs import Obs, Tracer, MetricsRegistry, FlightRecorder
    obs = Obs(tracer=Tracer(), metrics=MetricsRegistry(),
              recorder=FlightRecorder("decisions.jsonl", sample_rate=0.1))
    allocator = Allocator.from_config(AllocatorConfig(...), obs=obs)
    ...
    write_trace("trace.json", obs.tracer.records())   # -> ui.perfetto.dev
    obs.metrics.save("metrics.json")
"""
from __future__ import annotations

from typing import Optional

from repro.obs.export import device_profile, fence, trace_events, write_trace
from repro.obs.flight import FlightRecorder
from repro.obs.metrics import (NULL_METRICS, Counter, Gauge, Histogram,
                               MetricsRegistry, NullMetrics)
from repro.obs.trace import NULL_TRACER, NullTracer, Record, Tracer

__all__ = [
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_METRICS",
    "NULL_OBS",
    "NullMetrics",
    "NullTracer",
    "Obs",
    "Record",
    "Tracer",
    "device_profile",
    "fence",
    "trace_events",
    "write_trace",
]


class Obs:
    """The bundle every instrumented layer holds: tracer + metrics +
    flight recorder (+ optional device-profile directory). Omitted pieces
    resolve to their no-op twins, so instrumentation never branches."""

    __slots__ = ("tracer", "metrics", "recorder", "profile_dir")

    def __init__(self, tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 recorder: Optional[FlightRecorder] = None,
                 profile_dir: Optional[str] = None):
        self.tracer = NULL_TRACER if tracer is None else tracer
        self.metrics = NULL_METRICS if metrics is None else metrics
        self.recorder = recorder
        self.profile_dir = profile_dir

    @classmethod
    def enabled(cls, clock=None, capacity: int = 65536,
                recorder: Optional[FlightRecorder] = None,
                profile_dir: Optional[str] = None) -> "Obs":
        """A fully recording bundle (the one-liner for drivers/tests)."""
        import time
        tr = Tracer(clock=clock or time.perf_counter, capacity=capacity)
        return cls(tracer=tr, metrics=MetricsRegistry(), recorder=recorder,
                   profile_dir=profile_dir)

    @property
    def is_null(self) -> bool:
        return (self.tracer is NULL_TRACER and self.metrics is NULL_METRICS
                and self.recorder is None)


NULL_OBS = Obs()
