"""Observability plane (repro.obs): span nesting under a fake clock,
shard-merged histogram percentiles, tracing-on/off replay identity,
Perfetto trace_event schema, flight-recorder determinism, and the shared
batcher/tracer timebase.

The identity test is the load-bearing one: the plane is *always on* (every
seam calls into an Obs bundle), so a recording bundle must observe without
perturbing — a seeded replay's ClusterReport has to come out equal whether
the installed tracer records or no-ops.
"""
import gc
import json

import numpy as np
import pytest

from repro.api import AllocationDecision, AllocationRequest
from repro.cluster import ClusterConfig, ClusterSimulator
from repro.core.allocator import AllocationPolicy
from repro.core.models import NNConfig
from repro.core.pipeline import TasqConfig, TasqPipeline
from repro.obs import (NULL_OBS, FlightRecorder, Histogram, MetricsRegistry,
                       Obs, Tracer, trace_events, write_trace)
from repro.serve import MicroBatcher, ServingPlane, WarmupConfig
from repro.serve.aot import model_pool_inputs
from repro.serve.service import AllocationService
from repro.workloads import TraceGenerator


class FakeClock:
    """Injectable deterministic clock (seconds)."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float = 1.0) -> None:
        self.t += dt


# ------------------------------------------------------------ span tracing --
def test_span_nesting_and_order_under_fake_clock():
    clk = FakeClock()
    tr = Tracer(clock=clk, capacity=16)
    with tr.span("outer", phase="a") as outer:
        clk.tick(1.0)
        with tr.span("inner") as inner:
            clk.tick(2.0)
            inner.attrs["found"] = 7          # attach mid-span
        clk.tick(1.0)
    assert (outer.depth, inner.depth) == (0, 1)
    assert (outer.t0, outer.t1) == (0.0, 4.0)
    assert (inner.t0, inner.t1) == (1.0, 3.0)
    assert outer.attrs == {"phase": "a"}
    assert inner.attrs == {"found": 7}
    # records land in completion order: inner closes before outer
    assert [r.name for r in tr.records()] == ["inner", "outer"]
    assert tr.spans() == tr.records()
    assert tr.dropped == 0


def test_ring_buffer_drops_oldest_and_restores_order():
    clk = FakeClock()
    tr = Tracer(clock=clk, capacity=4)
    for i in range(10):
        tr.point(f"p{i}", i=i)
        clk.tick()
    assert tr.dropped == 6
    recs = tr.records()
    assert [r.name for r in recs] == ["p6", "p7", "p8", "p9"]
    assert [r.t0 for r in recs] == [6.0, 7.0, 8.0, 9.0]   # oldest first
    tr.clear()
    assert tr.records() == [] and tr.dropped == 0


# -------------------------------------------------------- histogram merging --
def test_histogram_shard_merge_equals_whole_population():
    """K per-shard histograms merged == the whole population histogrammed
    in one place: same counts, hence *identical* percentiles (the property
    that makes per-shard registries safe to aggregate)."""
    rng = np.random.default_rng(5)
    pop = rng.lognormal(-6.0, 2.0, 20_000)        # ~5 decades of latency
    K = 4
    shards = [MetricsRegistry() for _ in range(K)]
    for reg, part in zip(shards, np.array_split(pop, K)):
        reg.histogram("lat").record_many(part)
        reg.counter("decide_calls").inc(int(part.size))
    whole = Histogram("lat")
    whole.record_many(pop)

    merged = MetricsRegistry()
    for reg in shards:
        merged.merge(reg)
    h = merged.histogram("lat")
    assert np.array_equal(h.counts, whole.counts)
    assert (h.n, h.total, h.vmin, h.vmax) == \
        (whole.n, whole.total, whole.vmin, whole.vmax)
    for q in (50.0, 90.0, 99.0, 99.9):
        assert h.percentile(q) == whole.percentile(q)
    # bucket-edge percentiles are conservative: never below the exact
    # percentile by more than one bucket's relative width (2**0.25)
    for q in (50.0, 99.0):
        exact = float(np.percentile(pop, q))
        assert h.percentile(q) >= exact / 2 ** 0.25
        assert h.percentile(q) <= exact * 2 ** 0.25
    assert merged.counter("decide_calls").value == pop.size
    snap = merged.snapshot()
    assert snap["lat"]["count"] == pop.size
    json.dumps(snap)                               # JSON-ready


def test_histogram_merge_rejects_mismatched_geometry():
    """Regression: merge used to check only bucket *count* and ``lo``, so
    two histograms with the same shape but different edges (different
    ``hi``) merged silently — adding counts bucket-by-bucket across
    *different* value ranges, corrupting every percentile. Any geometry
    mismatch is now a hard error."""
    a = Histogram("lat", lo=1e-3, hi=1e3)
    a.record_many(np.array([0.5, 2.0]))
    same = Histogram("lat", lo=1e-3, hi=1e3)
    same.record(7.0)
    a.merge(same)                                  # identical edges: fine
    assert a.n == 3
    # hi=1048 lands in the same bucket count as hi=1e3 with the same lo, so
    # the pre-fix (size, lo) check merged it silently; lo=1e-2 changes the
    # bucket count outright; hi=1e6 changes it with lo equal
    for bad in (Histogram("lat", lo=1e-3, hi=1048.0),
                Histogram("lat", lo=1e-2, hi=1e3),
                Histogram("lat", lo=1e-3, hi=1e6)):
        bad.record(1.0)
        with pytest.raises(AssertionError):
            a.merge(bad)
    assert a.n == 3                                # rejected merges add nothing


def test_gauge_merge_keeps_peak_and_null_twins_are_inert():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.gauge("queue_depth_peak").set(3.0)
    b.gauge("queue_depth_peak").set(11.0)
    a.merge(b)
    assert a.gauge("queue_depth_peak").value == 11.0
    # the disabled plane: same call surface, nothing recorded
    nm = NULL_OBS.metrics
    nm.counter("x").inc()
    nm.histogram("y").record(1.0)
    assert nm.names() == [] and nm.snapshot() == {}
    assert NULL_OBS.is_null and not NULL_OBS.tracer.enabled
    with NULL_OBS.tracer.span("s") as sp:
        assert sp is None
    assert NULL_OBS.tracer.records() == []


# ------------------------------------------------------- replay identity ----
@pytest.fixture(scope="module")
def service():
    cfg = TasqConfig(n_train=140, n_eval=40, nn=NNConfig(epochs=6))
    p = TasqPipeline(cfg).build()
    p.train("nn", loss="lf2")
    return AllocationService(p.models["nn:lf2"],
                             AllocationPolicy(max_slowdown=0.05))


@pytest.fixture(scope="module")
def trace():
    return TraceGenerator(seed=29, n_unique=32, rate_qps=1.0).generate(500)


def test_traced_replay_is_decision_identical(service, trace, tmp_path):
    """Seeded replay with the full recording plane (tracer + metrics +
    flight recorder) vs the default no-op plane: ClusterReport equal,
    bit for bit — and the recording run actually observed."""
    cfg = ClusterConfig(capacity=8192, n_shards=2, admission="edf",
                        elastic=True, pricing="elastic")
    base = ClusterSimulator(service, cfg).run(trace)
    obs = Obs.enabled(recorder=FlightRecorder(sample_rate=0.25, seed=3))
    traced = ClusterSimulator(service, cfg, obs=obs).run(trace)

    assert dict(base.metrics) == dict(traced.metrics)
    assert base.cache_stats == traced.cache_stats
    assert np.array_equal(base.alloc_errors, traced.alloc_errors,
                          equal_nan=True)
    assert np.array_equal(base.cache_hits, traced.cache_hits)
    bt, be = base.error_series
    tt, te = traced.error_series
    assert np.array_equal(bt, tt)
    assert np.array_equal(be, te, equal_nan=True)

    # ... and the plane saw the whole lifecycle
    names = {r.name for r in obs.tracer.records()}
    assert "router.route" in names and "scheduler.expire" in names
    assert names & {"service.decide", "fabric.decide"}
    assert names & {"scheduler.admit", "cluster_epoch_step"}
    assert obs.metrics.counter("decide_calls").value > 0
    assert obs.metrics.histogram("decision_latency_s").n > 0
    assert obs.metrics.counter("admitted").value > 0
    assert obs.recorder.n_recorded > 0
    for row in obs.recorder.rows()[:5]:
        assert row["provenance"] in ("MODEL", "HISTORY")
        assert row["tokens"] > 0 and row["shard"] in (0, 1)
    # the run's obs was scoped to the run: the service is back on no-op
    assert service.obs is NULL_OBS

    # the recorded run exports as a schema-valid Perfetto trace
    n = write_trace(str(tmp_path / "replay.json"), obs.tracer.records())
    doc = json.loads((tmp_path / "replay.json").read_text())
    assert doc["traceEvents"] and len(doc["traceEvents"]) == n
    _assert_trace_event_schema(doc["traceEvents"])


# ------------------------------------------------------ the served path ----
def _serve(service, trace, obs, n: int):
    """Send ``n`` single-query requests through a two-worker plane over
    ``service``'s model with ``obs`` installed; a full collection runs
    while the plane is up. Returns (gc.callbacks while it ran, after)."""
    svc = AllocationService(service.model, service.policy, obs=obs)
    pool = model_pool_inputs(svc.model, trace.jobs)
    plane = ServingPlane(svc, n_workers=2, max_batch=8, backlog=64)
    plane.start(warm_jobs=trace.jobs,
                warmup=WarmupConfig(buckets=(8,), observed=(True, False)))
    hooks = list(gc.callbacks)
    obs.tracer.clear()
    futs = [plane.submit({k: v[i % v.shape[0]] for k, v in pool.items()},
                         observed_tokens=None if i % 3 else 40 + i)
            for i in range(n)]
    assert all(f.result(timeout=60) >= 1 for f in futs)
    gc.collect()
    plane.stop()
    return hooks, list(gc.callbacks)


def test_the_served_path_records_ids_downloads_and_collections(service,
                                                               trace):
    obs = Obs.enabled()
    before = list(gc.callbacks)
    hooks, after = _serve(service, trace, obs, 40)
    assert len(hooks) == len(before) + 1 and after == before
    recs = obs.tracer.records()
    puts = {r.attrs["id"]: r for r in recs if r.name == "backlog.put"}
    gets = {r.attrs["id"]: r for r in recs if r.name == "backlog.get"}
    assert set(puts) == set(gets) == set(range(40))
    assert all(puts[i].t0 <= gets[i].t0 for i in puts)
    assert {gets[i].thread for i in gets} != {puts[0].thread}
    batches = [r for r in recs if r.name == "plane.batch"]
    assert sum(b.attrs["n"] for b in batches) == 40
    decides = [r for r in recs if r.name == "service.decide"]
    downloads = {r.parent: r for r in recs if r.name == "decide.download"}
    dispatches = {r.parent: r for r in recs if r.name == "decide.dispatch"}
    assert decides and len(downloads) == len(decides)
    by_id = {r.span_id: r for r in recs if r.kind == "span"}
    for d in decides:
        down, disp = downloads[d.span_id], dispatches[d.span_id]
        assert down.attrs == {"transfers": 4}
        assert d.t0 <= disp.t0 <= disp.t1 <= down.t0 <= down.t1 <= d.t1
        assert down.thread == d.thread == by_id[
            by_id[d.parent].parent].thread      # flush, then the batch
        assert by_id[by_id[d.parent].parent].name == "plane.batch"
    assert any(r.name == "plane.idle" for r in recs)
    gcs = [r for r in recs if r.name == "python.gc"]
    assert 2 in {r.attrs["generation"] for r in gcs}


def test_the_null_plane_records_nothing_on_the_served_path(service, trace):
    before = list(gc.callbacks)
    hooks, after = _serve(service, trace, NULL_OBS, 12)
    assert hooks == before and after == before
    assert NULL_OBS.tracer.records() == [] and NULL_OBS.tracer.dropped == 0
    assert NULL_OBS.metrics.names() == []


# --------------------------------------------------------- perfetto export --
def _assert_trace_event_schema(events):
    last_ts = {}
    for e in events:
        assert {"ph", "name", "pid", "tid", "ts"} <= set(e), e
        assert e["ph"] in {"X", "i", "C", "M"}, e
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
        json.dumps(e)                              # every field JSON-safe
        key = (e["pid"], e["tid"])
        assert e["ts"] >= last_ts.get(key, 0), \
            f"ts not monotone within lane {key}"   # per-track monotonicity
        last_ts[key] = e["ts"]


def test_device_profile_raises_when_the_profiler_cannot_start(tmp_path):
    """A trace that was asked for and cannot be taken is an error, not a
    silently missing file: here the profiler is already running."""
    import jax
    from repro.obs import device_profile
    jax.profiler.start_trace(str(tmp_path / "outer"))
    try:
        ran = []
        with pytest.raises(RuntimeError, match="already been started"):
            with device_profile(str(tmp_path / "inner")):
                ran.append(True)
        assert not ran
    finally:
        jax.profiler.stop_trace()


@pytest.mark.parametrize("log_dir", [None, "profile"])
def test_device_profile_propagates_body_exceptions(tmp_path, log_dir):
    from repro.obs import device_profile
    path = None if log_dir is None else str(tmp_path / log_dir)
    with pytest.raises(KeyError, match="boom"):
        with device_profile(path):
            raise KeyError("boom")
    if path is not None:          # the capture was still closed and written
        assert list((tmp_path / log_dir).rglob("*.xplane.pb"))


def test_perfetto_export_schema_and_tracks(tmp_path):
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("epoch", Q=3):
        clk.tick(0.5)
        tr.point("lease.grant", track=1, n=2)
        tr.sample("pool_in_use", track=1, shard0=10, shard1=12)
        clk.tick(0.5)
    path = tmp_path / "trace.json"
    n = write_trace(str(path), tr.records(),
                    track_names={0: "host", 1: "shard 0"})
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert len(events) == n
    _assert_trace_event_schema(events)
    # metadata rows name the lanes
    meta = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
    assert meta == {0: "host", 1: "shard 0"}
    # counters carry one series per sampled key (per-shard lanes)
    (counter,) = [e for e in events if e["ph"] == "C"]
    assert counter["args"] == {"shard0": 10, "shard1": 12}
    # the span's duration is the fake-clock elapsed time, in microseconds
    (span,) = [e for e in events if e["ph"] == "X"]
    assert span["name"] == "epoch" and span["dur"] == pytest.approx(1e6)
    # ts offsets rebase to the earliest record, so fake clocks start at ~0
    assert min(e["ts"] for e in events if e["ph"] != "M") == 0


# --------------------------------------------------------- flight recorder --
def _columnar_pair(n: int):
    rng = np.random.default_rng(11)
    req = AllocationRequest(
        model_in={"features": rng.normal(size=(n, 4))},
        observed_tokens=rng.integers(8, 512, n).astype(np.int64),
        template_id=np.arange(n, dtype=np.int64),
        sla=rng.integers(0, 3, n).astype(np.int64),
        deadline_s=rng.uniform(10, 100, n))
    dec = AllocationDecision(
        tokens=rng.integers(1, 4096, n).astype(np.int64),
        runtime=rng.uniform(0.1, 5.0, n),
        a=np.full(n, -0.7), b=rng.uniform(1, 9, n),
        cost=rng.uniform(1, 100, n), price=np.full(n, 1.4),
        shard=rng.integers(0, 4, n).astype(np.int64),
        provenance=rng.integers(0, 2, n).astype(np.int8))
    return req, dec


def test_flight_recorder_deterministic_sampling_and_jsonl(tmp_path):
    req, dec = _columnar_pair(400)
    path = tmp_path / "decisions.jsonl"
    with FlightRecorder(str(path), sample_rate=0.2, seed=9) as fr:
        kept = fr.record(req, dec, now=12.5)
        kept += fr.record(req, dec)                # second batch, new seqs
    assert fr.n_seen == 800 and fr.n_recorded == kept
    assert 0 < kept < 800                          # actually sampled
    # deterministic: same seed + same offered stream -> same rows
    fr2 = FlightRecorder(sample_rate=0.2, seed=9)
    fr2.record(req, dec, now=12.5)
    fr2.record(req, dec)
    assert fr2.rows() == fr.rows()
    # a different seed samples a different subset
    fr3 = FlightRecorder(sample_rate=0.2, seed=10)
    fr3.record(req, dec, now=12.5)
    fr3.record(req, dec)
    assert [r["seq"] for r in fr3.rows()] != [r["seq"] for r in fr.rows()]
    # JSONL on disk parses back to the in-memory rows, full provenance
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert lines == fr.rows()
    for row in lines:
        assert row["provenance"] in ("MODEL", "HISTORY")
        assert {"seq", "tokens", "runtime_s", "cost_token_s", "price",
                "shard", "a", "b", "observed_tokens", "template_id",
                "sla", "deadline_s", "model_version",
                "drift_score"} <= set(row)
        assert row["model_version"] == 0 and row["drift_score"] == 0.0


def test_flight_recorder_stamps_mlops_provenance():
    """Rows carry the model version + drift score current at record time:
    a hot-swap (version bump) and a drift-monitor stamp are visible on
    every row recorded after them."""
    req, dec = _columnar_pair(40)
    fr = FlightRecorder(sample_rate=1.0)
    fr.record(req, dec)
    fr.model_version = 2                  # what Allocator.swap_model sets
    fr.drift_score = 1.75                 # what DriftMonitor stamps
    fr.record(req, dec)
    rows = fr.rows()
    assert [r["model_version"] for r in rows[:40]] == [0] * 40
    assert [r["model_version"] for r in rows[40:]] == [2] * 40
    assert all(r["drift_score"] == 0.0 for r in rows[:40])
    assert all(r["drift_score"] == 1.75 for r in rows[40:])
    # rate extremes
    all_of_it = FlightRecorder(sample_rate=1.0)
    assert all_of_it.record(req, dec) == 40
    none_of_it = FlightRecorder(sample_rate=0.0)
    assert none_of_it.record(req, dec) == 0


# ------------------------------------------------- shared batcher timebase --
class _EchoService:
    """Stub: echoes each row's feature sum (no model training needed)."""

    def __init__(self):
        self.policy = AllocationPolicy()

    def decide(self, request, context=None):
        feats = request.model_in["features"]
        B = feats.shape[0]
        one = np.ones(B)
        return AllocationDecision(
            tokens=feats.reshape(B, -1).sum(axis=1).astype(np.int64),
            runtime=one, a=one, b=one, cost=one, price=one,
            shard=np.zeros(B, np.int64), provenance=np.zeros(B, np.int8))


def test_microbatcher_shares_the_tracer_clock(tmp_path):
    """Queue timestamps, due() timeouts, queue-wait histograms, and span
    timings all read the tracer's injected clock — one timebase."""
    clk = FakeClock()
    obs = Obs.enabled(clock=clk)
    mb = MicroBatcher(_EchoService(), max_wait_s=5.0, obs=obs)
    mb.submit(AllocationRequest(request_id=0,
                                model_in={"features": np.full(4, 1.0)}))
    clk.tick(2.0)
    mb.submit(AllocationRequest(request_id=1,
                                model_in={"features": np.full(4, 2.0)}))
    clk.tick(1.0)
    assert not mb.due()                  # oldest has waited 3s < 5s
    clk.tick(3.0)
    assert mb.due()                      # 6s >= 5s, on the fake clock
    out = mb.flush()
    assert out == {0: 4, 1: 8}
    # waits measured on the same clock: 6s and 4s exactly
    h = obs.metrics.histogram("queue_wait_s")
    assert h.n == 2 and (h.vmin, h.vmax) == (4.0, 6.0)
    # submit points carry the fake timestamps; the flush span closed at 6s
    pts = [r for r in obs.tracer.records() if r.name == "frontend.submit"]
    assert [(p.t0, p.attrs["id"]) for p in pts] == [(0.0, 0), (2.0, 1)]
    (flush,) = [r for r in obs.tracer.spans()
                if r.name == "microbatch.flush"]
    assert flush.t0 == flush.t1 == 6.0
    assert flush.attrs == {"n": 2, "groups": 1}
