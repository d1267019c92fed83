"""Cluster layer: trace generation, token pool, PCC cache refinement, and
the trace-driven simulator (repro.cluster)."""
import numpy as np
import pytest

from repro.cluster import (
    ClusterConfig,
    ClusterMetrics,
    ClusterSimulator,
    PCCCache,
    PoolShards,
    Router,
    TokenPool,
)
from repro.core.allocator import AllocationPolicy
from repro.core.arepas import simulate_runtime
from repro.core.dataset import PCC_FRACTIONS
from repro.core.models import NNConfig
from repro.core.pcc import fit_pcc
from repro.core.pipeline import TasqConfig, TasqPipeline
from repro.launch.serve import AllocationFrontend
from repro.serve import AllocationService
from repro.workloads import TraceGenerator, build_corpus


# ------------------------------------------------------------------- traces --
def test_build_corpus_threads_generator_seeds():
    a = build_corpus(10, rng=np.random.default_rng(123))
    b = build_corpus(10, rng=np.random.default_rng(123))
    c = build_corpus(10, rng=np.random.default_rng(124))
    for ja, jb in zip(a, b):
        assert ja.default_tokens == jb.default_tokens
        assert [s.num_tasks for s in ja.stages] == \
            [s.num_tasks for s in jb.stages]
    assert any(ja.default_tokens != jc.default_tokens
               or len(ja.operators) != len(jc.operators)
               for ja, jc in zip(a, c))



def test_trace_reproducible_from_single_seed():
    t1 = TraceGenerator(seed=5, n_unique=16, rate_qps=2.0).generate(300)
    t2 = TraceGenerator(seed=5, n_unique=16, rate_qps=2.0).generate(300)
    a1, a2 = t1.arrays(), t2.arrays()
    for k in a1:
        np.testing.assert_array_equal(a1[k], a2[k])
    for s1, s2 in zip(t1.skylines, t2.skylines):
        np.testing.assert_array_equal(s1, s2)
    t3 = TraceGenerator(seed=6, n_unique=16, rate_qps=2.0).generate(300)
    assert not np.array_equal(a1["job_index"], t3.arrays()["job_index"])


def test_trace_zipf_repeats_are_head_heavy():
    trace = TraceGenerator(seed=1, n_unique=40, rate_qps=2.0).generate(1000)
    counts = np.bincount(trace.arrays()["job_index"], minlength=40)
    uniform = 1000 / 40
    assert counts.max() > 3 * uniform          # a hot head of repeat queries
    assert np.mean(trace.repeat_mask()) > 0.5  # repeat-heavy traffic


def test_trace_tenancy_and_sla_consistent():
    trace = TraceGenerator(seed=2, n_unique=24, n_tenants=5,
                           rate_qps=2.0).generate(500)
    cols = trace.arrays()
    for u in np.unique(cols["job_index"]):
        m = cols["job_index"] == u
        assert len(np.unique(cols["tenant"][m])) == 1   # query owned by tenant
    for t in np.unique(cols["tenant"]):
        m = cols["tenant"] == t
        assert len(np.unique(cols["sla"][m])) == 1      # tenant has one class
    assert np.all(cols["sla"] < len(trace.sla_classes))


def test_trace_arrivals_sorted_and_bursty():
    gen = TraceGenerator(seed=3, n_unique=8, rate_qps=2.0, burst_factor=8.0)
    arr = gen.generate(2000).arrays()["arrival_s"]
    gaps = np.diff(arr)
    assert np.all(gaps >= 0) and arr[0] > 0
    # burst state compresses inter-arrivals: heavier-than-exponential spread
    assert np.std(gaps) > np.mean(gaps)


# --------------------------------------------------------------------- pool --
def test_token_pool_lease_cycle():
    pool = TokenPool(capacity=100, max_leases=8)
    pool.acquire_batch(np.array([1, 2, 3]), np.array([40, 30, 20]),
                       np.array([10.0, 20.0, 30.0]))
    assert pool.free == 10 and pool.n_active == 3
    assert pool.next_expiry() == 10.0
    qids, toks = pool.expire(15.0)
    assert list(qids) == [1] and list(toks) == [40]
    assert pool.free == 50
    qids, _ = pool.expire(100.0)
    assert sorted(qids.tolist()) == [2, 3]
    assert pool.free == 100 and pool.n_active == 0
    with pytest.raises(AssertionError):        # over-commit is a bug
        pool.acquire_batch(np.array([9]), np.array([101]), np.array([1.0]))


def test_pool_shards_cross_shard_expiry_and_resize():
    """The stacked-table kernels: expiry spanning shards in one call, and a
    resize batch that scatters into two shards' tables at once."""
    pool = PoolShards(capacity_per_shard=100, n_shards=3, max_leases=8)
    pool.acquire_batch(0, np.array([1, 2]), np.array([40, 30]),
                       np.array([10.0, 50.0]))
    pool.acquire_batch(2, np.array([3]), np.array([70]), np.array([10.0]))
    assert pool.free.tolist() == [30, 100, 30]
    assert pool.next_expiry() == 10.0
    sh, qids, toks = pool.expire(15.0)
    assert sorted(zip(sh.tolist(), qids.tolist())) == [(0, 1), (2, 3)]
    assert sorted(toks.tolist()) == [40, 70]
    assert pool.free.tolist() == [70, 100, 100]
    # cross-shard resize in one kernel call
    pool.acquire_batch(1, np.array([7]), np.array([50]), np.array([90.0]))
    pool.resize_batch(np.array([0, 1]), np.array([2, 7]),
                      np.array([10, 80]), np.array([60.0, 95.0]))
    assert pool.free.tolist() == [90, 20, 100]
    assert pool.n_active == 2
    with pytest.raises(AssertionError):          # per-shard over-commit
        pool.acquire_batch(1, np.array([9]), np.array([21]),
                           np.array([1.0]))


def test_fused_admission_keeps_a_lease_ending_within_f32_of_now():
    """The simulator's fused admission (impl="jnp") keeps float64 lease end
    times: a lease ending 1e-5 s after ``now`` — the same f32 value — is
    still live, so the next admission takes another slot and the host
    mirror, the device tables and the token count stay in agreement."""
    pool = PoolShards(capacity_per_shard=100, n_shards=1, max_leases=8)
    head = lambda v: np.array([[v] + [0] * 7])
    ends = lambda v: np.array([[v] + [0.0] * 7])
    assert np.float32(1000.00002) == np.float32(1000.00001)
    pool.admit_epoch(0.0, head(7), head(10), ends(1000.00002), impl="jnp")
    pool.expire(1000.00001)
    pool.admit_epoch(1000.00001, head(8), head(5), ends(2000.0), impl="jnp")
    assert pool._query[0, :2].tolist() == [7, 8]
    assert pool._tokens[0].sum() == pool.in_use[0] == 15
    np.testing.assert_array_equal(np.asarray(pool.device_tables[1]),
                                  pool._tokens)
    np.testing.assert_array_equal(np.asarray(pool.device_tables[0]),
                                  pool._end_s)


# ------------------------------------------------------------------- router --
def test_router_seeded_contracts():
    """Seeded twin of the hypothesis sweep (tests/test_router.py), so the
    router's three contracts hold even where hypothesis is absent."""
    keys = np.arange(4000)
    r = Router(8, load_factor=1.25, seed=1)
    np.testing.assert_array_equal(r.home(keys), r.home(keys))
    counts = np.bincount(r.rank(r.assign(keys)), minlength=8)
    assert counts.max() <= int(np.ceil(1.25 * keys.size / 8))
    grown = Router(9, seed=1).home(keys)
    moved = r.home(keys) != grown
    assert np.all(grown[moved] == 8) and 0 < moved.mean() < 0.5
    minus = Router(shard_ids=[0, 1, 2, 3, 4, 5, 6], seed=1).home(keys)
    kept = r.home(keys) != 7
    np.testing.assert_array_equal(r.home(keys)[kept], minus[kept])
    second = r.second(keys)
    assert np.all(second != r.home(keys))


# -------------------------------------------------------------------- cache --
def test_pcc_cache_refinement_matches_scalar_fit():
    trace = TraceGenerator(seed=9, n_unique=4, rate_qps=2.0).generate(4)
    u = 0
    sky = trace.skylines[u]
    job = trace.jobs[u]
    peak = int(sky.max())
    cache = PCCCache()
    assert u not in cache
    smax = len(sky)
    a, b = cache.refine_batch(
        np.array([u]), sky[None, :].astype(np.float32),
        np.array([smax], np.int32), np.array([job.default_tokens]),
        np.array([peak]))
    assert u in cache and len(cache) == 1
    # scalar oracle: same grid, numpy AREPAS, scalar log-log fit
    allocs = np.maximum(1, np.round(np.asarray(
        sorted(PCC_FRACTIONS, reverse=True)) * job.default_tokens)
        ).astype(np.int64)
    rts = np.array([len(sky) if al >= peak else simulate_runtime(sky, al)
                    for al in allocs])
    a_ref, b_ref = fit_pcc(allocs, np.maximum(rts, 1))
    assert a[0] == pytest.approx(min(a_ref, -1e-4), rel=1e-9)
    assert b[0] == pytest.approx(b_ref, rel=1e-9)
    hit, a_l, b_l = cache.lookup(np.array([u, 3]))
    assert hit.tolist() == [True, False]
    assert a_l[0] == a[0] and b_l[0] == b[0]


def _refine_one(cache, key, sky, tokens):
    sky = np.asarray(sky, np.float32)
    return cache.refine_batch(
        np.array([key]), sky[None, :], np.array([len(sky)], np.int32),
        np.array([tokens]), np.array([int(sky.max())]))


def test_pcc_cache_refits_on_drifted_volume():
    """Regression (satellite): a recurring template whose data volume drifts
    must be *refit*, not served from the stale curve — the drifted lookup is
    a miss, the entry is evicted, and the next refine stores the new fit."""
    trace = TraceGenerator(seed=9, n_unique=4, rate_qps=2.0).generate(4)
    sky = trace.skylines[0].astype(np.float32)
    tok = trace.jobs[0].default_tokens
    cache = PCCCache(drift_tol=0.25)
    a0, b0 = _refine_one(cache, 0, sky, tok)
    # same volume: hit, same curve
    hit, a_l, _ = cache.lookup(np.array([0]), areas=np.array([sky.sum()]))
    assert hit.tolist() == [True] and a_l[0] == a0[0]
    # the fresh day of data is 2x the volume: the cached curve is stale
    drifted = np.concatenate([sky, sky]).astype(np.float32)
    hit, _, _ = cache.lookup(np.array([0]),
                             areas=np.array([float(drifted.sum())]))
    assert hit.tolist() == [False]
    assert cache.stats["stale"] == 1 and 0 not in cache
    a1, b1 = _refine_one(cache, 0, drifted, tok)
    assert (a1[0], b1[0]) != (a0[0], b0[0])      # refit, not the stale curve
    hit, a_l, b_l = cache.lookup(np.array([0]),
                                 areas=np.array([float(drifted.sum())]))
    assert hit.tolist() == [True]
    assert a_l[0] == a1[0] and b_l[0] == b1[0]
    # within-tolerance jitter does not thrash the entry
    hit, _, _ = cache.lookup(np.array([0]),
                             areas=np.array([float(drifted.sum()) * 1.1]))
    assert hit.tolist() == [True]


def test_pcc_cache_duplicate_key_divergent_areas():
    """Regression: one lookup batch referencing the same key twice — once
    with a stale area, once fresh — must miss on *both* rows after the
    eviction, never resolve the survivor to a neighboring entry's curve."""
    trace = TraceGenerator(seed=9, n_unique=4, rate_qps=2.0).generate(4)
    cache = PCCCache(drift_tol=0.25)
    for u in (0, 1):
        _refine_one(cache, u, trace.skylines[u], trace.jobs[u].default_tokens)
    area1 = float(trace.skylines[1].sum())
    hit, a_l, _ = cache.lookup(np.array([1, 1]),
                               areas=np.array([area1 * 10, area1]))
    assert hit.tolist() == [False, False]
    assert a_l.tolist() == [0.0, 0.0]
    assert 1 not in cache and 0 in cache


def test_pcc_cache_dense_view_not_rebuilt_on_unchanged_lookups():
    """Regression (satellite): the sorted columnar view must be rebuilt only
    when entries change — the sharded hot path probes K caches every epoch
    and must not re-densify untouched shards."""
    trace = TraceGenerator(seed=9, n_unique=4, rate_qps=2.0).generate(4)
    cache = PCCCache()
    for u in (0, 1):
        _refine_one(cache, u, trace.skylines[u], trace.jobs[u].default_tokens)
    assert cache.stats["dense_rebuilds"] == 0     # nothing looked up yet
    cache.lookup(np.array([0, 1]))
    assert cache.stats["dense_rebuilds"] == 1
    for _ in range(5):                            # steady-state epochs: no
        cache.lookup(np.array([1, 0, 3]))         # mutation, no rebuild
        cache.missing(np.array([2, 3]))
    assert cache.stats["dense_rebuilds"] == 1
    _refine_one(cache, 2, trace.skylines[2], trace.jobs[2].default_tokens)
    cache.lookup(np.array([2]))                   # mutation -> one rebuild
    assert cache.stats["dense_rebuilds"] == 2
    cache.lookup(np.array([2]))
    assert cache.stats["dense_rebuilds"] == 2


def test_pcc_cache_lru_eviction_bound():
    trace = TraceGenerator(seed=9, n_unique=4, rate_qps=2.0).generate(4)
    cache = PCCCache(max_entries=2)
    for u in (0, 1):
        _refine_one(cache, u, trace.skylines[u],
                    trace.jobs[u].default_tokens)
    cache.lookup(np.array([0]))                  # 0 is now fresher than 1
    _refine_one(cache, 2, trace.skylines[2], trace.jobs[2].default_tokens)
    assert len(cache) == 2
    assert 0 in cache and 2 in cache and 1 not in cache
    assert cache.stats["evicted"] == 1
    assert cache.missing(np.array([0, 1, 2])).tolist() == [False, True, False]


# ------------------------------------------------------------------ metrics --
def test_metrics_slack_histogram_and_resize_counters():
    m = ClusterMetrics(capacity=100, sla_limits=np.array([2.0]))
    m.record_completions(
        arrival_s=np.zeros(4), start_s=np.zeros(4),
        finish_s=np.array([10.0, 20.0, 30.0, 40.0]),
        tokens=np.array([5, 5, 5, 5]), default_tokens=np.array([8, 8, 8, 8]),
        runtime_s=np.array([10, 20, 30, 40]),
        ideal_runtime_s=np.array([10, 10, 10, 10]),
        sla=np.zeros(4, np.int64), tenant=np.zeros(4, np.int64),
        cache_hit=np.zeros(4, bool), repeat=np.zeros(4, bool),
        alloc_error=np.zeros(4),
        cost_token_s=np.array([50.0, 100.0, 150.0, 200.0]),
        price=np.array([1.0, 2.0, 3.0, 4.0]),
        slack_s=np.array([-5.0, 5.0, 15.0, np.inf]))
    m.record_resizes(shrunk=3, reclaimed=40)
    m.record_resizes(grown=2, granted=10)
    rep = m.report()
    assert rep["cost_token_s"] == 500.0          # accrued, not tokens*runtime
    assert rep["resize_shrinks"] == 3 and rep["tokens_reclaimed"] == 40
    assert rep["resize_grows"] == 2 and rep["tokens_granted"] == 10
    assert rep["mean_price"] == 2.5
    assert rep["deadline_miss_rate"] == round(1 / 3, 4)       # finite slacks
    edges, counts = m.slack_histogram(bins=4)
    assert counts.sum() == 3                     # inf slack excluded
    assert edges[0] == -5.0 and edges[-1] == 15.0


# ---------------------------------------------------------------- simulator --
@pytest.fixture(scope="module")
def service():
    cfg = TasqConfig(n_train=160, n_eval=40, nn=NNConfig(epochs=8))
    p = TasqPipeline(cfg).build()
    p.train("nn", loss="lf2")
    return AllocationService(p.models["nn:lf2"],
                             AllocationPolicy(max_slowdown=0.05))


@pytest.fixture(scope="module")
def trace():
    return TraceGenerator(seed=33, n_unique=40, rate_qps=1.0).generate(800)


def test_simulator_end_to_end(service, trace):
    calls_before = service.stats["calls"]
    queries_before = service.stats["queries"]
    sim = ClusterSimulator(service, ClusterConfig(capacity=16384))
    rep = sim.run(trace)
    m = rep.metrics
    assert m["n_completed"] + m["n_rejected"] == len(trace)
    assert 0 < m["utilization"] <= 1.0
    assert 1.0 <= m["p50_slowdown"] <= m["p99_slowdown"]
    assert 0 <= m["sla_violation_rate"] <= 1
    assert m["cost_token_s"] > 0 and m["cost_saving_frac"] < 1
    assert rep.events_per_s > 0
    t, err = rep.error_series
    assert t.size == rep.n_epochs == err.size
    # every decision went through the batched service path: far fewer
    # compiled-batch calls than queries (no per-query fallback)
    n_calls = service.stats["calls"] - calls_before
    n_served = service.stats["queries"] - queries_before
    assert n_served >= len(trace)
    assert n_calls < len(trace) / 2


def test_cache_path_beats_cold_model_on_repeats(service, trace):
    assert np.mean(trace.repeat_mask()) > 0.5
    cold = ClusterSimulator(
        service, ClusterConfig(capacity=16384, use_cache=False)).run(trace)
    warm = ClusterSimulator(
        service, ClusterConfig(capacity=16384, use_cache=True)).run(trace)
    assert warm.metrics["cache_hit_rate"] > 0.2
    assert warm.cache_stats["refined"] > 0
    # the paper's distinction under load: repeat queries served from exact
    # history must beat the model's generalization, strictly
    rep_mask = warm.repeats
    err_warm = float(np.mean(warm.alloc_errors[rep_mask]))
    err_cold = float(np.mean(cold.alloc_errors[rep_mask]))
    assert err_cold > 0
    assert err_warm < err_cold
    # within the warm run: cache-hit decisions are exact, model ones are not
    assert warm.metrics["alloc_error_cache"] < warm.metrics["alloc_error_model"]
    assert warm.metrics["alloc_error_cache"] == pytest.approx(0.0, abs=1e-12)
    # online convergence: late-trace decisions beat early-trace decisions
    t, err = warm.error_series
    ok = ~np.isnan(err)
    half = ok.sum() // 2
    early = np.nanmean(err[ok][:half])
    late = np.nanmean(err[ok][half:])
    assert late < early


def test_priority_vs_fifo_admission(service, trace):
    pri = ClusterSimulator(service, ClusterConfig(
        capacity=4096, admission="priority")).run(trace)
    fifo = ClusterSimulator(service, ClusterConfig(
        capacity=4096, admission="fifo")).run(trace)
    for rep in (pri, fifo):
        assert rep.metrics["n_completed"] + rep.metrics["n_rejected"] \
            == len(trace)
        assert rep.metrics["mean_queue_depth"] > 0   # contention present
    # priority admission must favor the urgent class over the batch class
    assert (pri.metrics["mean_wait_s_class0"]
            < pri.metrics["mean_wait_s_class2"])
    # ... and serve the urgent class no worse than plain FIFO does
    assert (pri.metrics["mean_wait_s_class0"]
            <= fifo.metrics["mean_wait_s_class0"])


def test_frontend_wires_into_simulator(service):
    small = TraceGenerator(seed=44, n_unique=12, rate_qps=1.0).generate(120)
    fe = AllocationFrontend(service)
    rep = fe.run_cluster(small, ClusterConfig(capacity=16384))
    assert rep.metrics["n_completed"] == len(small)
    assert "sla_violation_rate" in rep.metrics


def test_edf_elastic_scheduler_end_to_end(service, trace):
    """Tentpole: EDF admission + lease resizing + per-class repricing must
    complete the trace, actually resize leases, price above neutral under
    contention, and cut total token-cost vs. the priority/fixed policy."""
    base = ClusterSimulator(service, ClusterConfig(capacity=4096)).run(trace)
    edf = ClusterSimulator(service, ClusterConfig(
        capacity=4096, admission="edf", elastic=True,
        pricing="elastic")).run(trace)
    for rep in (base, edf):
        assert rep.metrics["n_completed"] + rep.metrics["n_rejected"] \
            == len(trace)
    m = edf.metrics
    assert m["resize_shrinks"] > 0               # the pool was pressured
    assert m["tokens_reclaimed"] > 0
    assert m["mean_price"] > 1.0                 # contention priced in
    assert m["cost_token_s"] < base.metrics["cost_token_s"]
    # slack accounting flows through to the report
    assert "mean_slack_s" in m and "deadline_miss_rate" in m
    for cls in (0, 1, 2):
        assert f"cost_token_s_class{cls}" in m


def test_deterministic_replay_same_seed_same_policy(service):
    """Satellite: same seed + same policy -> identical ClusterMetrics
    series, for the elastic scheduler as well as the fixed baseline."""
    trace = TraceGenerator(seed=55, n_unique=16, rate_qps=1.0).generate(300)
    for cfg in (ClusterConfig(capacity=4096),
                ClusterConfig(capacity=4096, admission="edf", elastic=True,
                              pricing="elastic")):
        r1 = ClusterSimulator(service, cfg).run(trace)
        r2 = ClusterSimulator(service, cfg).run(trace)
        m1, m2 = dict(r1.metrics), dict(r2.metrics)
        assert m1 == m2
        t1, e1 = r1.error_series
        t2, e2 = r2.error_series
        np.testing.assert_array_equal(t1, t2)
        np.testing.assert_array_equal(e1, e2)
        np.testing.assert_array_equal(r1.alloc_errors, r2.alloc_errors)
        np.testing.assert_array_equal(r1.cache_hits, r2.cache_hits)


def test_sharded_k1_reproduces_legacy_single_pool_replay(service):
    """Satellite regression: the sharded simulator at K=1 *is* the legacy
    single-pool path. A default-config replay (the pre-fabric construction)
    must be bitwise-identical in every metric to an explicit K=1 run, and
    the routing knobs must be inert at K=1 — turning them must not perturb
    a single decision, completion, or epoch sample.

    (The same equality was verified against the captured pre-refactor
    ClusterReport on the seeded 10k trace before this refactor landed.)
    """
    trace = TraceGenerator(seed=55, n_unique=16, rate_qps=1.0).generate(400)
    for base_cfg, k1_cfg in (
            (ClusterConfig(capacity=4096),
             ClusterConfig(capacity=4096, n_shards=1, load_factor=2.0,
                           router_vnodes=16, router_seed=9)),
            (ClusterConfig(capacity=4096, admission="edf", elastic=True,
                           pricing="elastic"),
             ClusterConfig(capacity=4096, admission="edf", elastic=True,
                           pricing="elastic", n_shards=1,
                           spill_threshold=0.1))):
        legacy = ClusterSimulator(service, base_cfg).run(trace)
        k1 = ClusterSimulator(service, k1_cfg).run(trace)
        assert dict(legacy.metrics) == dict(k1.metrics)
        np.testing.assert_array_equal(legacy.alloc_errors, k1.alloc_errors)
        np.testing.assert_array_equal(legacy.cache_hits, k1.cache_hits)
        t1, e1 = legacy.error_series
        t2, e2 = k1.error_series
        np.testing.assert_array_equal(t1, t2)
        np.testing.assert_array_equal(e1, e2)
        assert legacy.metrics.get("n_spilled", 0) == 0
        assert "utilization_shard0" not in legacy.metrics  # K=1 report clean


def test_sharded_fabric_replay_end_to_end(service):
    """Tentpole: a K-shard replay must conserve completions, keep cache
    affinity (hit rate within 2 points of single-shard on the same
    Zipf-repeat trace), account spills, and report per-shard columns."""
    trace = TraceGenerator(seed=33, n_unique=40, rate_qps=1.0).generate(800)
    K = 4
    one = ClusterSimulator(service, ClusterConfig(capacity=16384)).run(trace)
    rep = ClusterSimulator(service, ClusterConfig(
        capacity=16384, n_shards=K)).run(trace)
    m = rep.metrics
    assert m["n_completed"] + m["n_rejected"] == len(trace)
    assert abs(m["cache_hit_rate"] - one.metrics["cache_hit_rate"]) <= 0.02
    assert "spill_rate" in m and "shard_imbalance" in m
    for k in range(K):
        assert f"utilization_shard{k}" in m
    # per-shard utilization decomposes fabric utilization (equal shares)
    per_shard = np.array([m[f"utilization_shard{k}"] for k in range(K)])
    assert np.isclose(per_shard.mean(), m["utilization"], atol=2e-3)
    # every decision was computed by a replica, and replicas saw real load
    stats = rep.replica_stats
    assert sum(s["queries"] for s in stats) >= len(trace)
    assert sum(s["queries"] > 0 for s in stats) == K
    # deterministic replay holds for the sharded loop too
    rep2 = ClusterSimulator(service, ClusterConfig(
        capacity=16384, n_shards=K)).run(trace)
    assert dict(rep.metrics) == dict(rep2.metrics)


def test_sharded_decisions_match_single_shard_oracles(service):
    """Fabric decisions on a replay are bitwise the per-shard oracles': the
    cache-hit rows of one epoch batch re-decided by a plain single-shard
    service on the routed partition give identical tokens. (The fused cold
    path has the same guarantee — tests/test_alloc_parity.py and
    test_serve.py cover it at the service level.)"""
    from repro.serve import AllocationService, ShardedAllocationService
    rng = np.random.RandomState(4)
    a = rng.uniform(-2.5, -0.01, 200)
    b = np.exp(rng.uniform(0.0, 8.0, 200))
    obs = rng.randint(1, 7000, 200)
    router = Router(4, seed=2)
    shard_of = router.rank(router.home(rng.randint(0, 500, 200)))
    fabric = ShardedAllocationService(service, n_shards=4)
    got = fabric.allocate_params(shard_of, a, b, observed_tokens=obs)
    for k in range(4):
        m = shard_of == k
        solo = AllocationService(service.model, service.policy)
        want = solo.allocate_params(a[m], b[m], observed_tokens=obs[m])
        np.testing.assert_array_equal(got.tokens[m], want.tokens)


def test_simulator_replays_10k_trace(service):
    """Acceptance: a >=10k-query trace end to end, reporting events/sec."""
    trace = TraceGenerator(seed=7, n_unique=48, rate_qps=2.0).generate(10_000)
    rep = ClusterSimulator(service, ClusterConfig(capacity=32768)).run(trace)
    m = rep.metrics
    assert m["n_completed"] + m["n_rejected"] == 10_000
    assert rep.events_per_s > 0
    for key in ("cost_token_s", "utilization", "p50_slowdown", "p99_slowdown",
                "sla_violation_rate", "mean_queue_depth"):
        assert key in m


# ------------------------------------------------------------ fused kernels --
def test_fused_epoch_path_matches_unfused(service):
    """Tentpole acceptance: the fused epoch path (one cluster_epoch_step
    launch per epoch over the device-resident lease tables, fused
    decision+AREPAS+reprice launches for resize events) is
    decision-identical to the unfused loop for the fixed, edf-elastic and
    K=4 configs — every metric, per-decision series and epoch sample."""
    trace = TraceGenerator(seed=33, n_unique=24, rate_qps=1.0).generate(500)
    for kw in (dict(capacity=2048, epoch_s=8.0),
               dict(capacity=1024, epoch_s=4.0, admission="edf",
                    elastic=True, pricing="elastic"),
               dict(capacity=2048, epoch_s=8.0, n_shards=4)):
        base = ClusterSimulator(service, ClusterConfig(**kw)).run(trace)
        fused = ClusterSimulator(
            service, ClusterConfig(fused=True, **kw)).run(trace)
        assert dict(base.metrics) == dict(fused.metrics), kw
        np.testing.assert_array_equal(base.alloc_errors, fused.alloc_errors)
        np.testing.assert_array_equal(base.cache_hits, fused.cache_hits)
        np.testing.assert_array_equal(base.repeats, fused.repeats)
        assert base.cache_stats == fused.cache_stats
        tb, eb = base.error_series
        tf, ef = fused.error_series
        np.testing.assert_array_equal(tb, tf)
        # epochs with no decisions sample NaN mean error: equal_nan compare
        assert np.array_equal(eb, ef, equal_nan=True), kw


# ------------------------------------------------------- streaming arrivals --
def test_streaming_replay_matches_epoch_loop(service):
    """Serving-plane acceptance: the event-driven arrival path (producer
    thread streaming the trace through a bounded backlog, epoch boundaries
    draining by watermark) is decision-identical to the synchronous epoch
    loop for the fixed, edf-elastic, and K=4 configs — every metric,
    per-decision series, and epoch sample."""
    trace = TraceGenerator(seed=33, n_unique=24, rate_qps=1.0).generate(500)
    for kw in (dict(capacity=2048, epoch_s=8.0),
               dict(capacity=1024, epoch_s=4.0, admission="edf",
                    elastic=True, pricing="elastic"),
               dict(capacity=2048, epoch_s=8.0, n_shards=4)):
        base = ClusterSimulator(service, ClusterConfig(**kw)).run(trace)
        stream = ClusterSimulator(
            service, ClusterConfig(**kw)).run_streaming(trace, backlog=256,
                                                        chunk=32)
        assert dict(base.metrics) == dict(stream.metrics), kw
        assert base.n_epochs == stream.n_epochs, kw
        np.testing.assert_array_equal(base.alloc_errors, stream.alloc_errors)
        np.testing.assert_array_equal(base.cache_hits, stream.cache_hits)
        np.testing.assert_array_equal(base.repeats, stream.repeats)
        assert base.cache_stats == stream.cache_stats
        tb, eb = base.error_series
        ts, es = stream.error_series
        np.testing.assert_array_equal(tb, ts)
        assert np.array_equal(eb, es, equal_nan=True), kw


def test_fused_loop_keeps_pool_state_device_resident(service, monkeypatch):
    """Satellite regression: the fused epoch loop must never re-upload the
    host lease-table mirrors — the whole point of the fusion is that pool
    state lives on device across epochs, with the numpy mirrors updated
    from the kernel's (K,) outputs. The spy flags any ``jnp.asarray`` of a
    live pool's mirror tables during the replay."""
    import jax
    import jax.numpy as jnp
    import repro.cluster.pool as pool_mod

    pools = []
    orig_init = pool_mod.PoolShards.__init__

    def init_spy(self, *a, **k):
        orig_init(self, *a, **k)       # the one-time upload happens here
        pools.append(self)

    monkeypatch.setattr(pool_mod.PoolShards, "__init__", init_spy)
    offenders = []
    orig_asarray = jnp.asarray

    def asarray_spy(x, *a, **k):
        if isinstance(x, np.ndarray):
            for p in pools:
                if x is p._end_s or x is p._tokens:
                    offenders.append(x.shape)
        return orig_asarray(x, *a, **k)

    monkeypatch.setattr(jax.numpy, "asarray", asarray_spy)
    trace = TraceGenerator(seed=44, n_unique=12, rate_qps=1.0).generate(200)
    rep = ClusterSimulator(
        service, ClusterConfig(capacity=2048, fused=True)).run(trace)
    assert rep.metrics["n_completed"] + rep.metrics["n_rejected"] == 200
    assert pools, "the simulator must build its PoolShards"
    assert not offenders, f"pool mirrors re-uploaded: {offenders}"
    # after the replay the resident device tables equal the host mirrors
    p = pools[-1]
    assert isinstance(p._d_end, jax.Array) and isinstance(p._d_tok, jax.Array)
    np.testing.assert_array_equal(np.asarray(p._d_tok), p._tokens)
    np.testing.assert_array_equal(np.asarray(p._d_end), p._end_s)


def test_fused_replay_conserves_and_reports_roofline():
    """The 1M-event replay driver at test size: every event is admitted or
    rejected, every admitted lease completes, one launch per epoch, and
    the roofline row accounts the launches. The buffered stream replays
    deterministically."""
    from repro.cluster import FusedReplay, ReplayConfig
    gen = TraceGenerator(seed=71, n_unique=32, rate_qps=4.0)
    stream = gen.stream(3000, chunk_size=1024).buffer()
    cfg = ReplayConfig(capacity=65536, n_shards=4, max_leases=1024,
                       epoch_s=60.0, queue_block=512)
    rep = FusedReplay(cfg).run(stream)
    assert rep.n_events == 3000
    assert rep.n_admitted + rep.n_rejected == 3000
    assert rep.n_completed == rep.n_admitted
    assert rep.launches == rep.n_epochs
    row = rep.roofline.row()
    assert row["kernel"] == "cluster_epoch_step"
    assert row["launches"] == rep.launches
    assert row["total_gb"] > 0 and rep.events_per_s > 0
    rep2 = FusedReplay(cfg).run(stream)
    assert rep2.n_admitted == rep.n_admitted
    assert rep2.n_epochs == rep.n_epochs
    assert rep2.mean_utilization == rep.mean_utilization


def test_fused_replay_pallas_body_matches_jnp_twin():
    """The replay on the Pallas epoch body (interpreted here; compiled on a
    TPU), whose f32/i32 tables feed the next launch, counts what the
    float64 jnp twin counts: whole-second times are exact in f32."""
    from repro.cluster import FusedReplay, ReplayConfig
    stream = TraceGenerator(seed=71, n_unique=16, rate_qps=2.0).stream(
        600, chunk_size=256).buffer()
    reps = [FusedReplay(ReplayConfig(capacity=8192, n_shards=2,
                                     max_leases=256, epoch_s=60.0,
                                     queue_block=128, impl=impl)).run(stream)
            for impl in ("interpret", "jnp")]
    for f in ("n_admitted", "n_completed", "n_rejected", "n_epochs",
              "launches", "mean_utilization"):
        assert getattr(reps[0], f) == getattr(reps[1], f), f
    assert reps[0].n_completed == reps[0].n_admitted > 0
