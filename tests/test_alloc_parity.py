"""Numeric parity: the jnp allocation policies must match the numpy oracles
bitwise — the serving hot path may be compiled, but it is not allowed to
make different decisions than the paper's reference policies. The sharded
fabric inherits the same contract: a K-shard ``ShardedAllocationService``
must decide bitwise-identically to K independent single-shard services fed
the routed partitions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import AllocationRequest, DecisionContext, Provenance
from repro.cluster.router import Router
from repro.core.allocator import (
    AllocationPolicy,
    choose_tokens,
    choose_tokens_batch,
    choose_tokens_priced,
    choose_tokens_priced_batch,
    min_tokens_within_slowdown,
    min_tokens_within_slowdown_jnp,
)
from repro.serve import AllocationService, ShardedAllocationService

POLICIES = [
    AllocationPolicy(),                                       # defaults
    AllocationPolicy(min_gain=0.001),
    AllocationPolicy(min_gain=0.1, max_slowdown=0.05),
    AllocationPolicy(max_slowdown=0.05),
    AllocationPolicy(max_slowdown=0.5),
    AllocationPolicy(max_slowdown=0.0),                       # gain-only edge
    AllocationPolicy(min_tokens=4, max_tokens=100,
                     max_slowdown=0.05),
]


def _sweep_params(seed=0, n=200):
    rng = np.random.RandomState(seed)
    # bulk random + hand-picked edges: flat (a=0), barely-monotone, positive
    a = np.concatenate([rng.uniform(-3.0, 0.5, n),
                        [0.0, -1e-4, -1.0, 0.5, -2.9]])
    b = np.concatenate([np.exp(rng.uniform(-1.0, 9.0, n)),
                        [1.0, 100.0, 3.5, 7.0, 1e4]])
    return a, b


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("with_observed", [False, True])
def test_choose_tokens_bitwise_parity(policy, with_observed):
    a, b = _sweep_params()
    obs = (np.random.RandomState(1).randint(1, 7000, a.size)
           if with_observed else None)
    got = choose_tokens_batch(a, b, policy, obs)
    want = np.array([
        choose_tokens(float(ai), float(bi), policy,
                      None if obs is None else int(obs[i]))
        for i, (ai, bi) in enumerate(zip(a, b))])
    np.testing.assert_array_equal(got, want)


def test_choose_tokens_observed_cap_edge():
    """observed_tokens caps the search range, including observed < min_tokens
    and observed == 1."""
    pol = AllocationPolicy(min_tokens=4, max_slowdown=0.05)
    a = np.full(6, -1.5)
    b = np.full(6, 50.0)
    obs = np.array([1, 2, 4, 5, 100, 6287], np.int64)
    got = choose_tokens_batch(a, b, pol, obs)
    want = np.array([choose_tokens(-1.5, 50.0, pol, int(o)) for o in obs])
    np.testing.assert_array_equal(got, want)


def test_choose_tokens_zero_slowdown_is_gain_only():
    """max_slowdown=0 must bypass the bisection entirely (oracle semantics:
    the marginal-gain cut-off alone decides)."""
    pol = AllocationPolicy(max_slowdown=0.0, min_gain=0.01)
    a, b = _sweep_params(seed=3, n=64)
    got = choose_tokens_batch(a, b, pol)
    want = np.array([choose_tokens(float(ai), float(bi), pol)
                     for ai, bi in zip(a, b)])
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------- price-weighted policy --
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("with_observed", [False, True])
def test_choose_tokens_priced_bitwise_parity(policy, with_observed):
    """The price-weighted jnp policy (the scheduler's elastic-repricing hot
    path) must match the scalar numpy oracle bitwise in float64, across the
    same policy grid as the unpriced twin plus a price sweep with edges
    (neutral 1.0, fractional, and heavy-contention prices)."""
    a, b = _sweep_params(seed=7)
    rng = np.random.RandomState(11)
    price = np.concatenate([
        np.exp(rng.uniform(0.0, np.log(32.0), a.size - 4)),
        [1.0, 1.0 + 1e-12, 7.5, 32.0]])
    obs = (np.random.RandomState(13).randint(1, 7000, a.size)
           if with_observed else None)
    got = choose_tokens_priced_batch(a, b, policy, price, obs)
    want = np.array([
        choose_tokens_priced(float(ai), float(bi), policy, float(price[i]),
                             None if obs is None else int(obs[i]))
        for i, (ai, bi) in enumerate(zip(a, b))])
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("with_observed", [False, True])
def test_priced_at_unit_price_equals_unpriced(with_observed):
    """price == 1 must reproduce the unpriced policy exactly — the elastic
    scheduler's neutral price is a bitwise no-op, not an approximation."""
    pol = AllocationPolicy(max_slowdown=0.05)
    a, b = _sweep_params(seed=21)
    obs = (np.random.RandomState(22).randint(1, 7000, a.size)
           if with_observed else None)
    got = choose_tokens_priced_batch(a, b, pol, np.ones(a.size), obs)
    want = choose_tokens_batch(a, b, pol, obs)
    np.testing.assert_array_equal(got, want)


def test_priced_decisions_monotone_in_price():
    """Higher price never buys more tokens (per query, elementwise)."""
    pol = AllocationPolicy(max_slowdown=0.05)
    a, b = _sweep_params(seed=31)
    obs = np.random.RandomState(32).randint(1, 7000, a.size)
    prev = None
    for price in (1.0, 2.0, 4.0, 8.0, 16.0):
        toks = choose_tokens_priced_batch(a, b, pol, np.full(a.size, price),
                                          obs)
        if prev is not None:
            assert np.all(toks <= prev), price
        prev = toks


# ------------------------------------------------------- sharded fabric --
class _PolicyOnlyModel:
    """Stub for policy-only service paths (never applied)."""
    cache_key = "stub#parity"
    supports_jit = True
    scaler = params = None
    family = "stub"


def _routed_partitions(n, n_shards, seed=0):
    rng = np.random.RandomState(seed)
    a = np.concatenate([rng.uniform(-3.0, -1e-4, n), [-1e-4, -1.0, -2.9]])
    b = np.concatenate([np.exp(rng.uniform(-1.0, 9.0, n)), [1.0, 7.0, 1e4]])
    obs = rng.randint(1, 7000, a.size)
    price = np.exp(rng.uniform(0.0, np.log(16.0), a.size))
    router = Router(n_shards, seed=3)
    shard_of = router.rank(router.assign(rng.randint(0, 10_000, a.size)))
    return a, b, obs, price, shard_of


@pytest.mark.parametrize("n_shards", [1, 3, 8])
@pytest.mark.parametrize("with_observed", [False, True])
def test_sharded_service_bitwise_matches_per_shard_oracles(n_shards,
                                                           with_observed):
    """The fabric's one compiled (K, Bp) policy call must decide bitwise
    like K independent single-shard services — and therefore like the
    scalar numpy oracle — on the routed partitions."""
    pol = AllocationPolicy(max_slowdown=0.05)
    a, b, obs, price, shard_of = _routed_partitions(120, n_shards)
    obs_in = obs if with_observed else None
    fabric = ShardedAllocationService(
        AllocationService(_PolicyOnlyModel(), pol), n_shards=n_shards)
    got = fabric.allocate_params(shard_of, a, b, observed_tokens=obs_in)
    got_priced = fabric.allocate_params_priced(shard_of, a, b, price,
                                               observed_tokens=obs_in)
    for k in range(n_shards):
        m = shard_of == k
        solo = AllocationService(_PolicyOnlyModel(), pol)
        want = solo.allocate_params(a[m], b[m],
                                    None if obs_in is None else obs_in[m])
        np.testing.assert_array_equal(got.tokens[m], want.tokens)
        np.testing.assert_array_equal(got.runtime[m], want.runtime)
        want_p = solo.allocate_params_priced(
            a[m], b[m], price[m], None if obs_in is None else obs_in[m])
        np.testing.assert_array_equal(got_priced.tokens[m], want_p.tokens)
    # ... and the single-shard services themselves are oracle-parity, so
    # the fabric is transitively bitwise-equal to the scalar policy
    want_np = choose_tokens_batch(a, b, pol, obs_in)
    np.testing.assert_array_equal(got.tokens, want_np)


def test_sharded_service_empty_and_lopsided_shards():
    """Shards with zero rows must not perturb the loaded shards, and the
    block bucket follows the fullest shard."""
    pol = AllocationPolicy(max_slowdown=0.05)
    a, b, obs, _, _ = _routed_partitions(64, 1, seed=5)
    shard_of = np.zeros(a.size, np.int64)       # everything on shard 0 of 4
    fabric = ShardedAllocationService(
        AllocationService(_PolicyOnlyModel(), pol), n_shards=4)
    got = fabric.allocate_params(shard_of, a, b, observed_tokens=obs)
    np.testing.assert_array_equal(got.tokens, choose_tokens_batch(a, b, pol,
                                                                  obs))
    stats = fabric.replica_stats()
    assert stats[0]["queries"] == a.size
    assert all(s["queries"] == 0 for s in stats[1:])


# ------------------------------------------------- typed decide() protocol --
@pytest.mark.parametrize("sharded", [False, True])
@pytest.mark.parametrize("with_price", [False, True])
@pytest.mark.parametrize("with_observed", [False, True])
def test_decide_protocol_matches_oracle_grid(sharded, with_price,
                                             with_observed):
    """Acceptance: the one typed entry point —
    ``decide(AllocationRequest, DecisionContext)`` — reproduces the scalar
    numpy oracles bitwise across the full policy x price x shard x observed
    grid that used to be eight separate methods."""
    for pol in (AllocationPolicy(max_slowdown=0.05),
                AllocationPolicy(),
                AllocationPolicy(min_gain=0.1, max_slowdown=0.05)):
        a, b, obs, price, shard_of = _routed_partitions(
            80, 3 if sharded else 1, seed=17)
        obs_in = obs if with_observed else None
        price_in = price if with_price else None
        req = AllocationRequest(a=a, b=b, observed_tokens=obs_in)
        if sharded:
            engine = ShardedAllocationService(
                AllocationService(_PolicyOnlyModel(), pol), n_shards=3)
            got = engine.decide(req, DecisionContext(price=price_in,
                                                     shard_of=shard_of))
            np.testing.assert_array_equal(got.shard, shard_of)
        else:
            engine = AllocationService(_PolicyOnlyModel(), pol)
            got = engine.decide(req, DecisionContext(price=price_in))
            assert np.all(got.shard == 0)
        want = (choose_tokens_priced_batch(a, b, pol, price, obs_in)
                if with_price else choose_tokens_batch(a, b, pol, obs_in))
        np.testing.assert_array_equal(got.tokens, want)
        # decision metadata is consistent with the inputs
        np.testing.assert_array_equal(
            got.price, price if with_price else np.ones(a.size))
        np.testing.assert_array_equal(got.cost, got.tokens * got.runtime)
        assert np.all(got.provenance == Provenance.HISTORY)


def test_decide_observed_mode_switch():
    """``DecisionContext(observed=False)`` must decide as if the run had
    never been observed — bitwise the no-cap oracle — without the caller
    stripping ``observed_tokens`` off the request."""
    pol = AllocationPolicy(max_slowdown=0.05)
    a, b, obs, _, _ = _routed_partitions(64, 1, seed=23)
    svc = AllocationService(_PolicyOnlyModel(), pol)
    req = AllocationRequest(a=a, b=b, observed_tokens=obs)
    got = svc.decide(req, DecisionContext(observed=False))
    np.testing.assert_array_equal(got.tokens,
                                  choose_tokens_batch(a, b, pol, None))
    np.testing.assert_array_equal(
        svc.decide(req).tokens, choose_tokens_batch(a, b, pol, obs))


def test_decide_chunks_beyond_max_batch():
    """Requests past MAX_BATCH are chunked without changing decisions, on
    the plain service and the fabric alike."""
    pol = AllocationPolicy(max_slowdown=0.05)
    n = AllocationService.MAX_BATCH + 77
    rng = np.random.RandomState(9)
    a = rng.uniform(-3.0, -1e-4, n)
    b = np.exp(rng.uniform(-1.0, 9.0, n))
    obs = rng.randint(1, 7000, n)
    shard_of = rng.randint(0, 2, n)
    want = choose_tokens_batch(a, b, pol, obs)
    svc = AllocationService(_PolicyOnlyModel(), pol)
    got = svc.decide(AllocationRequest(a=a, b=b, observed_tokens=obs))
    np.testing.assert_array_equal(got.tokens, want)
    fabric = ShardedAllocationService(
        AllocationService(_PolicyOnlyModel(), pol), n_shards=2)
    got_sh = fabric.decide(AllocationRequest(a=a, b=b, observed_tokens=obs),
                           DecisionContext(shard_of=shard_of))
    np.testing.assert_array_equal(got_sh.tokens, want)
    np.testing.assert_array_equal(got_sh.shard, shard_of)


def test_service_policy_default_not_shared():
    """Satellite regression: the default AllocationPolicy must be built per
    service instance, not one module-level instance aliased everywhere."""
    s1 = AllocationService(_PolicyOnlyModel())
    s2 = AllocationService(_PolicyOnlyModel())
    assert s1.policy == s2.policy            # same value ...
    assert s1.policy is not s2.policy        # ... distinct instances


@pytest.mark.parametrize("max_slowdown", [0.0, 0.05, 0.3])
def test_min_tokens_within_slowdown_parity(max_slowdown):
    SMAX = 256
    with jax.enable_x64(True):
        fn = jax.jit(jax.vmap(min_tokens_within_slowdown_jnp,
                              in_axes=(0, 0, 0, None)),
                     static_argnums=3)
        skys, lens, obss, want = [], [], [], []
        for seed in range(25):
            rng = np.random.RandomState(seed)
            L = int(rng.randint(5, 200))
            sky = rng.randint(1, 50, L).astype(np.int64)
            pad = np.zeros(SMAX, np.int64)
            pad[:L] = sky
            for obs in (1, int(sky.max()), int(sky.max() * 2), 500):
                skys.append(pad)
                lens.append(L)
                obss.append(obs)
                want.append(min_tokens_within_slowdown(sky, obs, max_slowdown))
        got = fn(jnp.asarray(np.stack(skys)),
                 jnp.asarray(np.asarray(lens, np.int32)),
                 jnp.asarray(np.asarray(obss, np.int64)), max_slowdown)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
