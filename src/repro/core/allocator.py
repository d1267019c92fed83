"""Optimal token allocation from a PCC (paper §1-2, Figure 2/3).

Two allocation policies:
  * marginal-gain cut-off (§2.1): keep adding tokens while each additional
    token still buys >= ``min_gain`` relative runtime improvement; for the
    power law this closes to A* = |a| / min_gain;
  * bounded-slowdown: the smallest allocation whose (predicted or simulated)
    runtime stays within ``max_slowdown`` of the full-allocation runtime —
    this is the policy behind Figure 2's "5% performance loss" curve.

``token_reduction_cdf`` reproduces Figure 2 directly from AREPAS-simulated
skylines (the "(estimated) impact" of the paper).

Each numpy policy has a jnp twin (``choose_tokens_jnp`` /
``min_tokens_within_slowdown_jnp``): vectorized fixed-iteration bisections
that jit/vmap for the serving hot path and — run in float64 via
``jax.enable_x64`` — return decisions bitwise-equal to the
scalar oracles (tests/test_alloc_parity.py). ``choose_tokens_batch`` is the
host-side convenience wrapper.

``choose_tokens_priced`` (+ jnp twin / batch wrapper) is the cost-aware
variant behind the cluster scheduler's elastic repricing: a per-query
multiplicative ``price`` (>= 1, set per SLA class from pool contention)
scales the marginal-gain threshold *and* the slowdown budget, so a
pressured class slides down its PCC to the cost-optimal point while
``price == 1`` reproduces ``choose_tokens`` exactly.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import arepas
from repro.core.pcc import pcc_runtime

__all__ = ["AllocationPolicy", "available_policies", "build_policy",
           "choose_tokens", "choose_tokens_jnp",
           "choose_tokens_batch", "choose_tokens_priced",
           "choose_tokens_priced_jnp", "choose_tokens_priced_batch",
           "min_tokens_within_slowdown", "min_tokens_within_slowdown_jnp",
           "register_policy", "token_reduction_cdf"]

# Bisection ranges are token counts (< 2^48 by a huge margin); a fixed
# iteration count makes the search jit-able — extra iterations are no-ops,
# exactly like the scalar loop's termination.
_BISECT_ITERS = 48


@dataclasses.dataclass(frozen=True)
class AllocationPolicy:
    min_gain: float = 0.01          # stop when +1 token gains < 1% runtime
    max_slowdown: float = 0.0       # acceptable runtime increase vs full alloc
    min_tokens: int = 1
    max_tokens: int = 6287


# ---------------------------------------------------------- policy registry --
# Symmetric to repro.core.models.build_model: a string key resolves a policy
# builder, so AllocatorConfig (repro.api) and any declarative caller can name
# the allocation policy the way they name the model family.
_POLICY_REGISTRY: dict = {}


def register_policy(name: str):
    """``@register_policy("bounded_slowdown")`` exposes a builder —
    ``(**overrides) -> AllocationPolicy`` — to ``build_policy``."""
    def deco(fn):
        _POLICY_REGISTRY[name] = fn
        return fn
    return deco


def build_policy(name: str = "default", **overrides) -> AllocationPolicy:
    """Construct an ``AllocationPolicy`` by registered name; keyword
    overrides win over the preset's fields."""
    if name not in _POLICY_REGISTRY:
        raise KeyError(f"unknown allocation policy {name!r}; "
                       f"known: {sorted(_POLICY_REGISTRY)}")
    return _POLICY_REGISTRY[name](**overrides)


def available_policies() -> Tuple[str, ...]:
    return tuple(sorted(_POLICY_REGISTRY))


@register_policy("default")
def _default_policy(**overrides) -> AllocationPolicy:
    """Paper defaults: marginal-gain cut-off only."""
    return AllocationPolicy(**overrides)


@register_policy("marginal_gain")
def _marginal_gain_policy(**overrides) -> AllocationPolicy:
    """§2.1 gain cut-off alone (explicitly no slowdown bisection)."""
    overrides.setdefault("max_slowdown", 0.0)
    return AllocationPolicy(**overrides)


@register_policy("bounded_slowdown")
def _bounded_slowdown_policy(**overrides) -> AllocationPolicy:
    """Figure 2's "5% performance loss" operating point."""
    overrides.setdefault("max_slowdown", 0.05)
    return AllocationPolicy(**overrides)


def choose_tokens(a: float, b: float, policy: AllocationPolicy,
                  observed_tokens: Optional[int] = None) -> int:
    """Pick the allocation for a job from its (predicted) PCC parameters.

    Delegates to ``choose_tokens_priced`` at the neutral price — an exact
    no-op (every priced operation multiplies by 1.0), so there is a single
    implementation of the gain cut-off + slowdown bisection to maintain.
    """
    return choose_tokens_priced(a, b, policy, 1.0, observed_tokens)


def choose_tokens_jnp(a: jax.Array, b: jax.Array, policy: AllocationPolicy,
                      observed_tokens: Optional[jax.Array] = None
                      ) -> jax.Array:
    """Vectorized jnp twin of ``choose_tokens``: (J,) params -> (J,) tokens.

    The policy is static (branching on ``max_slowdown`` happens at trace
    time); ``observed_tokens`` is an optional (J,) int array. Trace under
    ``jax.enable_x64`` with float64 (a, b) for bitwise parity with the
    oracle.
    Same neutral-price delegation as the scalar.
    """
    a = jnp.asarray(a)
    return choose_tokens_priced_jnp(a, jnp.asarray(b), policy,
                                    jnp.ones((), a.dtype), observed_tokens)


@functools.lru_cache(maxsize=None)
def _compiled_policy(policy: AllocationPolicy, with_observed: bool):
    def f(a, b, hi):
        return choose_tokens_jnp(a, b, policy, hi if with_observed else None)
    return jax.jit(f)


def choose_tokens_batch(a: np.ndarray, b: np.ndarray,
                        policy: AllocationPolicy = AllocationPolicy(),
                        observed_tokens: Optional[np.ndarray] = None
                        ) -> np.ndarray:
    """Batched allocation decisions, bitwise-equal to a ``choose_tokens``
    loop: one jitted float64 call over (J,) parameter arrays."""
    with jax.enable_x64(True):
        aj = jnp.asarray(np.asarray(a, np.float64))
        bj = jnp.asarray(np.asarray(b, np.float64))
        obs = (None if observed_tokens is None
               else jnp.asarray(np.asarray(observed_tokens, np.int64)))
        fn = _compiled_policy(policy, observed_tokens is not None)
        out = fn(aj, bj, obs)
        return np.asarray(out)


def choose_tokens_priced(a: float, b: float, policy: AllocationPolicy,
                         price: float,
                         observed_tokens: Optional[int] = None) -> int:
    """Cost-aware allocation: ``price`` scales both policy knobs.

    The marginal-gain threshold becomes ``min_gain * price`` (each token must
    buy ``price``-times more runtime to stay worth leasing) and the slowdown
    budget becomes ``max_slowdown * price`` (a pressured class accepts more
    stretch). Both shrink the decision monotonically in ``price``;
    ``price == 1`` is exactly ``choose_tokens``.
    """
    hi = policy.max_tokens if observed_tokens is None else observed_tokens
    eff_gain = max(policy.min_gain, 1e-9) * price
    if a >= 0:   # degenerate / flat curve: minimum allocation is optimal
        t_gain = policy.min_tokens
    else:
        t_gain = int(np.clip(np.round(abs(a) / eff_gain),
                             policy.min_tokens, hi))
    if policy.max_slowdown <= 0:
        return t_gain
    base = pcc_runtime(a, b, hi)
    limit = (1.0 + policy.max_slowdown * price) * base
    lo, hi_s = policy.min_tokens, hi
    while lo < hi_s:                      # smallest A with rt <= limit
        mid = (lo + hi_s) // 2
        if pcc_runtime(a, b, mid) <= limit:
            hi_s = mid
        else:
            lo = mid + 1
    return max(min(t_gain, policy.max_tokens), lo)


def choose_tokens_priced_jnp(a: jax.Array, b: jax.Array,
                             policy: AllocationPolicy, price: jax.Array,
                             observed_tokens: Optional[jax.Array] = None
                             ) -> jax.Array:
    """Vectorized jnp twin of ``choose_tokens_priced``: (J,) params and
    (J,) prices -> (J,) tokens. Same float64 discipline as
    ``choose_tokens_jnp`` for bitwise parity with the scalar oracle."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    price = jnp.asarray(price)
    dt = a.dtype
    lo0 = policy.min_tokens
    hi = (jnp.full(a.shape, policy.max_tokens, jnp.int64)
          if observed_tokens is None
          else jnp.asarray(observed_tokens).astype(jnp.int64))
    eff_gain = max(policy.min_gain, 1e-9) * price
    a_star = jnp.abs(a) / eff_gain
    t_gain = jnp.clip(jnp.round(a_star), lo0, hi.astype(dt)).astype(jnp.int64)
    t_gain = jnp.where(a >= 0, jnp.int64(lo0), t_gain)
    if policy.max_slowdown <= 0:
        return t_gain

    base = b * hi.astype(dt) ** a
    limit = (1.0 + policy.max_slowdown * price) * base

    def body(_, st):
        lo, hi_s = st
        cond = lo < hi_s
        mid = (lo + hi_s) // 2
        ok = b * mid.astype(dt) ** a <= limit
        return (jnp.where(cond & ~ok, mid + 1, lo),
                jnp.where(cond & ok, mid, hi_s))

    # Under ``jax.shard_map`` the body's outputs vary over the mesh axes of
    # (a, b, price, observed); the initial carry must vary the same way.
    init = tuple(_vary_like(x, limit)
                 for x in (jnp.full(a.shape, lo0, jnp.int64), hi))
    lo, _ = jax.lax.fori_loop(0, _BISECT_ITERS, body, init)
    return jnp.maximum(jnp.minimum(t_gain, policy.max_tokens), lo)


def _vary_like(x: jax.Array, ref: jax.Array) -> jax.Array:
    """``x`` marked as varying over every manual mesh axis ``ref`` varies
    over (a no-op outside ``shard_map``)."""
    missing = tuple(jax.typeof(ref).vma - jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


@functools.lru_cache(maxsize=None)
def _compiled_priced_policy(policy: AllocationPolicy, with_observed: bool):
    def f(a, b, price, hi):
        return choose_tokens_priced_jnp(a, b, policy, price,
                                        hi if with_observed else None)
    return jax.jit(f)


def choose_tokens_priced_batch(a: np.ndarray, b: np.ndarray,
                               policy: AllocationPolicy, price: np.ndarray,
                               observed_tokens: Optional[np.ndarray] = None
                               ) -> np.ndarray:
    """Batched priced decisions, bitwise-equal to a ``choose_tokens_priced``
    loop: one jitted float64 call over (J,) parameter/price arrays."""
    with jax.enable_x64(True):
        aj = jnp.asarray(np.asarray(a, np.float64))
        bj = jnp.asarray(np.asarray(b, np.float64))
        pj = jnp.asarray(np.asarray(price, np.float64))
        obs = (None if observed_tokens is None
               else jnp.asarray(np.asarray(observed_tokens, np.int64)))
        fn = _compiled_priced_policy(policy, observed_tokens is not None)
        return np.asarray(fn(aj, bj, pj, obs))


def min_tokens_within_slowdown(skyline: np.ndarray, observed_tokens: int,
                               max_slowdown: float) -> int:
    """Smallest allocation whose AREPAS-simulated runtime stays within
    (1 + max_slowdown) of the observed runtime. Exact bisection: AREPAS
    runtime is non-increasing in the allocation."""
    base = len(skyline)
    limit = (1.0 + max_slowdown) * base
    lo, hi = 1, max(observed_tokens, 1)
    while lo < hi:
        mid = (lo + hi) // 2
        if arepas.simulate_runtime(skyline, mid) <= limit:
            hi = mid
        else:
            lo = mid + 1
    return lo


def min_tokens_within_slowdown_jnp(skyline: jax.Array, valid_len: jax.Array,
                                   observed_tokens: jax.Array,
                                   max_slowdown: float) -> jax.Array:
    """jnp twin of ``min_tokens_within_slowdown`` over a padded skyline.

    skyline: (Smax,) padded usage; valid_len: () true length; exact thanks to
    ``simulate_runtime_jax`` being bitwise-equal to the numpy simulator.
    vmap over leading axes for batches; ``max_slowdown`` is static.
    """
    base = valid_len.astype(jnp.float64)
    limit = (1.0 + max_slowdown) * base
    lo = jnp.asarray(1, jnp.int64)
    hi = jnp.maximum(jnp.asarray(observed_tokens, jnp.int64), 1)

    def body(_, st):
        lo, hi = st
        cond = lo < hi
        mid = (lo + hi) // 2
        rt = arepas.simulate_runtime_jax(skyline, valid_len,
                                         jnp.maximum(mid, 1))
        ok = rt.astype(jnp.float64) <= limit
        return (jnp.where(cond & ~ok, mid + 1, lo),
                jnp.where(cond & ok, mid, hi))

    lo, _ = jax.lax.fori_loop(0, _BISECT_ITERS, body, (lo, hi))
    return lo


def token_reduction_cdf(skylines: Sequence[np.ndarray],
                        observed_tokens: Sequence[int],
                        max_slowdown: float = 0.0,
                        grid: int = 101) -> Tuple[np.ndarray, np.ndarray]:
    """Figure 2: CDF of potential token-request reduction.

    Returns (reduction_grid in [0,1], fraction of jobs achieving >= r).
    """
    reductions = []
    for sky, tok in zip(skylines, observed_tokens):
        best = min_tokens_within_slowdown(sky, tok, max_slowdown)
        reductions.append(1.0 - best / max(tok, 1))
    reductions = np.asarray(reductions)
    r = np.linspace(0, 1, grid)
    frac = (reductions[None, :] >= r[:, None]).mean(1)
    return r, frac
