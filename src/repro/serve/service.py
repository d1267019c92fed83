"""AllocationService: one compiled call from features to token decisions.

The deploy/allocate stage of the paper (§2.2) as an online service: a
trained ``PCCModel`` plus an ``AllocationPolicy`` become a batch function

    model inputs (B, ...) -> scaled z -> PCCScaler.decode -> (a, b)
                          -> choose_tokens_jnp -> tokens (B,)

fused into a single jitted XLA executable per (model, input-shape bucket,
policy). Decisions are computed in float64 (``jax.enable_x64``) so they are
bitwise-equal to the numpy ``choose_tokens`` oracle run on the same decoded
parameters. Host-only models (GBDT) predict (a, b) on the host and share
the compiled policy stage.

Compiled functions are cached on (model.cache_key, shape signature,
observed?, policy); ``stats["compiles"]`` exposes cache behavior to tests
and benchmarks.

Each compiled decide function returns one device array: its outputs
(tokens, runtime; plus a, b on the fused path) stacked as the rows of a
float64 array, each exact there. ``_download`` brings that array to the
host in one transfer and casts each row back to the dtype a static
layout gives it (``POLICY_LAYOUT``; ``AllocationService.fused_layout``,
fixed by the model), so every decide call costs one device-to-host
transfer whatever its path.

Typed protocol (PR 5): the one entry point is

    decide(AllocationRequest, DecisionContext) -> AllocationDecision

(``repro.api.types``). A request carries raw model inputs (the fused cold
path) or known PCC parameters (the policy-only history path); the context
carries the price vector, shard placement, and observed-mode switch that
used to be separate methods. The pre-protocol method matrix
(``allocate_params`` / ``allocate_params_priced`` / ``allocate_batch`` /
``allocate_dataset``, plus the sharded twins) survives as thin deprecation
shims over ``decide`` for one release — same compiled kernels underneath,
decisions bitwise-equal by construction.

Sharded fabric (PR 4): the mutable serving state — compiled-executable
cache plus decision counters — lives in a ``ReplicaState``, of which a
plain ``AllocationService`` owns exactly one. ``ShardedAllocationService``
puts N replicas of one trained model behind the same ``decide`` protocol:
``DecisionContext.shard_of`` tags each row with a shard rank, per-shard
rows are stacked into one (K, Bp) block, and the fused
features -> decode -> policy stage runs across every replica in a single
compiled call — under ``jax.shard_map`` when the mesh really has one
device per shard, falling back to a loop over the shard axis on 1-device
hosts. Per-shard blocks keep single-shard shapes, so decisions stay
bitwise-equal to K independent single-shard services fed the same routed
partitions (tests/test_alloc_parity.py).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.api._compat import warn_deprecated
from repro.api.types import (AllocationDecision, AllocationRequest,
                             DecisionContext, Provenance)
from repro.core.allocator import (AllocationPolicy, choose_tokens_jnp,
                                  choose_tokens_priced_jnp)
from repro.obs import NULL_OBS, Obs
from repro.serve.batching import batch_bucket, pad_to, shard_positions

__all__ = ["AllocationResult", "AllocationService", "ReplicaState",
           "ShardedAllocationService", "make_fused_decide",
           "make_policy_decide", "make_priced_decide",
           "make_sharded_policy_per_shard"]


@dataclasses.dataclass
class AllocationResult:
    """Legacy result type of the pre-protocol method matrix (the shims still
    return it); new code consumes ``repro.api.AllocationDecision``."""
    tokens: np.ndarray        # (B,) int64 allocation decisions
    a: np.ndarray             # (B,) decoded PCC exponent
    b: np.ndarray             # (B,) decoded PCC coefficient
    runtime: np.ndarray       # (B,) predicted runtime at the chosen tokens


def _as_result(decision: AllocationDecision) -> AllocationResult:
    return AllocationResult(tokens=decision.tokens, a=decision.a,
                            b=decision.b, runtime=decision.runtime)


def _protocol_dispatch(engine, request: AllocationRequest,
                       ctx: DecisionContext, decide_params, decide_fused
                       ) -> AllocationDecision:
    """The one ``decide()`` dispatch, shared by the single-replica service
    and the sharded fabric (which differ only in the kernels passed in):

      * validate the request — exactly one of ``model_in`` or ``(a, b)``;
      * apply the observed-mode switch;
      * route (a, b) to the policy-only path, host models (no jit surface)
        to host prediction + the compiled policy, jit models to the fused
        kernel — with the priced re-decide on decoded parameters when the
        context carries prices (exactly the legacy two-step).

    New ``DecisionContext`` fields (preempted remainders, refit triggers,
    ...) belong here, once, not in per-engine copies.
    """
    B = request.batch_size()
    obs = request.observed_tokens if ctx.observed else None
    if request.a is not None or request.b is not None:
        if request.a is None or request.b is None:
            raise ValueError("AllocationRequest needs both a and b for the "
                             "policy-only path")
        if request.model_in:
            raise ValueError("ambiguous AllocationRequest: set model_in "
                             "or (a, b), not both")
        return decide_params(request.a, request.b, ctx.price, obs)
    if not request.model_in:
        raise ValueError("AllocationRequest needs model_in or (a, b)")
    if not engine.model.supports_jit:
        # host models (GBDT): host (a, b) prediction + compiled policy
        ref = (obs if obs is not None
               else np.full(B, engine.policy.max_tokens, np.int64))
        a, b = engine.model.predict_params_batch(request.model_in,
                                                 np.asarray(ref))
        return dataclasses.replace(
            decide_params(a, b, ctx.price, obs),
            provenance=np.full(B, Provenance.MODEL, np.int8))
    d = decide_fused(request.model_in, obs)
    if ctx.price is not None:
        # priced re-decide on the decoded parameters — identical to the
        # fused-then-priced two-step the cluster loop runs
        d = dataclasses.replace(
            decide_params(d.a, d.b, ctx.price, obs),
            provenance=np.full(B, Provenance.MODEL, np.int8))
    return d


def _download(tracer, packed, layout) -> List[np.ndarray]:
    """A compiled decide call's packed output on the host, in one transfer
    under the ``decide.download`` span (the wait for the device included),
    split into its rows: row ``i`` of the (..., R, Bp) array comes back as
    ``layout[i]``, by a static layout (``POLICY_LAYOUT``,
    ``AllocationService.fused_layout``)."""
    with tracer.span("decide.download", transfers=1):
        host = np.asarray(packed)
        return [host[..., i, :].astype(dt, copy=False)
                for i, dt in enumerate(layout)]


def _observed_dispatch(engine, span_name: str, request: AllocationRequest,
                       ctx: DecisionContext, decide_params, decide_fused,
                       **span_attrs) -> AllocationDecision:
    """``_protocol_dispatch`` under the observability plane: one span per
    decide (with a compile-vs-cached-hit attribute), decision latency into
    the cached-call or compile histogram, and a sampled provenance row to
    the flight recorder. With ``NULL_OBS`` installed every hook is a shared
    no-op.

    Compile detection is per-thread (``ReplicaState.begin_dispatch`` /
    ``compile_stalled``): only a call whose own builder inserted — or waited
    out a concurrent insert of — a compiled executable lands in
    ``decision_compile_s``. The old ``stats["compiles"] > c0`` delta was
    racy under the serving plane's worker threads: two concurrent
    first-calls both read ``c0`` stale, and an unrelated compile on another
    thread tagged a fast cached call as a compile."""
    o = engine.obs
    tr = o.tracer
    rep = engine.compile_state
    with tr.span(span_name, B=request.batch_size(),
                 path="history" if request.a is not None else "model",
                 priced=ctx.price is not None, **span_attrs) as sp:
        rep.begin_dispatch()
        t0 = tr.clock()
        d = _protocol_dispatch(engine, request, ctx,
                               decide_params, decide_fused)
        dt = tr.clock() - t0
        compiled = rep.compile_stalled()
        if sp is not None:
            sp.attrs["compiled"] = compiled
    # compiles land in their own histogram so decision_latency_s percentiles
    # (the SLO-gated series) measure the cached-executable steady state
    o.metrics.histogram(
        "decision_compile_s" if compiled else "decision_latency_s").record(dt)
    o.metrics.counter("decide_calls").inc()
    o.metrics.counter("decide_queries").inc(len(d))
    if o.recorder is not None:
        o.recorder.record(request, d, ctx)
    return d


# --------------------------------------------------------------- kernels --
# Module-level factories for the pure decide functions. The lazy builders
# below wrap them in ``jax.jit`` on first request; the AOT warmup
# (``repro.serve.aot``) lowers and compiles the *same* functions at startup
# — one definition, so the two paths are bitwise-identical by construction.
#
# Each returns its outputs packed as the rows of one float64 array — (2, Bp)
# for the policy stages, (4, Bp) for the fused stage, with a leading K on
# the sharded paths — so a call's results reach the host in one transfer
# (``_download``). Every row is exact in float64: tokens are integers far
# below 2**53 and (a, b) are widened from the model's float. The static
# layouts (``POLICY_LAYOUT``, ``AllocationService.fused_layout``) give each
# row's dtype back.

POLICY_LAYOUT = (np.int64, np.float64)                # tokens, runtime


def decode_dtype(model) -> np.dtype:
    """The float dtype a jit model decodes (a, b) to: its parameters'."""
    return np.result_type(*(leaf.dtype
                            for leaf in jax.tree.leaves(model.params)))


def _pack(*rows):
    return jnp.stack([r.astype(jnp.float64) for r in rows])


def _policy_rows(a, b, policy: AllocationPolicy, price, observed):
    toks = (choose_tokens_jnp(a, b, policy, observed) if price is None
            else choose_tokens_priced_jnp(a, b, policy, price, observed))
    return _pack(toks, b * toks.astype(a.dtype) ** a)


def make_policy_decide(policy: AllocationPolicy, with_observed: bool):
    def decide(a, b, observed):
        return _policy_rows(a, b, policy, None,
                            observed if with_observed else None)

    return decide


def make_priced_decide(policy: AllocationPolicy, with_observed: bool):
    def decide(a, b, price, observed):
        return _policy_rows(a, b, policy, price,
                            observed if with_observed else None)

    return decide


def make_fused_decide(model, policy: AllocationPolicy, with_observed: bool):
    scaler = model.scaler
    dt = decode_dtype(model)

    def fused(params, model_in, observed):
        z = model.serve_apply(params, model_in)
        a, b = scaler.decode(z)
        if a.dtype != dt or b.dtype != dt:
            raise TypeError(f"{model.family} decodes (a, b) to "
                            f"{a.dtype}/{b.dtype}, not its parameters' {dt}")
        a64 = a.astype(jnp.float64)
        b64 = b.astype(jnp.float64)
        toks = choose_tokens_jnp(a64, b64, policy,
                                 observed if with_observed else None)
        rt = b64 * toks.astype(jnp.float64) ** a64
        return _pack(toks, a64, b64, rt)

    return fused


def make_sharded_policy_per_shard(policy: AllocationPolicy,
                                  with_observed: bool, priced: bool):
    def per_shard(a, b, price, obs):
        # exactly the single-shard policy stage on a (Bp,) block
        return _policy_rows(a, b, policy, price if priced else None,
                            obs if with_observed else None)

    return per_shard


class ReplicaState:
    """Mutable serving state of one model replica.

    A plain ``AllocationService`` owns exactly one (its compiled-executable
    cache and decision counters); a ``ShardedAllocationService`` owns one
    per shard, so per-replica traffic and compile behavior stay observable
    after the fabric batches decisions across shards.

    The streaming serving plane decides from worker threads, so the cache
    and counters are guarded by ``lock`` (``get_or_build`` is the one
    double-checked insert path), and compile classification is per-thread:
    a dispatch is a compile iff *its own* builder inserted an executable or
    waited out a concurrent insert — not iff the global ``compiles``
    counter moved while it ran. AOT warmup (``repro.serve.aot``) pins
    pre-built executables via ``install`` without touching ``compiles``,
    so a fully warmed replica serves with ``stats["compiles"] == 0``.
    """

    __slots__ = ("shard", "stats", "compiled", "lock", "_tls")

    def __init__(self, shard: int = 0):
        self.shard = int(shard)
        self.stats: Dict[str, int] = {"compiles": 0, "calls": 0,
                                      "queries": 0, "executables_retired": 0}
        self.compiled: Dict[Tuple, callable] = {}
        self.lock = threading.RLock()
        self._tls = threading.local()

    # ----------------------------------------- per-thread compile tracking --
    def begin_dispatch(self) -> None:
        self._tls.compile_stall = False

    def note_compile_stall(self) -> None:
        self._tls.compile_stall = True

    def compile_stalled(self) -> bool:
        return getattr(self._tls, "compile_stall", False)

    # --------------------------------------------------------- cache paths --
    def get_or_build(self, key: Tuple, build):
        """Return the cached executable for ``key``, building it exactly
        once across threads. Every thread that raced the build — winner or
        loser — is flagged compile-stalled: its decide latency covered
        executable construction either way."""
        fn = self.compiled.get(key)
        if fn is not None:
            return fn
        with self.lock:
            fn = self.compiled.get(key)
            if fn is None:
                self.stats["compiles"] += 1
                fn = self.compiled[key] = build()
            self.note_compile_stall()
        return fn

    def install(self, key: Tuple, fn) -> bool:
        """Pin a pre-compiled executable (AOT warmup) without counting a
        compile. First install wins; returns whether ``fn`` was pinned."""
        with self.lock:
            if key in self.compiled:
                return False
            self.compiled[key] = fn
            return True

    def invalidate(self) -> int:
        """Retire every pinned/compiled executable (model hot-swap: the
        replaced replica must never dispatch a stale compiled fn again).
        Dispatches already holding an executable reference finish on it;
        the next ``get_or_build`` rebuilds. Returns the number retired
        (also accumulated in ``stats["executables_retired"]``)."""
        with self.lock:
            n = len(self.compiled)
            self.compiled.clear()
            self.stats["executables_retired"] += n
            return n

    def count(self, calls: int = 0, queries: int = 0) -> None:
        """Thread-safe counter bump for the dispatch paths."""
        with self.lock:
            self.stats["calls"] += calls
            self.stats["queries"] += queries


class AllocationService:
    """Batched allocation decisions for one trained PCCModel."""

    # largest single compiled batch; bigger requests are served in chunks
    MAX_BATCH = 4096

    def __init__(self, model, policy: Optional[AllocationPolicy] = None,
                 batch_floor: int = 8, obs: Optional[Obs] = None):
        self.model = model
        # per-instance default: a shared module-level AllocationPolicy()
        # instance would alias every service built without an explicit one
        self.policy = AllocationPolicy() if policy is None else policy
        self.batch_floor = batch_floor
        self.replica = ReplicaState()
        self.obs = NULL_OBS if obs is None else obs

    @property
    def _cache(self) -> Dict[Tuple, callable]:
        return self.replica.compiled

    @property
    def stats(self) -> Dict[str, int]:
        return self.replica.stats

    @property
    def compile_state(self) -> ReplicaState:
        return self.replica

    @functools.cached_property
    def fused_layout(self) -> Tuple:
        """Row dtypes of the fused stage's packed output, fixed by the
        model: tokens, a, b, runtime."""
        dt = decode_dtype(self.model)
        return (np.int64, dt, dt, np.float64)

    # ------------------------------------------------------------ jit cache --
    def _shape_sig(self, model_in: Dict[str, np.ndarray]) -> Tuple:
        # full padded shapes (batch dim included): one cache entry == one
        # XLA executable, so ``stats["compiles"]`` counts real compilations
        return tuple(sorted((k, v.shape) for k, v in model_in.items()))

    def _fused_fn(self, sig: Tuple, with_observed: bool):
        key = ("fused", self.model.cache_key, sig, with_observed, self.policy)
        return self.replica.get_or_build(key, lambda: jax.jit(
            make_fused_decide(self.model, self.policy, with_observed)))

    def _policy_fn(self, n_padded: int, with_observed: bool):
        key = ("policy", n_padded, with_observed, self.policy)
        return self.replica.get_or_build(key, lambda: jax.jit(
            make_policy_decide(self.policy, with_observed)))

    def _priced_fn(self, n_padded: int, with_observed: bool):
        key = ("priced", n_padded, with_observed, self.policy)
        return self.replica.get_or_build(key, lambda: jax.jit(
            make_priced_decide(self.policy, with_observed)))

    def _chunks(self, B: int) -> List[slice]:
        return [slice(i, min(i + self.MAX_BATCH, B))
                for i in range(0, B, self.MAX_BATCH)]

    # ------------------------------------------------------------ protocol --
    def decide(self, request: AllocationRequest,
               context: Optional[DecisionContext] = None
               ) -> AllocationDecision:
        """The one entry point: a typed request + context in, a typed
        decision out. Dispatch is by request/context *fields*:

          * ``request.a/b`` set      -> policy-only history path;
          * ``request.model_in`` set -> fused model path (host models
            predict (a, b) on the host and share the compiled policy);
          * ``context.price``        -> the priced policy twin;
          * ``context.observed``     -> honor ``request.observed_tokens``.

        Batches beyond ``MAX_BATCH`` are served in MAX_BATCH-sized chunks;
        each chunk's batch dimension is padded to a power-of-two bucket so
        repeated traffic reuses one compiled executable per shape.

        ``stats["calls"]`` counts compiled-kernel batch invocations, not
        protocol entries: a priced fused decision runs two kernel stages
        (fused model+policy, then the priced policy twin on the decoded
        parameters — exactly the legacy two-step) and accrues two calls.
        """
        ctx = DecisionContext() if context is None else context
        if ctx.shard_of is not None:
            raise ValueError(
                "AllocationService is single-replica; shard placement "
                "(DecisionContext.shard_of) needs a ShardedAllocationService "
                "or an Allocator")
        B = request.batch_size()
        if B > self.MAX_BATCH:
            return AllocationDecision.concat(
                self.decide(request.narrow(s), ctx.narrow(s))
                for s in self._chunks(B))
        return _observed_dispatch(self, "service.decide", request, ctx,
                                  self._decide_params, self._decide_fused)

    def _decide_params(self, a: np.ndarray, b: np.ndarray,
                       price: Optional[np.ndarray],
                       obs: Optional[np.ndarray]) -> AllocationDecision:
        a = np.asarray(a)
        B = a.shape[0]
        self.replica.count(calls=1, queries=B)
        tracer = self.obs.tracer
        with tracer.span("decide.dispatch"):
            Bp = batch_bucket(B, self.batch_floor)
            a64 = pad_to(np.asarray(a, np.float64), Bp)
            b64 = pad_to(np.asarray(b, np.float64), Bp)
            obs_p = (None if obs is None
                     else pad_to(np.asarray(obs, np.int64), Bp))
            obs_j = None if obs_p is None else jnp.asarray(obs_p)
            if price is None:
                fn = self._policy_fn(Bp, obs is not None)
                with jax.enable_x64(True):
                    out = fn(jnp.asarray(a64), jnp.asarray(b64), obs_j)
                price_out = np.ones(B, np.float64)
            else:
                p64 = np.ones(Bp, np.float64)  # neutral price on padded rows
                p64[:B] = np.asarray(price, np.float64)
                fn = self._priced_fn(Bp, obs is not None)
                with jax.enable_x64(True):
                    out = fn(jnp.asarray(a64), jnp.asarray(b64),
                             jnp.asarray(p64), obs_j)
                price_out = np.asarray(price, np.float64)
        toks, rt = _download(tracer, out, POLICY_LAYOUT)
        toks, rt = toks[:B], rt[:B]
        return AllocationDecision(
            tokens=toks, runtime=rt, a=a, b=np.asarray(b),
            cost=toks.astype(np.float64) * rt, price=price_out,
            shard=np.zeros(B, np.int64),
            provenance=np.full(B, Provenance.HISTORY, np.int8))

    def _decide_fused(self, model_in: Dict[str, np.ndarray],
                      obs: Optional[np.ndarray]) -> AllocationDecision:
        B = next(iter(model_in.values())).shape[0]
        self.replica.count(calls=1, queries=B)
        tracer = self.obs.tracer
        with tracer.span("decide.dispatch"):
            Bp = batch_bucket(B, self.batch_floor)
            padded = {k: pad_to(np.asarray(v), Bp)
                      for k, v in model_in.items()}
            # zero-padded observed rows are harmless: the bisection
            # degenerates and their outputs are sliced off below
            obs_p = (None if obs is None
                     else pad_to(np.asarray(obs, np.int64), Bp))
            fn = self._fused_fn(self._shape_sig(padded), obs is not None)
            with jax.enable_x64(True):
                out = fn(self.model.params,
                         {k: jnp.asarray(v) for k, v in padded.items()},
                         None if obs_p is None else jnp.asarray(obs_p))
        toks, a, b, rt = _download(tracer, out, self.fused_layout)
        toks, rt = toks[:B], rt[:B]
        return AllocationDecision(
            tokens=toks, runtime=rt, a=a[:B], b=b[:B],
            cost=toks.astype(np.float64) * rt, price=np.ones(B, np.float64),
            shard=np.zeros(B, np.int64),
            provenance=np.full(B, Provenance.MODEL, np.int8))

    # ----------------------------------------------- legacy shims (one rel) --
    def allocate_batch(self, model_in: Dict[str, np.ndarray],
                       observed_tokens: Optional[np.ndarray] = None
                       ) -> AllocationResult:
        """Deprecated: use ``decide(AllocationRequest(model_in=...))``."""
        warn_deprecated("AllocationService.allocate_batch",
                        "decide(AllocationRequest(model_in=...))")
        return _as_result(self.decide(AllocationRequest(
            model_in=model_in, observed_tokens=observed_tokens)))

    def allocate_params(self, a: np.ndarray, b: np.ndarray,
                        observed_tokens: Optional[np.ndarray] = None
                        ) -> AllocationResult:
        """Deprecated: use ``decide(AllocationRequest(a=..., b=...))``."""
        warn_deprecated("AllocationService.allocate_params",
                        "decide(AllocationRequest(a=..., b=...))")
        return _as_result(self.decide(AllocationRequest(
            a=a, b=b, observed_tokens=observed_tokens)))

    def allocate_params_priced(self, a: np.ndarray, b: np.ndarray,
                               price: np.ndarray,
                               observed_tokens: Optional[np.ndarray] = None
                               ) -> AllocationResult:
        """Deprecated: use ``decide(AllocationRequest(a=..., b=...),
        DecisionContext(price=...))``."""
        warn_deprecated("AllocationService.allocate_params_priced",
                        "decide(..., DecisionContext(price=...))")
        return _as_result(self.decide(
            AllocationRequest(a=a, b=b, observed_tokens=observed_tokens),
            DecisionContext(price=price)))

    def allocate_dataset(self, ds, use_observed: bool = True
                         ) -> AllocationResult:
        """Deprecated: use ``decide(AllocationRequest.from_dataset(...))``."""
        warn_deprecated("AllocationService.allocate_dataset",
                        "decide(AllocationRequest.from_dataset(...))")
        return _as_result(self.decide(
            AllocationRequest.from_dataset(self.model, ds, use_observed)))


class ShardedAllocationService:
    """N replicas of one trained model behind a single batched API.

    Wraps an ``AllocationService`` (whose compiled cache and counters keep
    serving single-shard traffic) and serves the same ``decide`` protocol
    for shard-tagged traffic: ``DecisionContext.shard_of`` carries a shard
    rank in [0, K) per row; rows are stacked into a (K, Bp) block — ``Bp``
    the batch bucket of the fullest shard — and one compiled call computes
    every replica's decisions. With a mesh that has one device per shard
    the per-shard stage runs under ``jax.shard_map`` (each device sees
    exactly the single-shard shapes); on smaller hosts it loops over the
    shard axis (``jax.lax.map``). Either way the per-shard math is the
    single-shard math, so decisions are bitwise-equal to K independent
    ``AllocationService`` instances fed the routed partitions.

    Fabric-level counters accrue into the wrapped service's ``stats``;
    per-replica traffic lands in ``replicas[k].stats``.
    """

    def __init__(self, service: AllocationService, n_shards: int = 1,
                 mesh=None):
        assert n_shards >= 1
        self.service = service
        self.model = service.model
        self.policy = service.policy
        self.n_shards = int(n_shards)
        self.replicas = [ReplicaState(k) for k in range(n_shards)]
        # shard_map needs exactly one device per shard; anything else (and
        # in particular the 1-device smoke mesh) means a loop over the axis
        self.mesh = (mesh if mesh is not None
                     and dict(mesh.shape).get("shard") == n_shards
                     and n_shards > 1 else None)

    @property
    def stats(self) -> Dict[str, int]:
        return self.service.stats

    @property
    def compile_state(self) -> ReplicaState:
        # one executable cache (and one lock) for fabric + wrapped service
        return self.service.replica

    @property
    def obs(self) -> Obs:
        # one Obs bundle per service; the fabric shares its wrapped
        # service's so single-shard and fabric traffic land in one place
        return self.service.obs

    @obs.setter
    def obs(self, value: Obs) -> None:
        self.service.obs = value

    def replica_stats(self) -> List[Dict[str, int]]:
        """Per-shard decision counters, shard-rank order."""
        return [dict(r.stats) for r in self.replicas]

    # ------------------------------------------------------------ kernels --
    def _map_over_shards(self, per_shard, n_args: int, with_params: bool):
        """Lift a per-shard block function over the (K, ...) shard axis.

        ``per_shard`` sees exactly the single-shard shapes (Bp, ...). Under
        ``shard_map`` each device's block keeps a size-1 shard dim, which is
        squeezed before and restored after so both modes run the same math.
        """
        if self.mesh is not None:
            def block_fn(*args):
                squeeze = lambda t: jax.tree.map(lambda v: v[0], t)
                if with_params:
                    out = per_shard(args[0], *map(squeeze, args[1:]))
                else:
                    out = per_shard(*map(squeeze, args))
                return jax.tree.map(lambda v: v[None], out)

            specs = ((jax.tree.map(lambda _: P(), self.model.params),)
                     if with_params else ())
            specs += (P("shard"),) * n_args
            return jax.shard_map(block_fn, mesh=self.mesh, in_specs=specs,
                                 out_specs=P("shard"))
        # one device: a loop over shards, each at the single-shard shapes
        # (``vmap`` would batch the model's matmuls, which changes their
        # rounding)
        if with_params:
            return lambda params, *xs: jax.lax.map(
                lambda x: per_shard(params, *x), xs)
        return lambda *xs: jax.lax.map(lambda x: per_shard(*x), xs)

    def _sharded_policy_fn(self, Bp: int, with_observed: bool, priced: bool):
        key = ("sharded_policy", self.n_shards, Bp, with_observed, priced,
               self.policy, self.mesh is not None)
        return self.service.replica.get_or_build(key, lambda: jax.jit(
            self._map_over_shards(
                make_sharded_policy_per_shard(self.policy, with_observed,
                                              priced), 4, False)))

    def _sharded_fused_fn(self, sig: Tuple, with_observed: bool):
        key = ("sharded_fused", self.n_shards, self.model.cache_key, sig,
               with_observed, self.policy, self.mesh is not None)
        return self.service.replica.get_or_build(key, lambda: jax.jit(
            # the single-shard fused stage on each replica's (Bp, ...)
            # block: identical shapes, identical math
            self._map_over_shards(
                make_fused_decide(self.model, self.policy, with_observed),
                2, True)))

    # ------------------------------------------------------------ stacking --
    def _place(self, shard_of: np.ndarray):
        shard_of = np.asarray(shard_of, np.int64)
        assert shard_of.size == 0 or (0 <= shard_of.min()
                                      and shard_of.max() < self.n_shards)
        pos, counts, Bp = shard_positions(shard_of, self.n_shards,
                                          self.service.batch_floor)
        for k, r in enumerate(self.replicas):
            if counts[k]:
                r.count(calls=1, queries=int(counts[k]))
        self.service.replica.count(calls=1, queries=int(shard_of.size))
        return shard_of, pos, Bp

    def _stack(self, shard_of, pos, Bp, x, dtype, fill=0) -> np.ndarray:
        """Scatter a flat (B, ...) array into its (K, Bp, ...) block."""
        x = np.asarray(x, dtype)
        out = np.full((self.n_shards, Bp) + x.shape[1:], fill, dtype)
        out[shard_of, pos] = x
        return out

    # ------------------------------------------------------------ protocol --
    def decide(self, request: AllocationRequest,
               context: Optional[DecisionContext] = None
               ) -> AllocationDecision:
        """The fabric's ``decide``: identical protocol to the single-shard
        service, with ``context.shard_of`` placing each row on a replica
        (None places everything on shard 0). One compiled (K, Bp) call
        decides for every replica at once; results come back in input
        order."""
        ctx = DecisionContext() if context is None else context
        B = request.batch_size()
        if ctx.shard_of is None:
            ctx = dataclasses.replace(ctx, shard_of=np.zeros(B, np.int64))
        if B > self.service.MAX_BATCH:
            return AllocationDecision.concat(
                self.decide(request.narrow(s), ctx.narrow(s))
                for s in self.service._chunks(B))
        shard_of = ctx.shard_of
        return _observed_dispatch(
            self, "fabric.decide", request, ctx,
            lambda a, b, price, obs: self._decide_params(shard_of, a, b,
                                                         price, obs),
            lambda model_in, obs: self._decide_fused(shard_of, model_in,
                                                     obs),
            K=self.n_shards)

    def _decide_params(self, shard_of: np.ndarray, a: np.ndarray,
                       b: np.ndarray, price: Optional[np.ndarray],
                       obs: Optional[np.ndarray]) -> AllocationDecision:
        a = np.asarray(a)
        B = a.shape[0]
        tracer = self.obs.tracer
        with tracer.span("decide.dispatch"):
            shard_of, pos, Bp = self._place(shard_of)
            a2 = self._stack(shard_of, pos, Bp, a, np.float64)
            b2 = self._stack(shard_of, pos, Bp, b, np.float64)
            p2 = (np.ones((self.n_shards, Bp), np.float64) if price is None
                  else self._stack(shard_of, pos, Bp, price, np.float64,
                                   fill=1))
            obs2 = (np.zeros((self.n_shards, Bp), np.int64) if obs is None
                    else self._stack(shard_of, pos, Bp, obs, np.int64))
            fn = self._sharded_policy_fn(Bp, obs is not None,
                                         price is not None)
            with jax.enable_x64(True):
                out = fn(jnp.asarray(a2), jnp.asarray(b2), jnp.asarray(p2),
                         jnp.asarray(obs2))
        toks, rt = _download(tracer, out, POLICY_LAYOUT)
        toks, rt = toks[shard_of, pos], rt[shard_of, pos]
        return AllocationDecision(
            tokens=toks, runtime=rt, a=a, b=np.asarray(b),
            cost=toks.astype(np.float64) * rt,
            price=(np.ones(B, np.float64) if price is None
                   else np.asarray(price, np.float64)),
            shard=shard_of,
            provenance=np.full(B, Provenance.HISTORY, np.int8))

    def _decide_fused(self, shard_of: np.ndarray,
                      model_in: Dict[str, np.ndarray],
                      obs: Optional[np.ndarray]) -> AllocationDecision:
        """Stack each replica's inputs, run features -> decode -> policy
        across all K replicas in one compiled call, unstack to input
        order."""
        B = next(iter(model_in.values())).shape[0]
        tracer = self.obs.tracer
        with tracer.span("decide.dispatch"):
            shard_of, pos, Bp = self._place(shard_of)
            stacked = {k: self._stack(shard_of, pos, Bp, v,
                                      np.asarray(v).dtype)
                       for k, v in model_in.items()}
            obs2 = (np.zeros((self.n_shards, Bp), np.int64) if obs is None
                    else self._stack(shard_of, pos, Bp, obs, np.int64))
            sig = tuple(sorted((k, v.shape) for k, v in stacked.items()))
            fn = self._sharded_fused_fn(sig, obs is not None)
            with jax.enable_x64(True):
                out = fn(self.model.params,
                         {k: jnp.asarray(v) for k, v in stacked.items()},
                         jnp.asarray(obs2))
        toks, a, b, rt = _download(tracer, out, self.service.fused_layout)
        toks, rt = toks[shard_of, pos], rt[shard_of, pos]
        return AllocationDecision(
            tokens=toks, runtime=rt, a=a[shard_of, pos], b=b[shard_of, pos],
            cost=toks.astype(np.float64) * rt,
            price=np.ones(B, np.float64), shard=shard_of,
            provenance=np.full(B, Provenance.MODEL, np.int8))

    # ----------------------------------------------- legacy shims (one rel) --
    def allocate_params(self, shard_of: np.ndarray, a: np.ndarray,
                        b: np.ndarray,
                        observed_tokens: Optional[np.ndarray] = None,
                        price: Optional[np.ndarray] = None
                        ) -> AllocationResult:
        """Deprecated: use ``decide(AllocationRequest(a=..., b=...),
        DecisionContext(shard_of=...))``."""
        warn_deprecated("ShardedAllocationService.allocate_params",
                        "decide(..., DecisionContext(shard_of=...))")
        return _as_result(self.decide(
            AllocationRequest(a=a, b=b, observed_tokens=observed_tokens),
            DecisionContext(price=price, shard_of=shard_of)))

    def allocate_params_priced(self, shard_of: np.ndarray, a: np.ndarray,
                               b: np.ndarray, price: np.ndarray,
                               observed_tokens: Optional[np.ndarray] = None
                               ) -> AllocationResult:
        """Deprecated: use ``decide(...,
        DecisionContext(price=..., shard_of=...))``."""
        warn_deprecated("ShardedAllocationService.allocate_params_priced",
                        "decide(..., DecisionContext(price=..., "
                        "shard_of=...))")
        return _as_result(self.decide(
            AllocationRequest(a=a, b=b, observed_tokens=observed_tokens),
            DecisionContext(price=np.asarray(price, np.float64),
                            shard_of=shard_of)))

    def allocate_batch(self, shard_of: np.ndarray,
                       model_in: Dict[str, np.ndarray],
                       observed_tokens: Optional[np.ndarray] = None
                       ) -> AllocationResult:
        """Deprecated: use ``decide(AllocationRequest(model_in=...),
        DecisionContext(shard_of=...))``."""
        warn_deprecated("ShardedAllocationService.allocate_batch",
                        "decide(..., DecisionContext(shard_of=...))")
        return _as_result(self.decide(
            AllocationRequest(model_in=model_in,
                              observed_tokens=observed_tokens),
            DecisionContext(shard_of=shard_of)))
