"""Ahead-of-time compiled decision executables: warm before traffic.

The lazy serving path jits each (engine, shape-bucket) decision function
on first request — a multi-hundred-millisecond stall that lands on a live
query's tail latency. This module moves every one of those compiles to
startup: enumerate the (engine, batch-bucket, priced, observed) grid the
stack can serve, ``jax.jit(...).lower(...).compile()`` each executable
(the ``launch/dryrun.py`` lower/compile pattern), warm it with one dummy
invocation — whose one packed output comes to the host in one transfer —
so first-touch runtime costs (program load, allocator warmup) are paid
too, and pin the result into ``ReplicaState.compiled`` at the
exact key the lazy builder would have used — the hot path then finds every
key present and never traces (``stats["compiles"] == 0``).

The compiled functions are the *same module-level factories* the lazy
builders wrap (``make_policy_decide`` & co. in ``serve/service.py``), so
AOT and lazy decisions are bitwise-identical by construction. Executables
are built with ``donate_argnums`` on the per-call batch buffers (never the
model parameters): on accelerators the padded input buffers are reused for
outputs instead of reallocated; on CPU XLA declines donation (harmlessly).

Warmup cost is first-class: each executable's lower/compile/warm split is
recorded (``decision_cold_start_s`` histogram, ``aot.warmup`` span) and
the totals surface in ``WarmupReport`` — the ``aot_serving`` benchmark
publishes ``cold_start_s`` and ``n_precompiled`` so the bench trajectory
tracks warmup cost as the grid grows.
"""
from __future__ import annotations

import dataclasses
import os
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.featurize import batch_graphs, batch_job_features
from repro.obs import NULL_OBS, Obs
from repro.serve.service import (AllocationService, ShardedAllocationService,
                                 make_fused_decide, make_policy_decide,
                                 make_priced_decide,
                                 make_sharded_policy_per_shard)

__all__ = ["WarmupConfig", "WarmupReport", "ExecutableRecord",
           "batch_buckets", "model_pool_inputs", "model_input_template",
           "warm_service", "warm_fabric", "warm_allocation_stack"]


def batch_buckets(floor: int = 8, cap: int = 4096) -> Tuple[int, ...]:
    """The power-of-two batch buckets in [floor, cap] — every padded batch
    dimension ``batch_bucket`` can produce (requests beyond ``cap`` are
    chunked by the service, so the grid is closed)."""
    out, p = [], max(int(floor), 1)
    while p <= cap:
        out.append(p)
        p *= 2
    return tuple(out)


def model_pool_inputs(model, jobs) -> Dict[str, np.ndarray]:
    """Model inputs for a set of unique queries, gatherable by job index —
    the same pool construction the cluster simulator serves decisions
    from, so shapes/dtypes derived here match the replay exactly."""
    if model.family == "gnn":
        gf, ga, gm = batch_graphs(jobs)
        return {"features": gf, "adj": ga, "mask": gm}
    return {"features": batch_job_features(jobs)}


def model_input_template(model, jobs) -> Dict[str, Tuple[Tuple[int, ...],
                                                         np.dtype]]:
    """Per-input (shape-sans-batch, dtype) template for fused executables,
    derived from the real featurization of ``jobs`` (for GNNs this fixes
    the pool-wide node dimension the trace will serve with)."""
    pool = model_pool_inputs(model, jobs)
    return {k: (tuple(v.shape[1:]), v.dtype) for k, v in pool.items()}


@dataclasses.dataclass(frozen=True)
class WarmupConfig:
    """What to pre-compile.

    The default grid covers everything the protocol can dispatch with
    observed-mode on (every cluster/plane path passes observed tokens);
    ``observed=(True, False)`` doubles the grid for stacks that also serve
    hint-free traffic. ``buckets`` overrides the power-of-two enumeration
    (floor..max_bucket) with an explicit set.
    """
    max_bucket: int = 4096               # == AllocationService.MAX_BATCH
    buckets: Optional[Tuple[int, ...]] = None
    observed: Tuple[bool, ...] = (True,)
    priced: bool = True                  # include the priced policy twins
    fused: bool = True                   # include fused model executables
    donate: bool = True                  # donate per-call batch buffers
    warm: bool = True                    # one dummy invocation per exec

    def bucket_set(self, floor: int) -> Tuple[int, ...]:
        return (self.buckets if self.buckets is not None
                else batch_buckets(floor, self.max_bucket))


@dataclasses.dataclass
class ExecutableRecord:
    kind: str                            # policy|priced|fused|sharded_*
    bucket: int                          # padded batch dimension
    lower_s: float
    compile_s: float
    warm_s: float

    @property
    def total_s(self) -> float:
        return self.lower_s + self.compile_s + self.warm_s


@dataclasses.dataclass
class WarmupReport:
    """What a warmup pass built, and what it cost."""
    n_precompiled: int = 0               # executables pinned by this pass
    n_already_cached: int = 0            # keys that were already present
    cold_start_s: float = 0.0            # wall clock of the whole pass
    lower_s: float = 0.0
    compile_s: float = 0.0
    warm_s: float = 0.0
    records: List[ExecutableRecord] = dataclasses.field(default_factory=list)

    def add(self, rec: ExecutableRecord) -> None:
        self.n_precompiled += 1
        self.lower_s += rec.lower_s
        self.compile_s += rec.compile_s
        self.warm_s += rec.warm_s
        self.records.append(rec)

    def merge(self, other: "WarmupReport") -> "WarmupReport":
        self.n_precompiled += other.n_precompiled
        self.n_already_cached += other.n_already_cached
        self.cold_start_s += other.cold_start_s
        self.lower_s += other.lower_s
        self.compile_s += other.compile_s
        self.warm_s += other.warm_s
        self.records.extend(other.records)
        return self

    def to_json(self) -> Dict:
        by_kind: Dict[str, Dict[str, float]] = {}
        for r in self.records:
            agg = by_kind.setdefault(
                r.kind, {"n": 0, "lower_s": 0.0, "compile_s": 0.0,
                         "warm_s": 0.0})
            agg["n"] += 1
            agg["lower_s"] = round(agg["lower_s"] + r.lower_s, 4)
            agg["compile_s"] = round(agg["compile_s"] + r.compile_s, 4)
            agg["warm_s"] = round(agg["warm_s"] + r.warm_s, 4)
        return {"n_precompiled": self.n_precompiled,
                "n_already_cached": self.n_already_cached,
                "cold_start_s": round(self.cold_start_s, 4),
                "lower_s": round(self.lower_s, 4),
                "compile_s": round(self.compile_s, 4),
                "warm_s": round(self.warm_s, 4),
                "by_kind": by_kind}


def _sds(shape: Tuple[int, ...], dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype)


def _concrete(aval):
    """A dummy concrete argument matching an aval tree (for warm calls)."""
    if aval is None:
        return None
    if isinstance(aval, jax.ShapeDtypeStruct):
        return np.zeros(aval.shape, aval.dtype)
    if isinstance(aval, dict):
        return {k: _concrete(v) for k, v in aval.items()}
    return aval                           # already concrete (model params)


def _aot_compile_cells(cells, replica, cfg: WarmupConfig, obs: Obs,
                       rep: WarmupReport) -> None:
    """``jit(raw).lower(*avals).compile()`` (+ one warm call) for every
    ``(kind, key, bucket, raw, avals, donate)`` cell whose key ``replica``
    does not hold yet, pinning each result at its key.

    Lowering traces, so it runs here, under the caller's x64 setting; the
    compiles then run concurrently — XLA releases the GIL while it compiles,
    and a TPU grid of float64 policy programs takes seconds per
    executable. Warm calls and pins follow in cell order. Donation is
    restricted to argnums whose aval is a real array tree; XLA's "donated
    buffers were not usable" advisory (CPU declines donation) is
    suppressed — it is expected there, not actionable."""
    todo = []
    for kind, key, bucket, raw, avals, donate in cells:
        if key in replica.compiled:
            rep.n_already_cached += 1
        else:
            todo.append((kind, key, bucket, raw, avals, donate))
    if not todo:
        return

    def compile_one(lowered):
        t0 = time.perf_counter()
        return lowered.compile(), time.perf_counter() - t0

    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        lowered, lower_s = [], []
        for _, _, _, raw, avals, donate in todo:
            donate_idx = tuple(i for i in donate
                               if cfg.donate and avals[i] is not None)
            t0 = time.perf_counter()
            lowered.append(jax.jit(raw, donate_argnums=donate_idx)
                           .lower(*avals))
            lower_s.append(time.perf_counter() - t0)
        workers = min(len(lowered), os.cpu_count() or 1)
        with ThreadPoolExecutor(max_workers=workers) as pool:
            built = list(pool.map(compile_one, lowered))
        for (kind, key, bucket, _, avals, _), t_lower, (compiled, t_comp) \
                in zip(todo, lower_s, built):
            t0 = time.perf_counter()
            if cfg.warm:
                out = compiled(*[_concrete(a) for a in avals])
                jax.tree.map(lambda v: np.asarray(v), out)  # block on it
            rec = ExecutableRecord(kind=kind, bucket=bucket, lower_s=t_lower,
                                   compile_s=t_comp,
                                   warm_s=time.perf_counter() - t0)
            obs.metrics.histogram("decision_cold_start_s").record(rec.total_s)
            obs.tracer.point("aot.compile", kind=kind, bucket=bucket,
                             compile_ms=round(rec.compile_s * 1e3, 1))
            replica.install(key, compiled)
            rep.add(rec)


def warm_service(service: AllocationService,
                 template: Optional[Dict] = None,
                 cfg: WarmupConfig = WarmupConfig(),
                 obs: Optional[Obs] = None) -> WarmupReport:
    """Pre-compile the single-replica grid: the policy and priced-policy
    executables at every batch bucket, plus — given an input ``template``
    from ``model_input_template`` — the fused model+policy executables.
    Host-only models (GBDT) need no fused cells: they share the compiled
    policy stage."""
    o = service.obs if obs is None else obs
    policy = service.policy
    rep = WarmupReport()
    t_wall = time.perf_counter()
    fused_ok = cfg.fused and service.model.supports_jit and template
    cells = []
    with o.tracer.span("aot.warmup", scope="service"), jax.enable_x64(True):
        for Bp in cfg.bucket_set(service.batch_floor):
            f64 = _sds((Bp,), jnp.float64)
            for wo in cfg.observed:
                # the service converts observed to a jnp array *outside*
                # enable_x64, so the lazy executables see int32 — the AOT
                # avals must match exactly or dispatch misses the cache
                obs_aval = _sds((Bp,), jnp.int32) if wo else None
                obs64 = _sds((Bp,), jnp.int64) if wo else None
                cells.append(("policy", ("policy", Bp, wo, policy), Bp,
                              make_policy_decide(policy, wo),
                              (f64, f64, obs_aval), (0, 1, 2)))
                if cfg.priced:
                    cells.append(
                        ("priced", ("priced", Bp, wo, policy), Bp,
                         make_priced_decide(policy, wo),
                         (f64, f64, f64, obs_aval), (0, 1, 2, 3)))
                if fused_ok:
                    padded = {k: _sds((Bp,) + shape, dtype)
                              for k, (shape, dtype) in template.items()}
                    sig = tuple(sorted((k, v.shape)
                                       for k, v in padded.items()))
                    cells.append(
                        ("fused",
                         ("fused", service.model.cache_key, sig, wo, policy),
                         Bp, make_fused_decide(service.model, policy, wo),
                         # fused converts observed *inside* enable_x64 -> i64
                         (service.model.params, padded, obs64), (1, 2)))
        _aot_compile_cells(cells, service.replica, cfg, o, rep)
    rep.cold_start_s = time.perf_counter() - t_wall
    return rep


def warm_fabric(fabric: ShardedAllocationService,
                template: Optional[Dict] = None,
                cfg: WarmupConfig = WarmupConfig(),
                obs: Optional[Obs] = None) -> WarmupReport:
    """Pre-compile the sharded fabric's (K, Bp) grid: the per-shard policy
    stage (priced and unpriced twins) and — with a ``template`` — the
    sharded fused executables. The fabric always passes price/observed as
    stacked arrays, so every aval here is concrete."""
    o = fabric.obs if obs is None else obs
    policy = fabric.policy
    K = fabric.n_shards
    svc = fabric.service
    rep = WarmupReport()
    t_wall = time.perf_counter()
    fused_ok = cfg.fused and fabric.model.supports_jit and template
    priced_opts = (False, True) if cfg.priced else (False,)
    cells = []
    with o.tracer.span("aot.warmup", scope="fabric", K=K), \
            jax.enable_x64(True):
        for Bp in cfg.bucket_set(svc.batch_floor):
            f64 = _sds((K, Bp), jnp.float64)
            i64 = _sds((K, Bp), jnp.int64)
            for wo in cfg.observed:
                for pr in priced_opts:
                    cells.append(
                        (f"sharded_policy[{'priced' if pr else 'plain'}]",
                         ("sharded_policy", K, Bp, wo, pr, policy,
                          fabric.mesh is not None), Bp,
                         fabric._map_over_shards(
                             make_sharded_policy_per_shard(policy, wo, pr),
                             4, False),
                         (f64, f64, f64, i64), (0, 1, 2, 3)))
                if fused_ok:
                    stacked = {k: _sds((K, Bp) + shape, dtype)
                               for k, (shape, dtype) in template.items()}
                    sig = tuple(sorted((k, v.shape)
                                       for k, v in stacked.items()))
                    cells.append(
                        ("sharded_fused",
                         ("sharded_fused", K, fabric.model.cache_key, sig,
                          wo, policy, fabric.mesh is not None), Bp,
                         fabric._map_over_shards(
                             make_fused_decide(fabric.model, policy, wo),
                             2, True),
                         (fabric.model.params, stacked, i64), (1, 2)))
        _aot_compile_cells(cells, svc.replica, cfg, o, rep)
    rep.cold_start_s = time.perf_counter() - t_wall
    return rep


def warm_allocation_stack(service: AllocationService,
                          fabric: Optional[ShardedAllocationService] = None,
                          *, jobs=None, cfg: WarmupConfig = WarmupConfig(),
                          obs: Optional[Obs] = None) -> WarmupReport:
    """Warm a whole serving stack before traffic: the single-replica grid
    plus (when a fabric is passed) the sharded (K, Bp) grid. ``jobs`` — a
    sequence of ``Job`` plans (e.g. ``trace.jobs``) — derives the fused
    input template via the real featurization path, which for GNNs pins
    the trace's pool-wide node dimension; without it only the
    (model-independent) policy stages are warmed and fused shapes compile
    lazily on first miss."""
    o = (service.obs if obs is None else obs) or NULL_OBS
    template = (model_input_template(service.model, jobs)
                if jobs is not None and service.model.supports_jit else None)
    rep = warm_service(service, template=template, cfg=cfg, obs=o)
    if fabric is not None:
        rep.merge(warm_fabric(fabric, template=template, cfg=cfg, obs=o))
    o.metrics.counter("aot_precompiled").inc(rep.n_precompiled)
    o.metrics.gauge("aot_cold_start_s").set(round(rep.cold_start_s, 4))
    return rep
