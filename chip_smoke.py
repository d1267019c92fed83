#!/usr/bin/env python3
"""Bring-up smoke test: the allocator's main path on a TPU, end to end.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chips # the K=4 fabric, one shard per chip

One process drives the chip through the library's public entry points at
the sizes its users run, and checks every result against the repository's
own references:

  a. device: a TPU must be present (there is no CPU fallback); the compile
     cache directory is reported;
  b. train + decide: ``Allocator.from_config`` at the default ``TasqConfig``
     for the nn and gnn engines, then ``decide`` on the eval set, compared
     with the scalar numpy policy oracle ``choose_tokens`` row by row;
  c. cluster replay: the 10k-event trace through ``run_cluster`` with EDF
     admission and elastic pricing, fused and unfused, which must agree
     exactly;
  d. streaming: the same trace through ``run_streaming`` on an AOT-warmed
     stack, with zero hot-path compiles and a report identical to (c);
  e. fused replay: the compiled Pallas epoch kernel against its jnp twin,
     first on one random epoch, then over the 1M-event ``FusedReplay``
     stream; also the skyline kernel against the jnp AREPAS simulator.

``--four-chips`` runs only the fabric phase: the K=4 ``shard_map`` fabric
over four chips against K single-shard services and against the same
fabric on one device.

Per-phase wall seconds are printed as smoke timings, not as measurements.
The last line of standard output is one JSON object naming the device;
the exit code is non-zero, and that line absent, if any phase failed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

PLATFORM = "tpu"
TRACE_EVENTS = 10_000
REPLAY_EVENTS = 1_000_000
EPOCH_SHAPE = (4, 8192, 4096)       # (K, L, Q) of the fused replay
SKYLINE_SHAPE = (256, 2048, 4)      # (jobs, seconds, allocations)


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    return 1


class Smoke:
    """Runs the phases in order, records failures, and keeps going where a
    later phase does not depend on the failed one."""

    def __init__(self):
        self.failed = []

    def phase(self, name, fn, *args, **kwargs):
        log(f"== phase {name}")
        t0 = time.perf_counter()
        try:
            out = fn(*args, **kwargs)
        except Exception:                       # report, then fail the run
            traceback.print_exc()
            self.failed.append(name)
            log(f"phase {name}: FAILED")
            return None
        log(f"smoke timing: phase {name} {time.perf_counter() - t0:.1f} s")
        return out


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ------------------------------------------------------------- spies --
def install_spies():
    """Record which kernel body each fused path ran and that no Pallas
    kernel ran interpreted. Wraps the module attributes the library calls
    through; the library itself is unchanged."""
    import repro.cluster.pool as pool_mod
    import repro.cluster.replay as replay_mod
    import repro.kernels.cluster_step as cs
    import repro.kernels.skyline as sky
    from repro.kernels.ops import cluster_impl

    seen = {"admission": set(), "replay": set(), "interpret": []}

    def impl_spy(fn, key):
        def wrapped(*a, impl=None, **k):
            seen[key].add(cluster_impl(impl))
            return fn(*a, impl=impl, **k)
        return wrapped

    def kernel_spy(fn):
        def wrapped(*a, interpret=False, **k):
            seen["interpret"].append(bool(interpret))
            return fn(*a, interpret=interpret, **k)
        return wrapped

    pool_mod.cluster_epoch_step = impl_spy(pool_mod.cluster_epoch_step,
                                           "admission")
    replay_mod.cluster_epoch_step = impl_spy(replay_mod.cluster_epoch_step,
                                             "replay")
    for mod, name in ((cs, "epoch_step_pallas"), (cs, "resize_step_pallas"),
                      (sky, "skyline_runtimes")):
        setattr(mod, name, kernel_spy(getattr(mod, name)))
    return seen


# ------------------------------------------------------------ phases --
def oracle_mismatches(decision, policy, observed) -> int:
    from repro.core.allocator import choose_tokens
    want = [choose_tokens(float(a), float(b), policy, int(o))
            for a, b, o in zip(decision.a, decision.b, observed)]
    return int(sum(int(t) != w for t, w in zip(decision.tokens, want)))


def train_and_decide(family, **build):
    from repro.api import AllocationRequest, Allocator, AllocatorConfig
    t0 = time.perf_counter()
    alloc = Allocator.from_config(
        AllocatorConfig(family=family, aot_warmup=bool(build)), **build)
    log(f"smoke timing: {family} from_config (train"
        f"{' + AOT warmup' if build else ''}) "
        f"{time.perf_counter() - t0:.1f} s")
    ds = alloc.pipeline.eval_set
    return alloc, ds, AllocationRequest.from_dataset(alloc.model, ds)


def decide_vs_oracle(alloc, ds, request):
    dec = alloc.decide(request)
    n_bad = oracle_mismatches(dec, alloc.policy, ds.observed_alloc)
    log(f"{alloc.model.family}: decide on {len(ds)} eval jobs, "
        f"{n_bad} token mismatches vs choose_tokens")
    check(n_bad == 0, f"{n_bad} decisions differ from the numpy oracle")


def same_report(x, y, what):
    import numpy as np
    check(dict(x.metrics) == dict(y.metrics), f"{what}: metrics differ")
    check(x.n_epochs == y.n_epochs, f"{what}: epoch counts differ")
    for f in ("alloc_errors", "cache_hits", "repeats"):
        check(np.array_equal(getattr(x, f), getattr(y, f)),
              f"{what}: {f} differ")
    check(x.cache_stats == y.cache_stats, f"{what}: cache stats differ")
    (tx, ex), (ty, ey) = x.error_series, y.error_series
    check(np.array_equal(tx, ty) and np.array_equal(ex, ey, equal_nan=True),
          f"{what}: error series differ")


def streaming(alloc, trace, cfg):
    rep = alloc.run_streaming(trace, cfg)
    w = alloc.warmup_report
    log(f"streaming: {rep.summary()}")
    log(f"streaming: {w.n_precompiled} executables precompiled; "
        f"hot-path compiles {rep.service_stats['compiles']}")
    check(rep.service_stats["compiles"] == 0, "hot-path compiles in replay")
    return rep


def cluster_replay(alloc, trace, cfg, seen):
    import dataclasses
    reps = {}
    for fused in (True, False):
        reps[fused] = alloc.run_cluster(
            trace, dataclasses.replace(cfg, fused=fused))
        log(f"run_cluster fused={fused}: {reps[fused].summary()}")
    same_report(reps[True], reps[False], "fused vs unfused")
    log(f"simulator admission kernel: {sorted(seen['admission'])}")
    check(seen["admission"], "the fused run never reached the epoch kernel")
    return reps[True]


def epoch_kernel_vs_ref(seed):
    """One random epoch at the fused-replay sizes: the compiled Pallas
    kernel against the f32 jnp twin, every output bitwise."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.cluster_step import epoch_step_pallas, epoch_step_ref
    K, L, Q = EPOCH_SHAPE
    rng = np.random.default_rng(seed)
    tok = rng.integers(1, 256, (K, L)) * (rng.random((K, L)) < 0.6)
    end = np.where(tok > 0, rng.integers(0, 2000, (K, L)) + 0.25, np.inf)
    free = rng.integers(0, 1 << 20, K)
    q_tok = rng.integers(1, 2048, (K, Q)) * (np.arange(Q) < rng.integers(
        0, Q + 1, (K, 1)))
    q_end = np.where(q_tok > 0, 1000.0 + rng.integers(1, 5000, (K, Q)), 0.0)
    args = (jnp.asarray(end, jnp.float32), jnp.asarray(tok, jnp.int32),
            jnp.asarray(free, jnp.int32), jnp.asarray(q_tok, jnp.int32),
            jnp.asarray(q_end, jnp.float32), jnp.float32(1000.0))
    got = jax.jit(epoch_step_pallas)(*args)
    want = jax.jit(epoch_step_ref)(*args)
    names = ("new_end", "new_tok", "slot_of", "n_admit", "adm_tok", "freed",
             "n_expired")
    for n, g, w in zip(names, got, want):
        check(np.array_equal(np.asarray(g), np.asarray(w)),
              f"epoch kernel {n} differs from epoch_step_ref")
    log(f"epoch kernel vs epoch_step_ref at (K, L, Q)=({K}, {L}, {Q}): "
        f"equal; admitted {np.asarray(got[3]).tolist()}, "
        f"expired {np.asarray(got[6]).tolist()}")


def skyline_kernel_vs_ref(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.arepas import simulate_runtime_batch_jit
    from repro.kernels.skyline import skyline_runtimes
    J, S, A = SKYLINE_SHAPE
    rng = np.random.default_rng(seed)
    lens = rng.integers(16, S, J)
    sky = rng.integers(1, 400, (J, S)) * (np.arange(S) < lens[:, None])
    allocs = rng.integers(1, 400, (J, A))
    args = (jnp.asarray(sky, jnp.float32), jnp.asarray(lens, jnp.int32),
            jnp.asarray(allocs, jnp.int32))
    got = np.asarray(jax.jit(skyline_runtimes)(*args))
    want = np.asarray(simulate_runtime_batch_jit(*args))
    check(np.array_equal(got, want),
          f"skyline kernel differs from simulate_runtime_batch at "
          f"{int((got != want).sum())} of {got.size} points")
    log(f"skyline kernel vs simulate_runtime_batch at (J, S, A)="
        f"({J}, {S}, {A}): equal")


def fused_replay(seen):
    from repro.cluster import FusedReplay, ReplayConfig
    from repro.kernels.ops import cluster_impl
    from repro.workloads import TraceGenerator
    t0 = time.perf_counter()
    stream = TraceGenerator(seed=71, n_unique=256, rate_qps=100.0).stream(
        REPLAY_EVENTS).buffer()
    log(f"smoke timing: {REPLAY_EVENTS}-event stream generated in "
        f"{time.perf_counter() - t0:.1f} s")
    reps = {}
    for impl in (None, "jnp"):
        cfg = ReplayConfig(capacity=4_194_304, n_shards=4, max_leases=8192,
                           epoch_s=480.0, queue_block=4096,
                           max_queue=REPLAY_EVENTS + 1, impl=impl)
        seen["replay"].clear()
        rep = FusedReplay(cfg).run(stream)
        ran = sorted(seen["replay"])
        log(f"FusedReplay impl={impl!r} ran {ran}: n_admitted "
            f"{rep.n_admitted}, n_completed {rep.n_completed}, n_rejected "
            f"{rep.n_rejected}, n_epochs {rep.n_epochs}, launches "
            f"{rep.launches}")
        log(f"smoke timing: FusedReplay impl={impl!r} wall {rep.wall_s} s")
        check(ran == [cluster_impl(impl)], f"replay ran {ran}")
        check(rep.n_admitted + rep.n_rejected == rep.n_events,
              "token/event conservation violated")
        check(rep.n_completed == rep.n_admitted,
              "replay ended with leases still outstanding")
        reps[impl] = rep
    check(reps[None].launches and seen["interpret"]
          and not any(seen["interpret"]), "a Pallas kernel ran interpreted")
    check(cluster_impl(None) == "pallas", "impl=None did not pick Pallas")
    for f in ("n_admitted", "n_completed", "n_rejected", "n_epochs"):
        check(getattr(reps[None], f) == getattr(reps["jnp"], f),
              f"Pallas and jnp replays differ in {f}")


def four_chip_fabric():
    import jax
    import numpy as np
    from repro.api import AllocationRequest, DecisionContext
    from repro.cluster import ClusterConfig, ClusterSimulator
    from repro.serve import AllocationService, ShardedAllocationService
    from repro.serve.aot import (WarmupConfig, model_input_template,
                                 warm_fabric)
    from repro.workloads import TraceGenerator
    K = 4
    from repro.api import Allocator, AllocatorConfig
    t0 = time.perf_counter()
    alloc = Allocator.from_config(AllocatorConfig(family="nn", n_shards=K))
    log(f"smoke timing: nn from_config (train) "
        f"{time.perf_counter() - t0:.1f} s")
    fab = alloc.fabric
    devs = list(fab.mesh.devices.flat) if fab.mesh is not None else []
    log(f"fabric mesh: {fab.mesh and dict(fab.mesh.shape)} over "
        f"{[(d.platform, d.id) for d in devs]}")
    check(fab.mesh is not None, "the fabric did not take the shard_map path")
    check(len({d.id for d in devs}) == K
          and all(d.platform == PLATFORM for d in devs),
          "the fabric mesh does not span 4 distinct TPU devices")
    one_dev = ShardedAllocationService(alloc.service, K, mesh=None)
    check(one_dev.mesh is None, "mesh=None fabric should loop on one device")

    # Compile both fabrics' float64 decision programs up front, each grid
    # concurrently, at the buckets the eval-set decide (256) and the K=4
    # replay (8, 16) dispatch; any other bucket compiles on first use.
    trace = TraceGenerator(seed=23, n_unique=96).generate(TRACE_EVENTS)
    t0 = time.perf_counter()
    warm = WarmupConfig(buckets=(8, 16, 256), observed=(False, True),
                        priced=False)
    tmpl = model_input_template(alloc.model, trace.jobs)
    n_pre = sum(warm_fabric(f, template=tmpl, cfg=warm).n_precompiled
                for f in (fab, one_dev))
    log(f"smoke timing: {n_pre} fabric executables precompiled in "
        f"{time.perf_counter() - t0:.1f} s")

    ds = alloc.pipeline.eval_set
    req = AllocationRequest.from_dataset(alloc.model, ds)
    shard_of = np.arange(len(ds)) % K
    got = alloc.decide(req, DecisionContext(shard_of=shard_of))
    flat = one_dev.decide(req, DecisionContext(shard_of=shard_of))
    for f in ("tokens", "a", "b"):
        check(np.array_equal(getattr(got, f), getattr(flat, f)),
              f"4-chip fabric vs 1-device fabric: {f} differ")
    for k in range(K):
        m = shard_of == k
        solo = AllocationService(alloc.model, alloc.policy).decide(
            req.narrow(m))
        for f in ("tokens", "a", "b"):
            check(np.array_equal(getattr(got, f)[m], getattr(solo, f)),
                  f"4-chip fabric vs single-shard service {k}: {f} differ")
    n_bad = oracle_mismatches(got, alloc.policy, ds.observed_alloc)
    check(n_bad == 0, f"{n_bad} fabric decisions differ from the oracle")
    log(f"fabric decide on {len(ds)} shard-tagged eval jobs: equal to the "
        f"1-device fabric and to {K} single-shard services; "
        f"{n_bad} oracle mismatches")

    cfg = ClusterConfig(n_shards=K)
    rep4 = alloc.run_cluster(trace, cfg)
    rep1 = ClusterSimulator(alloc.service, cfg, fabric=one_dev).run(trace)
    log(f"run_cluster K={K} on 4 chips: {rep4.summary()}")
    same_report(rep4, rep1, "4-chip vs 1-device fabric replay")
    log("K=4 replay: 4-chip fabric report identical to the 1-device fabric")
    return jax.devices()


# -------------------------------------------------------------- main --
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the K=4 fabric over four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random kernel inputs")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        return fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, SRC)

    import jax
    from repro.launch.cache import enable_compile_cache
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != PLATFORM:
        return fail(f"no TPU found (JAX's default device is "
                    f"{d0.platform}); this smoke test runs on the chip only")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        return fail(f"{need} TPU devices needed, {len(devices)} found")
    cache_dir = enable_compile_cache()
    log(f"device: {d0.platform} {d0.device_kind} x{len(devices)}")
    log(f"compile cache: {cache_dir}")

    smoke = Smoke()
    if args.four_chips:
        smoke.phase("fabric-4chip", four_chip_fabric)
    else:
        seen = install_spies()
        from repro.cluster import ClusterConfig
        from repro.workloads import TraceGenerator
        trace = TraceGenerator(seed=23, n_unique=96).generate(TRACE_EVENTS)
        cfg = ClusterConfig(admission="edf", elastic=True, pricing="elastic",
                            fused=True)
        gnn = smoke.phase("b-gnn-train", train_and_decide, "gnn")
        if gnn:
            smoke.phase("b-gnn-decide", decide_vs_oracle, *gnn)
        nn = smoke.phase("b-nn-train", train_and_decide, "nn",
                         warmup_trace=trace)
        if nn:
            alloc = nn[0]
            stream = smoke.phase("d-streaming", streaming, alloc, trace, cfg)
            smoke.phase("b-nn-decide", decide_vs_oracle, *nn)
            epoch = smoke.phase("c-cluster", cluster_replay, alloc, trace,
                                cfg, seen)
            if stream and epoch:
                smoke.phase("d-vs-c", same_report, stream, epoch,
                            "streaming vs epoch loop")
        smoke.phase("e-epoch-kernel", epoch_kernel_vs_ref, args.seed)
        smoke.phase("e-skyline-kernel", skyline_kernel_vs_ref, args.seed)
        smoke.phase("e-fused-replay", fused_replay, seen)
    if smoke.failed:
        return fail(f"failed phases: {', '.join(smoke.failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
