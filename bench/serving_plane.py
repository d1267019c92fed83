"""Runner for mixes with ``"runner": "serving_plane"``: an open-loop
schedule of single-query decisions into ``ServingPlane.submit``.

Set-up builds the plane the configuration describes around the seed's
model, warms every executable the mix's requests can reach and sends each
plan through the plane once. The window then offers the schedule: request
``i`` is submitted when it falls due, and its latency runs from when it
fell due to when its future resolved. The harness keeps no future: each
one's done callback writes its time and answer into arrays made before
the window opens, as a client that forgets a request once it is answered.
Once the window has closed and every answer is in, each answer is
compared with the plain reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
import threading
import time
from typing import Dict, Optional

import numpy as np

from bench import check, devtrace
from bench.model import load_model, make_weights
from bench.traffic.generator import ServeSchedule, serve_schedule

ANSWER_WAIT_S = 60.0
LEAD_S = 0.05


class Spy:
    """What the timed path answered, row by row, written by request id
    into arrays made before the window opens: the (a, b, tokens, runtime)
    the service returned for each request. The micro-batcher's dispatch
    notes the ids of its batch on its own thread; the service's ``decide``,
    called next on that thread, writes the rows under them. While
    ``installed``, planes build their workers' micro-batchers from a
    subclass that notes the ids."""

    def __init__(self, n_ids: int):
        self.a = np.full(n_ids, np.nan)
        self.b = np.full(n_ids, np.nan)
        self.runtime = np.full(n_ids, np.nan)
        self.tokens = np.full(n_ids, -1, np.int64)
        self.batch = threading.local()

    @contextlib.contextmanager
    def installed(self):
        from repro.serve import plane
        from repro.serve.batching import MicroBatcher
        spy = self

        class RecordingBatcher(MicroBatcher):
            def _dispatch(self, sig, reqs):
                spy.batch.ids = np.fromiter(
                    (r.request_id for r in reqs), np.int64, len(reqs))
                return super()._dispatch(sig, reqs)

        plane.MicroBatcher = RecordingBatcher
        try:
            yield self
        finally:
            plane.MicroBatcher = MicroBatcher

    def service(self, svc):
        """``svc`` with a ``decide`` that records each decision's rows."""
        spy = self

        class RecordingService:
            def __getattr__(self, name):
                return getattr(svc, name)

            def decide(self, request, context=None):
                d = svc.decide(request, context)
                ids = spy.batch.ids
                keep = ids < spy.a.size
                ids = ids[keep]
                spy.a[ids] = np.asarray(d.a)[keep]
                spy.b[ids] = np.asarray(d.b)[keep]
                spy.runtime[ids] = np.asarray(d.runtime)[keep]
                spy.tokens[ids] = np.asarray(d.tokens)[keep]
                return d

        return RecordingService()


@dataclasses.dataclass
class ServeRun:
    """Everything a metric reader may read about one run."""
    seconds: float
    setup_s: float
    window: tuple                       # (open, close) on perf_counter
    due_abs: np.ndarray                 # when each request fell due
    submit_t: np.ndarray                # when the harness called submit
    done_t: np.ndarray                  # when its future resolved (inf: never)
    answered: np.ndarray                # resolved to tokens
    rid: np.ndarray                     # the plane's request id of each
    records: list                       # tracer records (traced runs)
    counters: Dict[str, tuple]          # (before, after) over the window
    trace: Optional[devtrace.Trace]
    lateness_s: np.ndarray              # submit_t - due_abs


def _program_inputs(jobs):
    """Each plan's model input, from the program's own featurization."""
    from repro.core.featurize import job_features
    return [{"features": job_features(j)} for j in jobs]


def build(config: Dict, params, obs, spy: Spy):
    """The plane the configuration describes, around the seed's model."""
    from repro.core.allocator import build_policy
    from repro.serve.plane import ServingPlane
    from repro.serve.service import AllocationService
    pol = config["policy"]
    sp = config["serving_plane"]
    policy = build_policy(pol["name"], **{k: v for k, v in pol.items()
                                          if k != "name"})
    service = AllocationService(load_model(config, params), policy, obs=obs)
    plane = ServingPlane(spy.service(service), n_workers=sp["n_workers"],
                         backlog=sp["backlog"], max_batch=sp["max_batch"],
                         obs=obs)
    return service, plane


def warm(config, service, plane, jobs, inputs) -> int:
    """Compile (or load from the cache) the fused executables at every
    (batch bucket, observed) the traffic can reach, start the plane, and
    send every plan through it once with and once without observed
    tokens."""
    import jax.numpy as jnp
    from repro.serve.aot import WarmupConfig, warm_service
    from repro.serve.batching import batch_bucket
    max_batch = config["serving_plane"]["max_batch"]
    cfg = WarmupConfig(max_bucket=batch_bucket(max_batch),
                       observed=(True, False), priced=False)
    d = inputs[0]["features"].shape[0]
    n = warm_service(service, template={"features": ((d,), jnp.float32)},
                     cfg=cfg).n_precompiled
    plane.start(warmup=WarmupConfig(buckets=()))
    futs = [plane.submit(x, observed_tokens=(j.default_tokens if k % 2
                                             else None))
            for k in range(2) for j, x in zip(jobs, inputs)]
    for f in futs:
        f.result(timeout=ANSWER_WAIT_S)
    return n


class Answers:
    """When each request's future resolved and what to, written by its
    done callback; -1 where it failed or has not resolved."""

    def __init__(self, n: int):
        self.done_t = np.full(n, np.inf)
        self.tokens = np.full(n, -1, np.int64)
        self.failure = None

    def callback(self, i: int):
        clock = time.perf_counter

        def done(f):
            self.done_t[i] = clock()
            err = f.exception()
            if err is None:
                self.tokens[i] = f.result()
            elif self.failure is None:
                self.failure = err
        return done

    def wait(self, timeout: float) -> None:
        """Until every request has resolved, or ``timeout`` seconds."""
        deadline = time.perf_counter() + timeout
        while (not np.all(np.isfinite(self.done_t))
               and time.perf_counter() < deadline):
            time.sleep(0.01)
        if self.failure is not None:
            print(f"requests unanswered; the first failure: "
                  f"{self.failure!r}", file=sys.stderr, flush=True)


def offer(plane, sched: ServeSchedule, inputs, t_open: float,
          seconds: float, device_s: float = 0.0):
    """Submit each request when it falls due; returns (submit times,
    answers). With ``device_s``, the first ``device_s`` seconds of the
    window carry the ``devtrace.WINDOW`` annotation."""
    import jax
    n = len(sched)
    submit_t = np.empty(n)
    answers = Answers(n)
    clock = time.perf_counter
    due = t_open + sched.due_s
    time.sleep(max(0.0, t_open - clock()))
    mark = None
    if device_s:
        mark = jax.profiler.TraceAnnotation(devtrace.WINDOW)
        mark.__enter__()
    for i in range(n):
        wait = due[i] - clock()
        if wait > 2e-3:
            time.sleep(wait - 1e-3)
        while clock() < due[i]:
            time.sleep(0)
        if mark is not None and due[i] >= t_open + device_s:
            mark.__exit__(None, None, None)
            mark = None
        obs_tok = int(sched.observed[i])
        submit_t[i] = clock()
        plane.submit(inputs[sched.pick[i]],
                     observed_tokens=obs_tok if obs_tok >= 0 else None
                     ).add_done_callback(answers.callback(i))
    rest = t_open + seconds - clock()
    if rest > 0:
        time.sleep(rest)
    if mark is not None:
        mark.__exit__(None, None, None)
    return submit_t, answers


class GcClock:
    """Python's garbage collections during the window: count and seconds
    per generation, and the longest pause."""

    def __init__(self):
        self.t0 = 0.0
        self.n = [0, 0, 0]
        self.s = [0.0, 0.0, 0.0]
        self.longest = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self.t0
        g = info["generation"]
        self.n[g] += 1
        self.s[g] += dt
        self.longest = max(self.longest, dt)

    def __str__(self):
        return ("gc in the window: " + ", ".join(
            f"gen{g} {self.n[g]} x {self.s[g]:.4f} s" for g in range(3))
            + f"; longest pause {self.longest:.4f} s")


def run(cell, seed: int, seconds: float, traced: bool, t_start: float,
        trace_dir: Optional[str] = None):
    """One run of a serving cell: returns (ServeRun, checks, device)."""
    import jax
    from repro.obs import NULL_OBS, Obs
    config = cell.config
    obs = Obs.enabled(capacity=1 << 22) if traced else NULL_OBS
    phases = [("start", time.perf_counter())]
    sched = serve_schedule(cell.mix, seconds, seed)
    phases.append(("plans and schedule", time.perf_counter()))
    rid0 = len(sched.jobs) * 2                 # ids the warm-up uses
    spy = Spy(rid0 + len(sched))
    with spy.installed():
        service, plane = build(config, make_weights(config, seed, sched.jobs),
                               obs, spy)
        phases.append(("weights and plane", time.perf_counter()))
        inputs = _program_inputs(sched.jobs)
        phases.append(("featurize", time.perf_counter()))
        n_exec = warm(config, service, plane, sched.jobs, inputs)
        phases.append((f"warm ({n_exec} executables compiled or loaded, "
                       f"each plan twice through the plane)",
                       time.perf_counter()))
        print("set-up: " + "; ".join(
            f"{name} {t - t_prev:.3f} s" for (_, t_prev), (name, t)
            in zip(phases, phases[1:])), file=sys.stderr, flush=True)

        def counters():
            return {"decide_calls": obs.metrics.counter("decide_calls").value,
                    "decide_queries":
                        obs.metrics.counter("decide_queries").value,
                    "compiles": service.stats["compiles"],
                    "saturations": plane.backlog.saturations}

        before = counters()
        if traced:
            obs.tracer.clear()
            jax.profiler.start_trace(
                trace_dir, profiler_options=devtrace.profile_options())
        t_open = time.perf_counter() + LEAD_S
        gc_clock = GcClock()
        gc.callbacks.append(gc_clock)
        submit_t, answers = offer(
            plane, sched, inputs, t_open, seconds,
            min(seconds, devtrace.WINDOW_S) if traced else 0.0)
        gc.callbacks.remove(gc_clock)
        after = counters()
        if traced:
            jax.profiler.stop_trace()
        answers.wait(ANSWER_WAIT_S)
        device = device_info(jax.devices()[:cell.chips])
        plane.stop()
    print(gc_clock, file=sys.stderr, flush=True)
    records = obs.tracer.records()
    trace = None
    if traced:
        t0 = time.perf_counter()
        trace = devtrace.load(trace_dir)
        print(f"trace read in {time.perf_counter() - t0:.3f} s",
              file=sys.stderr, flush=True)

    rid = rid0 + np.arange(len(sched))
    served = check.Served(answers=answers.tokens.copy(), a=spy.a[rid],
                          b=spy.b[rid], tokens=spy.tokens[rid],
                          runtime=spy.runtime[rid])
    done_t = answers.done_t.copy()
    del service, plane, spy                    # free the program's state
    checks = check.check(config, make_weights(config, seed, sched.jobs),
                         sched, served)
    return ServeRun(
        seconds=seconds, setup_s=t_open - t_start,
        window=(t_open, t_open + seconds), due_abs=t_open + sched.due_s,
        submit_t=submit_t, done_t=done_t, answered=served.answers >= 0,
        rid=rid, records=records,
        counters={k: (before[k], after[k]) for k in before},
        trace=trace, lateness_s=submit_t - (t_open + sched.due_s)), \
        checks, device


def control(cell, seed: int, seconds: float) -> Dict[str, Dict]:
    """The numbers compared for the requests of one window, with each of
    the configuration's controls in the program's place: ``{control name:
    checks}``."""
    sched = serve_schedule(cell.mix, seconds, seed)
    params = make_weights(cell.config, seed, sched.jobs)
    want = check.reference_for(cell.config, params, sched)
    return {name: check.compare(cell.config, sched,
                                check.control_served(cell.config, params,
                                                     sched, **lower), want)
            for name, lower in check.controls(cell.config).items()}


def device_info(devices) -> Dict:
    d0 = devices[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}
