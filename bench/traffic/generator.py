"""The one traffic generator: SCOPE-like plans, MMPP arrivals, Zipf picks.

Every mix under ``bench/traffic/*.json`` is a set of parameters for this
module; a new mix is a new data file, not new code. The plan sampler, the
executor that gives each plan its observed skyline, the MMPP arrival chain
and the Zipf popularity are copies of ``repro.workloads.generator`` and
``repro.workloads.executor``, so a change to those modules does not move
the yardstick. The system under test receives only what this module makes.

Seeds: the template pool, the arrival gaps and the multiset of template
picks come from the mix's own fixed seeds, so every ``--seed`` offers the
same work: the same plans, the same arrival instants, the same number of
first sightings. ``--seed`` permutes which plan arrives at which instant.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, List, Tuple

import numpy as np

NUM_OP_TYPES = 35
NUM_PARTITION_TYPES = 4
MAX_TOKENS = 6287
_ENGINE_SEED = 20210415


def _engine_truth_tables(seed: int) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.RandomState(seed)
    coeff = np.exp(rng.uniform(-1.5, 1.5, NUM_OP_TYPES))
    selectivity = np.clip(rng.lognormal(-0.3, 0.6, NUM_OP_TYPES), 0.05, 2.0)
    return coeff, selectivity


OP_COST_COEFF, OP_SELECTIVITY = _engine_truth_tables(_ENGINE_SEED)


@dataclasses.dataclass
class Operator:
    """One node of the plan DAG, with the paper's Table 2 features."""
    op_type: int
    partition_type: int
    est_cardinality: float
    input_cardinality: float
    input_children_cardinality: float
    avg_row_length: float
    est_cost: float
    est_exclusive_cost: float
    est_total_cost: float
    num_partitions: int
    num_partitioning_columns: int
    num_sort_columns: int

    def feature_row(self) -> np.ndarray:
        cont = np.log1p([
            self.est_cardinality, self.input_cardinality,
            self.input_children_cardinality, self.avg_row_length,
            self.est_cost, self.est_exclusive_cost, self.est_total_cost,
        ])
        cnt = [np.log2(1.0 + self.num_partitions),
               self.num_partitioning_columns, self.num_sort_columns]
        op_1h = np.zeros(NUM_OP_TYPES)
        op_1h[self.op_type] = 1.0
        pt_1h = np.zeros(NUM_PARTITION_TYPES)
        pt_1h[self.partition_type] = 1.0
        return np.concatenate([cont, cnt, op_1h, pt_1h]).astype(np.float32)


@dataclasses.dataclass
class Stage:
    op_ids: List[int]
    num_tasks: int
    task_duration: int
    deps: List[int]


@dataclasses.dataclass
class Job:
    job_id: int
    operators: List[Operator]
    edges: List[Tuple[int, int]]
    stages: List[Stage]
    default_tokens: int

    def num_operators(self) -> int:
        return len(self.operators)

    def num_stages(self) -> int:
        return len(self.stages)


def _sample_stage_chain(trng, irng, n_ops, input_card, nparts):
    ops: List[Operator] = []
    card = input_card
    child_card = input_card
    total_cost_acc = 0.0
    for _ in range(n_ops):
        ot = int(trng.randint(NUM_OP_TYPES))
        out_card = max(1.0, card * OP_SELECTIVITY[ot])
        row_len = float(np.clip(trng.lognormal(4.2, 0.7), 8, 4096))
        true_cost = card * OP_COST_COEFF[ot] * row_len * 1e-6
        noisy = lambda x: float(x * irng.lognormal(0.0, 0.35))
        exc = noisy(true_cost)
        total_cost_acc += exc
        ops.append(Operator(
            op_type=ot,
            partition_type=int(trng.randint(NUM_PARTITION_TYPES)),
            est_cardinality=noisy(out_card),
            input_cardinality=noisy(card),
            input_children_cardinality=noisy(child_card),
            avg_row_length=row_len,
            est_cost=noisy(true_cost),
            est_exclusive_cost=exc,
            est_total_cost=total_cost_acc,
            num_partitions=nparts,
            num_partitioning_columns=int(trng.randint(0, 4)),
            num_sort_columns=int(trng.randint(0, 5)),
        ))
        child_card = card
        card = out_card
    return ops, card


def sample_job(job_id: int, rng: np.random.RandomState) -> Job:
    """One SCOPE-like job: a DAG of stages, each a chain of operators."""
    trng = np.random.RandomState(rng.randint(2**31 - 1))
    n_stages = 1 + min(int(trng.geometric(0.30)), 11)
    operators: List[Operator] = []
    edges: List[Tuple[int, int]] = []
    stages: List[Stage] = []
    stage_out_card: List[float] = []
    stage_last_op: List[int] = []
    base_card = float(np.clip(trng.lognormal(15.2, 1.2), 1e3, 3e10))
    inst_scale = float(rng.lognormal(0.0, 0.5))
    for sid in range(n_stages):
        if sid == 0:
            deps: List[int] = []
            input_card = base_card * inst_scale
        else:
            k = 1 + int(trng.rand() < 0.3)
            deps = sorted(trng.choice(sid, size=min(k, sid),
                                      replace=False).tolist())
            input_card = float(sum(stage_out_card[d] for d in deps))
        nparts = int(2 ** np.clip(
            np.round(np.log2(max(input_card, 1.0) / 5e4)
                     + trng.uniform(-1.0, 1.0)), 0, 13))
        n_ops = 1 + int(trng.geometric(0.45))
        ops, out_card = _sample_stage_chain(trng, rng, min(n_ops, 6),
                                            input_card, nparts)
        base = len(operators)
        operators.extend(ops)
        for i in range(len(ops) - 1):
            edges.append((base + i, base + i + 1))
        for d in deps:
            edges.append((stage_last_op[d], base))
        width = int(np.clip(nparts, 1, MAX_TOKENS))
        rows_per_task = input_card / nparts
        coeff = float(np.mean([OP_COST_COEFF[o.op_type] for o in ops]))
        dur = int(np.clip(round(rows_per_task * coeff * 8e-4
                                * rng.lognormal(0.0, 0.25)), 1, 1200))
        stages.append(Stage(op_ids=list(range(base, base + len(ops))),
                            num_tasks=width, task_duration=dur, deps=deps))
        stage_out_card.append(out_card)
        stage_last_op.append(base + len(ops) - 1)
    peak = max(s.num_tasks for s in stages)
    if rng.rand() < 0.5:
        default = int(rng.choice([20, 50, 100, 200, 500],
                                 p=[0.15, 0.35, 0.30, 0.15, 0.05]))
    else:
        default = int(np.clip(round(peak * rng.lognormal(0.0, 0.6)),
                              1, MAX_TOKENS))
    return Job(job_id=job_id, operators=operators, edges=edges,
               stages=stages, default_tokens=max(1, default))


def observed_skyline(job: Job) -> np.ndarray:
    """Tokens in use each second when ``job`` runs at its default tokens
    under a work-conserving FIFO list scheduler (no noise)."""
    tokens = job.default_tokens
    nstages = len(job.stages)
    pending = [s.num_tasks for s in job.stages]
    unfinished = [s.num_tasks for s in job.stages]
    ndeps = [len(s.deps) for s in job.stages]
    children: List[List[int]] = [[] for _ in range(nstages)]
    for sid, s in enumerate(job.stages):
        for d in s.deps:
            children[d].append(sid)
    ready = [sid for sid in range(nstages) if ndeps[sid] == 0]
    free = tokens
    events: List[Tuple[int, int, int, int]] = []
    seq = 0
    intervals: List[Tuple[int, int, int]] = []

    def schedule(now: int) -> None:
        nonlocal free, seq
        i = 0
        while free > 0 and i < len(ready):
            sid = ready[i]
            if pending[sid] == 0:
                i += 1
                continue
            n = min(pending[sid], free)
            pending[sid] -= n
            free -= n
            dur = job.stages[sid].task_duration
            heapq.heappush(events, (now + dur, seq, sid, n))
            seq += 1
            intervals.append((now, now + dur, n))
            if pending[sid] == 0:
                i += 1

    schedule(0)
    while events:
        t, _, sid, n = heapq.heappop(events)
        free += n
        unfinished[sid] -= n
        if unfinished[sid] == 0:
            for c in children[sid]:
                ndeps[c] -= 1
                if ndeps[c] == 0:
                    ready.append(c)
        if not events or events[0][0] != t:
            ready[:] = [s for s in ready if pending[s] > 0]
            schedule(t)
    runtime = max(end for _, end, _ in intervals)
    diff = np.zeros(runtime + 1, np.int64)
    for s, e, n in intervals:
        diff[s] += n
        diff[e] -= n
    return np.cumsum(diff)[:runtime].astype(np.int32)


def template_pool(n_unique: int, seed: int, max_skyline_s: int = 16384
                  ) -> List[Job]:
    """The unique plans, as ``TraceGenerator`` builds its pool: a plan
    whose observed run is longer than ``max_skyline_s`` is drawn again."""
    g = np.random.default_rng(np.random.SeedSequence(seed).spawn(5)[0])
    jobs = []
    for u in range(n_unique):
        for _ in range(32):
            rng = np.random.RandomState(int(g.integers(2**31 - 1)))
            job = sample_job(u, rng)
            if len(observed_skyline(job)) <= max_skyline_s:
                break
        jobs.append(job)
    return jobs


def mmpp_arrivals(seconds: float, rate_qps: float, burst_factor: float,
                  p_burst: float, p_calm: float, seed: int) -> np.ndarray:
    """Arrival offsets in [0, seconds) of a Markov-modulated Poisson
    process: a calm state at ``rate_qps`` and a burst state at
    ``rate_qps * burst_factor``, switching with ``p_burst`` / ``p_calm``
    per event."""
    g = np.random.default_rng(seed)
    out = []
    t = 0.0
    burst = False
    while True:
        rate = rate_qps * (burst_factor if burst else 1.0)
        t += g.exponential(1.0 / rate)
        if t >= seconds:
            return np.asarray(out, np.float64)
        out.append(t)
        burst = (g.random() < p_burst if not burst
                 else g.random() >= p_calm)


def zipf_picks(n: int, n_unique: int, exponent: float, seed: int
               ) -> np.ndarray:
    """``n`` template indices drawn from Zipf weights over a shuffled
    rank order."""
    g = np.random.default_rng(seed)
    ranks = g.permutation(n_unique)
    p = (1.0 + ranks) ** -exponent
    return g.choice(n_unique, size=n, p=p / p.sum()).astype(np.int64)


@dataclasses.dataclass
class ServeSchedule:
    """An open-loop request schedule: request ``i`` is due at
    ``due_s[i]`` after the window opens and asks about plan
    ``jobs[pick[i]]``; ``observed[i]`` is the tokens its template ran
    with before, or -1 on the template's first sighting."""
    jobs: List[Job]
    due_s: np.ndarray
    pick: np.ndarray
    observed: np.ndarray

    def __len__(self) -> int:
        return len(self.due_s)


def serve_schedule(mix: Dict, seconds: float, seed: int) -> ServeSchedule:
    """The requests of one window of ``mix`` for ``--seed seed``."""
    pool = mix["pool"]
    arr = mix["arrivals"]
    jobs = template_pool(pool["n_unique"], pool["seed"],
                         pool.get("max_skyline_s", 16384))
    due = mmpp_arrivals(seconds, arr["rate_qps"], arr["burst_factor"],
                        arr["p_burst"], arr["p_calm"], arr["seed"])
    picks = zipf_picks(len(due), pool["n_unique"], mix["zipf_exponent"],
                       mix["pick_seed"])
    picks = np.random.default_rng(seed).permutation(picks)
    observed = np.full(len(picks), -1, np.int64)
    seen = np.zeros(len(jobs), bool)
    for i, u in enumerate(picks):
        if seen[u]:
            observed[i] = jobs[u].default_tokens
        seen[u] = True
    return ServeSchedule(jobs=jobs, due_s=due, pick=picks, observed=observed)
