#!/usr/bin/env python3
"""The deployment's normalization, made from the benchmark's own corpus.

    python bench/normalization.py bench/configs/tasq-nn-k1.json

A trained PCC model standardizes its inputs and its targets with constants
fit on its training corpus. The configuration's ``normalization`` holds
them, made here with plain numpy from the copied plan sampler (never by
the program under test): the corpus is ``corpus.n_train`` plans of the
pool seeded ``corpus.seed``. Feature mean and standard deviation are over
the reference's job vectors. The PCC of each plan is the least-squares
line of log runtime on log tokens, where the runtime at ``A`` tokens is the
copied list scheduler's run at ``A`` and ``A`` takes the fractions
``PCC_GRID`` of the plan's widest stage; plans whose grid gives one
allocation have no curve and are left out. The PCC scaler standardizes
``softplus^-1(-a)`` and ``log b`` over those curves. Prints the
``normalization`` object.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
from typing import Dict, List, Sequence

import numpy as np

PCC_GRID = (1 / 16, 1 / 8, 1 / 4, 1 / 2, 1.0)
A_MAX = -1e-3                 # a curve that does not fall is held at this


def softplus_inv(y: np.ndarray) -> np.ndarray:
    return y + np.log1p(-np.exp(-y))


def plan_pcc(job) -> List[float]:
    """(a, b) of ``runtime = b * tokens ** a`` fit to the plan's runs over
    the grid, or [] where the grid gives one allocation."""
    from bench.traffic.generator import observed_skyline
    peak = max(s.num_tasks for s in job.stages)
    toks = np.unique(np.maximum(1, np.round(peak * np.asarray(PCC_GRID))))
    if toks.size < 2:
        return []
    runtime = [len(observed_skyline(dataclasses.replace(
        job, default_tokens=int(t)))) for t in toks]
    a, log_b = np.polyfit(np.log(toks), np.log(runtime), 1)
    return [min(float(a), A_MAX), float(np.exp(log_b))]


def fit(jobs: Sequence) -> Dict:
    from bench.reference import job_vector
    feats = np.stack([job_vector(j) for j in jobs]).astype(np.float64)
    curves = np.array([c for c in map(plan_pcc, jobs) if c])
    ra, rb = softplus_inv(-curves[:, 0]), np.log(curves[:, 1])
    return {
        "pcc_scaler": {"mu_a": float(ra.mean()), "sd_a": float(ra.std()),
                       "mu_b": float(rb.mean()), "sd_b": float(rb.std())},
        "feature_mu": feats.mean(0).tolist(),
        "feature_sd": feats.std(0).tolist(),
    }


def for_config(config: Dict) -> Dict:
    from bench.traffic.generator import template_pool
    corpus = config["corpus"]
    return fit(template_pool(corpus["n_train"], corpus["seed"]))


def main(argv=None) -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = (argv or sys.argv[1:])[0]
    with open(path) as f:
        print(json.dumps(for_config(json.load(f)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
