#!/usr/bin/env python3
"""Knee sweep of a serving cell: the same schedule offered at each mean
rate in turn, one process, one JSON line per rate.

    python bench/tools/sweep.py --workload serve-nn-mmpp \
        --rates 1000,2000,4000 --seconds 10 --seed 1

The knee is the highest mean rate at which the backlog never saturated
and every request was answered within a second of the window's close.
The cell's mix then fixes its calm-state rate at 0.8 times the knee.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def mean_factor(arr) -> float:
    """Mean offered rate over the calm-state rate of the MMPP chain."""
    calm, burst = 1.0 / arr["p_burst"], 1.0 / arr["p_calm"]
    return (calm + burst) / (calm + burst / arr["burst_factor"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np
    from bench import serving_plane
    from bench.run import enable_cache
    from bench.spec import load_cell
    enable_cache()
    cell = load_cell(args.workload, ROOT)
    arr = cell.mix["arrivals"]
    for rate in (float(r) for r in args.rates.split(",")):
        arr["rate_qps"] = rate / mean_factor(arr)
        run, checks, _ = serving_plane.run(cell, args.seed, args.seconds,
                                           False, time.perf_counter())
        lat = np.where(run.answered, run.done_t - run.due_abs, np.inf)
        s0, s1 = run.counters["saturations"]
        print(json.dumps({
            "workload": cell.name, "mean_rate": rate,
            "offered_per_s": len(lat) / args.seconds,
            "saturations": s1 - s0,
            "answered_by_close_plus_1s": float(np.mean(
                run.done_t <= run.window[1] + 1.0)),
            "p50_ms": float(np.quantile(lat, 0.5)) * 1e3,
            "p95_ms": float(np.quantile(lat, 0.95)) * 1e3,
            "p99_ms": float(np.quantile(lat, 0.99)) * 1e3,
            "answered_in_window_per_s": float(np.sum(
                run.done_t <= run.window[1])) / args.seconds,
            "lateness_max_s": float(run.lateness_s.max()),
            "setup_s": run.setup_s,
            "checks": {k: v["value"] for k, v in checks.items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
