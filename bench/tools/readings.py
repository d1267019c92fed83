#!/usr/bin/env python3
"""Readings of the numbers ``correct`` compares, from the program and from
the control, seed by seed in one process: what each limit is set from.

    python bench/tools/readings.py --workload serve-nn-mmpp \
        --seeds 11,12,13 --seconds 10

One JSON line per seed and side ("program", or "control.<name>" for each
of the configuration's controls); standard error has every number
compared on a ``compared:`` line, limited or not.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--sides", default="program,control")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import serving_plane
    from bench.run import enable_cache
    from bench.spec import load_cell, read_metrics
    enable_cache()
    cell = load_cell(args.workload, ROOT)
    sides = args.sides.split(",")
    for seed in (int(s) for s in args.seeds.split(",")):
        if "program" in sides:
            run, checks, _ = serving_plane.run(cell, seed, args.seconds,
                                               False, time.perf_counter())
            e2e = read_metrics(cell.end_to_end, run, cell.bench)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "side": "program", "n": len(run.due_abs),
                              "metrics": {k: v["value"]
                                          for k, v in e2e.items()},
                              "checks": {k: v["value"]
                                         for k, v in checks.items()}}),
                  flush=True)
        if "control" in sides:
            for name, checks in serving_plane.control(
                    cell, seed, args.seconds).items():
                print(json.dumps({"workload": cell.name, "seed": seed,
                                  "side": f"control.{name}",
                                  "checks": {k: v["value"]
                                             for k, v in checks.items()}}),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
