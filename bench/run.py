#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` at the root of the checkout;
its configuration, traffic mix and metric readers are files under
``bench/`` found by name. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a run with the program's
tracer and the JAX profiler on. The last line of standard output is one
JSON object; the numbers compared with the reference are the last lines of
standard error and the last key of that object. Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
PLATFORM = "tpu"


def fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def enable_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed place
    (``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` in the checkout),
    every program in it, however fast it compiled."""
    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def execute(cell, seed: int, seconds: float, traced: bool,
            t_start: float) -> dict:
    """Set up, measure and check one run of ``cell``; the result object."""
    import importlib

    import numpy as np
    from bench import check, devtrace
    from bench.spec import read_metrics
    runner = importlib.import_module(f"bench.{cell.mix['runner']}")
    with tempfile.TemporaryDirectory() as trace_dir:
        run, checks, device = runner.run(cell, seed, seconds, traced,
                                         t_start, trace_dir)
    metrics = read_metrics(cell.per_layer if traced else cell.end_to_end,
                           run, cell.bench)
    result = {"correct": check.is_correct(checks),
              "attempted": int(len(run.due_abs)),
              "failed": int(checks["unanswered"]["value"]),
              "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = devtrace.busy_s(run.trace)
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": devtrace.top_ops(run.trace),
                               "idle_gaps": devtrace.idle_gaps(run.trace)}
    late = run.lateness_s
    print(f"generator lateness: median {np.median(late):.6f} s, max "
          f"{late.max():.6f} s over {late.size} requests", file=sys.stderr,
          flush=True)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench.spec import load_cell
    cell = load_cell(args.workload, ROOT)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        return fail(f"the program is missing: no package at {SRC}/repro")
    sys.path.insert(0, SRC)
    import jax
    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        return fail(f"no TPU: JAX's default device is "
                    f"{devices[0].platform}; this benchmark runs on the chip")
    if len(devices) < cell.chips:
        return fail(f"{cell.chips} chips needed, {len(devices)} found")
    print(f"set-up: interpreter, imports and the TPU client "
          f"{time.perf_counter() - T_START:.3f} s", file=sys.stderr,
          flush=True)
    enable_cache()
    result = execute(cell, args.seed, args.seconds, bool(args.trace), T_START)
    from bench.check import print_checks
    print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
