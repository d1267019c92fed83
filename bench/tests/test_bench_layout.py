"""The benchmark finds everything by name, and refuses to run off the chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

ROOT = spec.ROOT
BENCH = spec.BENCH


def test_every_cell_resolves_its_files_by_name():
    s = spec.load_spec()
    for w in s["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert os.path.isfile(spec.traffic_file(w["traffic"]))
        assert cell.end_to_end and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.reader(m["name"])), m["name"]
    for c in s["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(s["paths"][0] + "/")


ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_every_entry_has_exactly_its_keys(section):
    for entry in spec.load_spec()[section]:
        keys = set(entry)
        if section in ("end_to_end", "per_layer"):
            keys.discard("workloads")
        assert keys == ENTRY_KEYS[section], entry["name"]


def test_every_metric_has_a_reader_and_every_reader_a_metric():
    s = spec.load_spec()
    names = {m["name"] for m in s["end_to_end"] + s["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics"))
             if f.endswith(".py")}
    assert names == files


def test_new_files_are_picked_up_without_editing_old_ones(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    s = spec.load_spec()
    (root / "bench/configs/tasq-new.json").write_text(json.dumps(
        dict(spec.load_cell("serve-nn-mmpp").config, name="tasq-new")))
    (root / "bench/traffic/mix-new.json").write_text(json.dumps(
        dict(spec.load_cell("serve-nn-mmpp").mix, pick_seed=99)))
    (root / "bench/metrics/new_metric.py").write_text(
        "def read(run):\n    return run['x'] * 2\n")
    s["configs"].append({"name": "tasq-new", "source": "a paper",
                         "file": "bench/configs/tasq-new.json",
                         "reduced": [], "why": "new"})
    s["workloads"].append({"name": "serve-new", "config": "tasq-new",
                           "traffic": "mix-new", "chips": 1, "why": "new"})
    s["per_layer"].append({"name": "new_metric", "unit": "count",
                           "better": "lower", "source": "program_counter",
                           "layer": "decide", "moves": "decision_p95_ms",
                           "workloads": ["serve-new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    cell = spec.load_cell("serve-new", str(root))
    assert cell.config["name"] == "tasq-new"
    assert cell.mix["pick_seed"] == 99
    assert "new_metric" in [m["name"] for m in cell.per_layer]
    assert "new_metric" not in [
        m["name"] for m in spec.load_cell("serve-nn-mmpp",
                                          str(root)).per_layer]
    got = spec.read_metrics([m for m in cell.per_layer
                             if m["name"] == "new_metric"], {"x": 3},
                            cell.bench)
    assert got == {"new_metric": {"value": 6.0, "unit": "count"}}


def _run_bench(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve-nn-mmpp",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    out = _run_bench(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_refuses_to_run_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = _run_bench(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout == ""


@pytest.mark.parametrize("name", ["decision_p95_ms", "decisions_per_s"])
def test_a_request_never_answered_counts_against_the_window(name):
    import numpy as np
    from types import SimpleNamespace
    due = np.arange(20.0) * 0.05
    run = SimpleNamespace(
        due_abs=due, done_t=due + 0.001, answered=np.ones(20, bool),
        window=(0.0, 1.0), seconds=1.0)
    full = spec.reader(name)(run)
    run.answered[:2] = False
    run.done_t[:2] = np.inf
    part = spec.reader(name)(run)
    if name == "decisions_per_s":
        assert (full, part) == (20.0, 18.0)
    else:
        assert full == pytest.approx(1.0) and part is None
