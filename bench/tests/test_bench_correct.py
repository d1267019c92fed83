"""``correct`` holds for a sound run and fails for the control and for a
timed path broken underneath. Everything but the look for a chip runs, on
the CPU, at a size a test run can hold."""
import time

import numpy as np
import pytest

from bench import check, normalization, serving_plane, spec
from bench import run as bench_run

WORKLOAD = "serve-nn-mmpp"
SEED = 2**31 + 12345


def tiny(workload: str = WORKLOAD) -> spec.Cell:
    """The cell with 12 plans at 40 requests/s into 8-row batches."""
    cell = spec.load_cell(workload)
    cell.mix["pool"]["n_unique"] = 12
    cell.mix["arrivals"]["rate_qps"] = 40.0
    cell.config["serving_plane"]["max_batch"] = 8
    return cell


def run_once() -> dict:
    return bench_run.execute(tiny(), SEED, 1.0, False, time.perf_counter())


def break_fused_decide(monkeypatch, alter):
    """Alter what the fused executable produces, in both the AOT-warmed
    and the lazily compiled path."""
    from repro.serve import aot, service
    make = service.make_fused_decide

    def broken(model, policy, with_observed):
        fused = make(model, policy, with_observed)

        def decide(params, model_in, observed):
            return alter(*fused(params, model_in, observed))

        return decide

    monkeypatch.setattr(service, "make_fused_decide", broken)
    monkeypatch.setattr(aot, "make_fused_decide", broken)


def test_a_sound_run_is_correct():
    res = run_once()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 20 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(tiny().config["limits"])


def test_a_token_altered_where_produced_is_caught(monkeypatch):
    break_fused_decide(monkeypatch, lambda toks, a, b, rt:
                       (toks.at[0].add(1), a, b, rt))
    res = run_once()
    assert not res["correct"]
    assert res["checks"]["token_mismatches"]["value"] > 0


@pytest.mark.parametrize("rows,number", [("every", "ab_gap_median"),
                                         ("one", "runtime_gap")])
def test_a_curve_altered_where_produced_is_caught(rows, number, monkeypatch):
    """Every row's a scaled moves the forward's median gap; one row's a
    scaled leaves its row's runtime and tokens disagreeing with it."""
    def alter(toks, a, b, rt):
        return toks, (a * 1.5 if rows == "every" else a.at[0].multiply(1.5)), \
            b, rt
    break_fused_decide(monkeypatch, alter)
    res = run_once()
    assert not res["correct"]
    c = res["checks"][number]
    assert c["value"] > c["limit"]


def test_a_runtime_rounded_to_float32_where_produced_is_caught(monkeypatch):
    import jax.numpy as jnp
    break_fused_decide(monkeypatch, lambda toks, a, b, rt:
                       (toks, a, b, rt.astype(jnp.float32).astype(rt.dtype)))
    res = run_once()
    assert not res["correct"]
    c = res["checks"]["runtime_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 977])
@pytest.mark.parametrize("control,number", [("forward", "ab_gap_median"),
                                            ("policy", "runtime_gap")])
def test_each_control_is_not_correct(control, number, seed):
    checks = serving_plane.control(spec.load_cell(WORKLOAD), seed,
                                   10.0)[control]
    assert not check.is_correct(checks), checks
    assert checks[number]["value"] > checks[number]["limit"]


def test_the_normalization_is_the_benchmarks_own():
    config = spec.load_cell(WORKLOAD).config
    want = normalization.for_config(config)
    got = config["normalization"]
    for k, v in want["pcc_scaler"].items():
        assert got["pcc_scaler"][k] == pytest.approx(v, rel=1e-12)
    for k in ("feature_mu", "feature_sd"):
        np.testing.assert_allclose(got[k], want[k], rtol=1e-12)


def test_a_traced_run_reads_the_program_counters_and_spans():
    res = bench_run.execute(tiny(), SEED, 1.0, True, time.perf_counter())
    assert res["correct"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["hot_path_compiles"] == 0
    assert 1 <= m["batch_queries_mean"] <= 8
    assert m["plane_wait_ms.p95"] > 0 and m["decide_ms.p50"] > 0
    # the CPU has no TPU plane: the device reader finds nothing to read
    assert "device_idle_share.serve" not in m
    assert res["device"]["busy_s"] is None
