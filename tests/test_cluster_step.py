"""Fused cluster epoch kernels (repro.kernels.cluster_step) vs a
sequential numpy oracle.

The oracle walks each shard the way the unfused loop does: expire leases,
release their tokens, admit the longest queue prefix that fits BOTH the
free tokens and the open lease slots, scatter admitted leases into free
slots in slot order. The jnp twin must match it exactly in float64; the
Pallas kernel (interpret=True on this CPU container) must match the
float32-cast oracle — end times get cast to f32 *before* the oracle runs,
so the comparison never mixes rounding regimes.

A hypothesis sweep (skipped cleanly when hypothesis is absent, like
tests/test_scheduler_props.py) drives the same oracle with adversarial
queues: token conservation, no admission past capacity, and
expire-before-admit ordering hold for every generated epoch.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.allocator import AllocationPolicy, choose_tokens_priced
from repro.core.arepas import simulate_runtime
from repro.kernels.cluster_step import (
    epoch_step_pallas,
    epoch_step_ref,
    resize_step_pallas,
    resize_step_ref,
)

_OUT_NAMES = ("new_end", "new_tok", "slot_of", "n_admit", "adm_tok",
              "freed", "n_expired")


def oracle_epoch(end_s, tokens, free, q_tok, q_end, now):
    """Sequential per-shard reference: the unfused epoch loop."""
    K, L = end_s.shape
    Q = q_tok.shape[1]
    new_end, new_tok = end_s.copy(), tokens.copy()
    n_admit = np.zeros(K, np.int64)
    adm_tok = np.zeros(K, np.int64)
    freed = np.zeros(K, np.int64)
    n_exp = np.zeros(K, np.int64)
    slot_of = np.full((K, Q), -1, np.int32)
    for k in range(K):
        exp = (new_tok[k] > 0) & (new_end[k] <= now)
        freed[k] = new_tok[k][exp].sum()
        n_exp[k] = exp.sum()
        new_tok[k][exp] = 0
        new_end[k][exp] = np.inf
        avail = free[k] + freed[k]
        slots = np.flatnonzero(new_tok[k] == 0)
        j = s = 0
        for i in range(Q):
            if q_tok[k, i] <= 0 or s + q_tok[k, i] > avail or j >= slots.size:
                break
            s += q_tok[k, i]
            j += 1
        n_admit[k], adm_tok[k] = j, s
        for i in range(j):
            new_tok[k][slots[i]] = q_tok[k, i]
            new_end[k][slots[i]] = q_end[k, i]
            slot_of[k, i] = slots[i]
    return new_end, new_tok, slot_of, n_admit, adm_tok, freed, n_exp


def _random_epoch(rng, K, L, Q, slot_bound=False):
    now = float(rng.uniform(50, 150))
    tokens = rng.integers(0, 20, (K, L))
    tokens[rng.random((K, L)) < 0.3] = 0
    if slot_bound:                      # nearly-full table: slots bind
        tokens[:, :] = rng.integers(1, 20, (K, L))
        tokens[:, :2] = 0
    end_s = np.where(tokens > 0, rng.uniform(0, 300, (K, L)), np.inf)
    free = rng.integers(0, 200, K)
    nq = rng.integers(0, Q + 1, K)
    q_tok = np.zeros((K, Q), np.int64)
    q_end = np.zeros((K, Q))
    for k in range(K):
        q_tok[k, :nq[k]] = rng.integers(1, 15, nq[k])
        q_end[k, :nq[k]] = now + rng.uniform(1, 500, nq[k])
    return end_s, tokens, free, q_tok, q_end, now


def _assert_conserved(tokens, out):
    """Leased + freed - admitted stays balanced across the step."""
    new_tok, adm_tok, freed = out[1], out[4], out[5]
    assert (np.asarray(new_tok).sum()
            == tokens.sum() - np.asarray(freed).sum()
            + np.asarray(adm_tok).sum())


def test_epoch_ref_matches_sequential_oracle():
    rng = np.random.default_rng(0)
    with jax.enable_x64(True):
        for trial in range(12):
            case = _random_epoch(rng, K=int(rng.integers(1, 5)),
                                 L=int(rng.choice([8, 16, 32])),
                                 Q=int(rng.choice([4, 8, 16])),
                                 slot_bound=trial % 3 == 0)
            end_s, tokens, free, q_tok, q_end, now = case
            ref = epoch_step_ref(jnp.asarray(end_s, jnp.float64),
                                 jnp.asarray(tokens), jnp.asarray(free),
                                 jnp.asarray(q_tok), jnp.asarray(q_end),
                                 jnp.asarray(now))
            orc = oracle_epoch(*case)
            for name, r, o in zip(_OUT_NAMES, ref, orc):
                np.testing.assert_array_equal(np.asarray(r), o,
                                              err_msg=f"{trial}:{name}")
            _assert_conserved(tokens, ref)


def test_epoch_pallas_interpret_matches_f32_oracle():
    rng = np.random.default_rng(1)
    for trial in range(6):              # fixed shapes: one interpret trace
        case = _random_epoch(rng, K=2, L=16, Q=8, slot_bound=trial % 2 == 0)
        end_s, tokens, free, q_tok, q_end, now = case
        e32 = end_s.astype(np.float32)
        qe32 = q_end.astype(np.float32)
        n32 = np.float32(now)
        orc = oracle_epoch(e32.astype(np.float64), tokens, free, q_tok,
                           qe32.astype(np.float64), n32)
        pal = epoch_step_pallas(
            jnp.asarray(e32), jnp.asarray(tokens, jnp.int32),
            jnp.asarray(free, jnp.int32), jnp.asarray(q_tok, jnp.int32),
            jnp.asarray(qe32), jnp.asarray(n32),
            lease_block=8, interpret=True)
        for name, r, o in zip(_OUT_NAMES, pal, orc):
            np.testing.assert_allclose(np.asarray(r, np.float64), o,
                                       err_msg=f"{trial}:{name}")


def test_slot_exhaustion_caps_admission_without_leaking_tokens():
    """Regression: tokens may fit many more queries than the lease table
    has open slots. Admission must stop at the slot count — admitting past
    it would subtract tokens for leases that were never scattered, leaking
    them from the pool forever (the replay then spins at now=inf)."""
    K, L, Q = 1, 8, 6
    tokens = np.full((K, L), 5, np.int64)
    tokens[0, :2] = 0                          # exactly two open slots
    end_s = np.where(tokens > 0, 1e6, np.inf)  # nothing expires
    free = np.array([10_000], np.int64)        # tokens are NOT the bound
    q_tok = np.full((K, Q), 3, np.int64)
    q_end = np.full((K, Q), 500.0)
    with jax.enable_x64(True):
        out = epoch_step_ref(jnp.asarray(end_s, jnp.float64),
                             jnp.asarray(tokens), jnp.asarray(free),
                             jnp.asarray(q_tok), jnp.asarray(q_end),
                             jnp.asarray(100.0))
    new_end, new_tok, slot_of, n_admit, adm_tok, freed, n_exp = out
    assert int(n_admit[0]) == 2
    assert int(adm_tok[0]) == 6                # only the scattered tokens
    assert np.asarray(slot_of)[0, :2].tolist() == [0, 1]
    assert np.all(np.asarray(slot_of)[0, 2:] == -1)
    _assert_conserved(tokens, out)


def test_resize_ref_matches_scalar_oracle():
    """The fused resize twin vs the per-candidate scalar path the unfused
    simulator takes: choose_tokens_priced -> simulate_runtime -> reprice."""
    rng = np.random.default_rng(2)
    C, smax, cap = 5, 64, 256
    policy = AllocationPolicy(max_slowdown=0.05)
    lens = rng.integers(8, smax, C).astype(np.int32)
    sky = np.zeros((C, smax), np.float64)
    for i, ln in enumerate(lens):
        sky[i, :ln] = rng.integers(1, 50, ln)
    a = rng.uniform(-0.9, -0.2, C)
    b = lens * rng.uniform(2.0, 10.0, C)
    price = rng.uniform(1.0, 2.0, C)
    obs = rng.integers(8, 200, C).astype(np.float64)
    floor = rng.integers(1, 4, C).astype(np.float64)
    done = rng.uniform(0.0, 0.9, C)
    cand_tok = rng.integers(8, 200, C).astype(np.float64)
    cand_end = rng.uniform(100, 400, C)
    now, epoch_s = 50.0, 8.0
    with jax.enable_x64(True):
        tgt, sel, rt, new_end = resize_step_ref(
            jnp.asarray(a), jnp.asarray(b), jnp.asarray(price),
            jnp.asarray(obs), jnp.asarray(floor), jnp.asarray(done),
            jnp.asarray(cand_tok), jnp.asarray(cand_end),
            jnp.asarray(sky), jnp.asarray(lens), jnp.asarray(now),
            epoch_s, policy=policy, cap=cap)
    for i in range(C):
        want = min(choose_tokens_priced(float(a[i]), float(b[i]), policy,
                                        float(price[i]), int(obs[i])), cap)
        want = max(want, int(floor[i]))
        assert int(np.asarray(tgt)[i]) == want, i
        want_rt = max(simulate_runtime(sky[i, :lens[i]], max(want, 1)), 1)
        assert int(np.asarray(rt)[i]) == want_rt, i
        want_sel = want < cand_tok[i] and (cand_end[i] - now) > epoch_s
        assert bool(np.asarray(sel)[i]) == want_sel, i
        want_end = now + max(round(want_rt * (1.0 - done[i])), 1.0)
        assert float(np.asarray(new_end)[i]) == pytest.approx(want_end), i


def test_resize_pallas_interpret_matches_f32_twin():
    rng = np.random.default_rng(3)
    C, smax, cap = 4, 64, 256
    policy = AllocationPolicy(max_slowdown=0.05)
    lens = rng.integers(8, smax, C).astype(np.int32)
    sky = np.zeros((C, smax), np.float32)
    for i, ln in enumerate(lens):
        sky[i, :ln] = rng.integers(1, 50, ln)
    args = (jnp.asarray(rng.uniform(-0.9, -0.2, C), jnp.float32),
            jnp.asarray(lens * 4.0, jnp.float32),
            jnp.asarray(rng.uniform(1.0, 2.0, C), jnp.float32),
            jnp.asarray(rng.integers(8, 200, C), jnp.float32),
            jnp.asarray(rng.integers(1, 4, C), jnp.float32),
            jnp.asarray(rng.uniform(0.0, 0.9, C), jnp.float32),
            jnp.asarray(rng.integers(8, 200, C), jnp.float32),
            jnp.asarray(rng.uniform(100, 400, C), jnp.float32),
            jnp.asarray(sky), jnp.asarray(lens),
            jnp.asarray(50.0, jnp.float32))
    ref = resize_step_ref(*args, 8.0, policy=policy, cap=cap)
    pal = resize_step_pallas(*args, 8.0, policy=policy, cap=cap,
                             time_block=32, interpret=True)
    for name, r, p in zip(("tgt", "sel", "rt", "new_end"), ref, pal):
        np.testing.assert_allclose(np.asarray(p, np.float64),
                                   np.asarray(r, np.float64),
                                   rtol=1e-6, err_msg=name)
