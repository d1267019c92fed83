"""Property sweep for the fused cluster epoch kernel (hypothesis).

Token conservation, no admission past capacity, and expire-before-admit
ordering must hold for every generated epoch; each case is also checked
against the sequential numpy oracle. Skips cleanly when hypothesis is
absent (see requirements.txt), like tests/test_scheduler_props.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.cluster_step import epoch_step_ref

from tests.test_cluster_step import _OUT_NAMES, _assert_conserved, oracle_epoch

pytest.importorskip("hypothesis", reason="property sweep needs hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st


@st.composite
def epoch_cases(draw):
    K = draw(st.integers(1, 3))
    L = draw(st.sampled_from([4, 8, 16]))
    Q = draw(st.sampled_from([2, 4, 8]))
    now = float(draw(st.integers(10, 200)))
    tok = draw(st.lists(st.integers(0, 12), min_size=K * L, max_size=K * L))
    tokens = np.asarray(tok, np.int64).reshape(K, L)
    ends = draw(st.lists(st.integers(1, 400), min_size=K * L,
                         max_size=K * L))
    end_s = np.where(tokens > 0,
                     np.asarray(ends, np.float64).reshape(K, L), np.inf)
    free = np.asarray(draw(st.lists(st.integers(0, 60), min_size=K,
                                    max_size=K)), np.int64)
    nq = [draw(st.integers(0, Q)) for _ in range(K)]
    q_tok = np.zeros((K, Q), np.int64)
    q_end = np.zeros((K, Q))
    for k in range(K):
        row = draw(st.lists(st.integers(1, 10), min_size=nq[k],
                            max_size=nq[k]))
        q_tok[k, :nq[k]] = row
        q_end[k, :nq[k]] = now + np.arange(1, nq[k] + 1)
    return end_s, tokens, free, q_tok, q_end, now


@settings(max_examples=40, deadline=None)
@given(epoch_cases())
def test_epoch_properties(case):
    end_s, tokens, free, q_tok, q_end, now = case
    with jax.enable_x64(True):
        out = epoch_step_ref(jnp.asarray(end_s, jnp.float64),
                             jnp.asarray(tokens), jnp.asarray(free),
                             jnp.asarray(q_tok), jnp.asarray(q_end),
                             jnp.asarray(now))
    new_end = np.asarray(out[0])
    new_tok = np.asarray(out[1])
    n_admit = np.asarray(out[3])
    adm_tok = np.asarray(out[4])
    freed = np.asarray(out[5])
    # token conservation: no tokens created or destroyed by the step
    _assert_conserved(tokens, out)
    # no admission past capacity: post-step leased tokens fit each shard's
    # budget (whatever was leased before + its free headroom)
    budget = tokens.sum(axis=1) + free
    assert np.all(new_tok.sum(axis=1) <= budget)
    assert np.all(adm_tok <= free + freed)
    # expire-before-admit: nothing in the new table is already expired —
    # expiry ran first, and admitted leases end strictly after now
    assert not np.any((new_tok > 0) & (new_end <= now))
    # the admitted set is a queue prefix
    for k in range(len(n_admit)):
        j = int(n_admit[k])
        assert np.all(q_tok[k, :j] > 0)
    # and it matches the sequential oracle exactly
    orc = oracle_epoch(end_s, tokens, free, q_tok, q_end, now)
    for name, r, o in zip(_OUT_NAMES, out, orc):
        np.testing.assert_array_equal(np.asarray(r), o, err_msg=name)
