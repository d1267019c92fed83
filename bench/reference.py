"""Plain reference of one served decision, independent of the program.

plan -> features (paper Tables 1-2) -> PCC model forward -> decode (a, b)
-> bounded-slowdown token policy. Straightforward numpy and ``jax.numpy``;
nothing here imports ``repro``. The forward runs in float32 at the matmul
precision the configuration states (``precision.matmul``; XLA's
``default`` multiplies bfloat16 operands into float32 sums on the TPU),
the decode and the policy in float64. ``dtype`` arguments give the
lower-precision control.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

FORWARD_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


# ------------------------------------------------------------ features --
def job_vector(job) -> np.ndarray:
    """Aggregated job-level features: means of the 7 continuous and 3
    count features, counts of the 39 one-hot categories, #operators and
    #stages."""
    rows = np.stack([op.feature_row() for op in job.operators])
    return np.concatenate([rows[:, :10].mean(0), rows[:, 10:].sum(0),
                           [len(job.operators), len(job.stages)]]
                          ).astype(np.float32)


# ------------------------------------------------------------- forward --
def _dense(x, w, b, dt):
    """x w + b, with operands, sums and result in ``dt``."""
    y = jnp.dot(x.astype(dt), w.astype(dt), preferred_element_type=dt)
    return y + b.astype(dt)


def mlp(params: Dict, x, dt):
    n = len(params)
    for i in range(n):
        x = _dense(x, params[f"l{i}"]["w"], params[f"l{i}"]["b"], dt)
        if i < n - 1:
            x = jnp.maximum(x, 0)
    return x


def nn_forward(params, feats, mu, sd, dt=jnp.float32):
    """(B, 51) raw job features -> (B, 2) scaled PCC parameters."""
    x = (jnp.asarray(feats, jnp.float32) - mu) / sd
    return mlp(params, x.astype(dt), dt).astype(jnp.float32)


def template_z(config: Dict, params, jobs: Sequence, dtype: str = "float32"
               ) -> np.ndarray:
    """Scaled PCC parameters (U, 2) of each plan, on the default device."""
    dt = FORWARD_DTYPES[dtype]
    norm = config["normalization"]
    feats = np.stack([job_vector(j) for j in jobs])
    with jax.default_matmul_precision(config["precision"]["matmul"]):
        z = jax.jit(nn_forward, static_argnums=4)(
            params, feats, np.asarray(norm["feature_mu"], np.float32),
            np.asarray(norm["feature_sd"], np.float32), dt)
    return np.asarray(z, np.float64)


# ------------------------------------------------------- decode, policy --
def decode(z: np.ndarray, scaler: Dict, dtype=np.float64
           ) -> Tuple[np.ndarray, np.ndarray]:
    """a = -softplus(za sd_a + mu_a) < 0 < b = exp(zb sd_b + mu_b)."""
    z = np.asarray(z, dtype)
    a = -np.logaddexp(dtype(0), z[:, 0] * dtype(scaler["sd_a"])
                      + dtype(scaler["mu_a"]))
    b = np.exp(z[:, 1] * dtype(scaler["sd_b"]) + dtype(scaler["mu_b"]))
    return a, b


def policy_tokens(a, b, observed, policy: Dict, dtype=np.float64
                  ) -> np.ndarray:
    """Bounded-slowdown allocation: the marginal-gain point |a|/min_gain,
    raised to the smallest allocation whose predicted runtime b A^a stays
    within (1 + max_slowdown) of the runtime at the ceiling. The ceiling
    is the observed allocation where there is one, else max_tokens."""
    a = np.asarray(a, dtype)
    b = np.asarray(b, dtype)
    lo0, top = policy["min_tokens"], policy["max_tokens"]
    hi = np.where(np.asarray(observed) >= 0, observed, top).astype(np.int64)
    gain = np.clip(np.round(np.abs(a) / dtype(policy["min_gain"])), lo0,
                   hi.astype(dtype)).astype(np.int64)
    gain = np.where(a >= 0, lo0, gain)
    limit = (dtype(1) + dtype(policy["max_slowdown"])) \
        * (b * hi.astype(dtype) ** a)
    lo = np.full(a.shape, lo0, np.int64)
    up = hi.copy()
    while np.any(lo < up):                 # smallest A with runtime <= limit
        mid = (lo + up) // 2
        ok = b * mid.astype(dtype) ** a <= limit
        live = lo < up
        lo = np.where(live & ~ok, mid + 1, lo)
        up = np.where(live & ok, mid, up)
    return np.maximum(np.minimum(gain, top), lo)

