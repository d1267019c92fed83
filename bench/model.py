"""The deployment's model: weights drawn from the seed, normalization from
the configuration file (``bench/normalization.py`` made it), loaded into
the program's own PCC model object.

A deployment loads a trained model; the benchmark loads one drawn from
``--seed``, in float32 as it is served, made on the device in one jitted
call. A trained head predicts standardized PCC parameters, so the drawn
weights are brought into that range over the pool of plans: the last
layer is shifted and, where the pool's outputs spread wider than [-3, 3],
scaled down until they fit. Only the weights vary with the seed: the
normalization constants are compiled into the served programs, so they
stay fixed and every seed runs the programs the first run compiled.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def weight_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (beyond 32 bits too)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.key(int(word) >> 1)


def _dense(key, fan_in: int, fan_out: int, gain: float) -> Dict:
    kw, kb = jax.random.split(key)
    return {"w": jax.random.normal(kw, (fan_in, fan_out))
            * (gain / np.sqrt(fan_in)),
            "b": 0.1 * jax.random.normal(kb, (fan_out,))}


def _stack(key, dims, first: float, rest: float, prefix: str) -> Dict:
    keys = jax.random.split(key, len(dims) - 1)
    return {f"{prefix}{i}": _dense(k, dims[i], dims[i + 1],
                                   first if i == 0 else rest)
            for i, k in enumerate(keys)}


def init_params(arch: Dict, key: jax.Array) -> Dict:
    """The MLP's parameter tree, with N(0, gain^2 / fan_in) weights:
    ``init_gain`` gives the gain of the first layer and of the others."""
    g = arch["init_gain"]
    return _stack(key, [arch["in_dim"], *arch["hidden"], 2],
                  g["input"], g["hidden"], "l")


Z_SPAN = 3.0


def _fit_head(params: Dict, z: jax.Array) -> Dict:
    """Shift the last layer so the pool's outputs centre on 0, and scale
    it down where their half-range exceeds ``Z_SPAN``."""
    last = params[f"l{len(params) - 1}"]
    lo, hi = z.min(0), z.max(0)
    centre = (lo + hi) / 2
    scale = jnp.minimum(1.0, Z_SPAN / jnp.maximum((hi - lo) / 2, 1e-6))
    last["w"] = last["w"] * scale
    last["b"] = (last["b"] - centre) * scale
    return params


def make_weights(config: Dict, seed: int, jobs) -> Dict:
    """The seed's weights, fitted to the plans ``jobs``."""
    from bench import reference
    norm = config["normalization"]
    inputs = (np.stack([reference.job_vector(j) for j in jobs]),
              np.asarray(norm["feature_mu"], np.float32),
              np.asarray(norm["feature_sd"], np.float32))

    def draw(key, *x):
        params = init_params(config["model"], key)
        return _fit_head(params, reference.nn_forward(params, *x))

    with jax.default_matmul_precision("highest"):
        return jax.jit(draw)(weight_key(seed), *inputs)


def load_model(config: Dict, params: Dict):
    """The program's model object for ``config``, serving ``params``."""
    from repro.core.models import build_model
    from repro.core.models.nn import NNConfig, mlp_apply
    from repro.core.pcc import PCCScaler
    norm = config["normalization"]
    model = build_model("nn", cfg=NNConfig(
        hidden=tuple(config["model"]["hidden"])))
    model._mu = jnp.asarray(norm["feature_mu"], jnp.float32)
    model._sd = jnp.asarray(norm["feature_sd"], jnp.float32)
    model._apply = lambda p, model_in: mlp_apply(p, model_in["features"])
    model.scaler = PCCScaler(**norm["pcc_scaler"])
    model._params = params
    return model
