"""Feed-forward NN over aggregated job-level features -> scaled PCC params.

Also hosts the generic minibatch trainer (`fit_model`) shared with the GNN:
jit-compiled Adam steps via the framework's own optimizer (repro.optim), one
of the three §4.5 losses, deterministic shuffling.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.losses import LossWeights, make_loss
from repro.core.pcc import PCCScaler
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

__all__ = ["NNConfig", "init_mlp", "mlp_apply", "fit_model", "param_count"]


@dataclasses.dataclass(frozen=True)
class NNConfig:
    hidden: Tuple[int, ...] = (32, 16)
    lr: float = 3e-3
    epochs: int = 60
    batch_size: int = 256
    loss: str = "lf2"
    weights: LossWeights = LossWeights()
    seed: int = 0


def init_mlp(rng: jax.Array, in_dim: int, hidden: Tuple[int, ...],
             out_dim: int = 2) -> Dict:
    dims = (in_dim,) + tuple(hidden) + (out_dim,)
    keys = jax.random.split(rng, len(dims) - 1)
    return {
        f"l{i}": {
            "w": jax.random.normal(k, (dims[i], dims[i + 1])) *
                 (1.0 / math.sqrt(dims[i])),
            "b": jnp.zeros((dims[i + 1],)),
        }
        for i, k in enumerate(keys)
    }


def mlp_apply(params: Dict, x: jax.Array) -> jax.Array:
    n = len(params)
    for i in range(n):
        p = params[f"l{i}"]
        x = x @ p["w"] + p["b"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def param_count(params: Any) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(params)))


def fit_model(apply_fn: Callable, params: Any, inputs: Dict[str, np.ndarray],
              batch_extras: Dict[str, np.ndarray], scaler: PCCScaler,
              cfg: NNConfig) -> Tuple[Any, Dict[str, Any]]:
    """Generic trainer for PCC-parameter models.

    apply_fn(params, model_inputs) -> (B, 2) scaled predictions.
    inputs: arrays the model consumes (all shaped (N, ...)).
    batch_extras: target_z / observed_alloc / observed_runtime / xgb_runtime.
    Returns (trained params, history {loss curves, epoch_time_s}).
    """
    loss_fn = make_loss(cfg.loss, scaler, cfg.weights)
    opt_cfg = AdamWConfig(lr=cfg.lr, weight_decay=0.0, clip_norm=1.0,
                          warmup_steps=20, total_steps=10**9)  # flat lr
    opt = adamw_init(params)

    n = next(iter(batch_extras.values())).shape[0]
    nb = max(1, n // cfg.batch_size)

    @jax.jit
    def step(params, opt, model_in, extras):
        def f(p):
            pred = apply_fn(p, model_in)
            return loss_fn(pred, extras)
        (_, metrics), grads = jax.value_and_grad(f, has_aux=True)(params)
        params, opt, _ = adamw_update(params, grads, opt, opt_cfg)
        return params, opt, metrics

    rng = np.random.RandomState(cfg.seed)
    history = {"loss": [], "epoch_time_s": []}
    for _ in range(cfg.epochs):
        t0 = time.time()
        order = rng.permutation(n)
        ep_loss = 0.0
        for b in range(nb):
            sel = order[b * cfg.batch_size:(b + 1) * cfg.batch_size]
            model_in = {k: jnp.asarray(v[sel]) for k, v in inputs.items()}
            extras = {k: jnp.asarray(v[sel]) for k, v in batch_extras.items()}
            params, opt, m = step(params, opt, model_in, extras)
            ep_loss += float(m["loss"])
        history["loss"].append(ep_loss / nb)
        history["epoch_time_s"].append(time.time() - t0)
    return params, history


def make_nn(in_dim: int, cfg: NNConfig):
    """Returns (params, apply) for the job-level-feature MLP."""
    # Initial weights come from the non-partitionable threefry stream: the
    # one the engines were tuned on. jax 0.5 made the partitionable stream
    # the default, which draws other weights from the same seed.
    with jax.threefry_partitionable(False):
        params = init_mlp(jax.random.PRNGKey(cfg.seed), in_dim, cfg.hidden)

    def apply(p, model_in):
        return mlp_apply(p, model_in["features"])
    return params, apply
