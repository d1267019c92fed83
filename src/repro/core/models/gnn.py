"""Graph neural network over operator-level features + plan DAG (paper §4.4).

SimGNN-style three-stage architecture (Figure 9):
  1. GCN neighbor aggregation (Kipf-Welling) -> node embeddings;
  2. global-context attention pooling: context c = tanh(mean(H) W_c); node
     attention = sigmoid(H c); graph embedding = attention-weighted sum;
  3. MLP head -> the two scaled PCC parameters.

Operates on padded batches: features (B, N, P), normalized adjacency
(B, N, N), node mask (B, N). Masked nodes contribute nothing to means,
attention, or sums.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.models.nn import init_mlp, mlp_apply

__all__ = ["GNNConfig", "make_gnn", "gnn_apply", "init_gnn"]


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    gcn_dims: Tuple[int, ...] = (64, 64, 32)
    head_hidden: Tuple[int, ...] = (16,)
    seed: int = 0


def init_gnn(rng: jax.Array, in_dim: int, cfg: GNNConfig) -> Dict:
    dims = (in_dim,) + cfg.gcn_dims
    k_gcn, k_ctx, k_head = jax.random.split(rng, 3)
    keys = jax.random.split(k_gcn, len(dims) - 1)
    gcn = {
        f"g{i}": {
            "w": jax.random.normal(k, (dims[i], dims[i + 1])) /
                 math.sqrt(dims[i]),
            "b": jnp.zeros((dims[i + 1],)),
        }
        for i, k in enumerate(keys)
    }
    d = cfg.gcn_dims[-1]
    return {
        "gcn": gcn,
        "w_ctx": jax.random.normal(k_ctx, (d, d)) / math.sqrt(d),
        "head": init_mlp(k_head, d, cfg.head_hidden, 2),
    }


def gnn_apply(params: Dict, model_in: Dict[str, jax.Array]) -> jax.Array:
    """model_in: features (B,N,P), adj (B,N,N), mask (B,N) -> (B,2)."""
    h = model_in["features"]
    adj = model_in["adj"]
    mask = model_in["mask"][..., None]                  # (B, N, 1)

    ng = len(params["gcn"])
    for i in range(ng):
        p = params["gcn"][f"g{i}"]
        h = jnp.einsum("bnm,bmp->bnp", adj, h) @ p["w"] + p["b"]
        h = jax.nn.relu(h)
        h = h * mask                                    # re-zero padded nodes

    # global-context attention pooling
    denom = jnp.maximum(jnp.sum(mask, axis=1), 1.0)     # (B, 1)
    mean_h = jnp.sum(h, axis=1) / denom                 # (B, D)
    ctx = jnp.tanh(mean_h @ params["w_ctx"])            # (B, D)
    att = jax.nn.sigmoid(jnp.einsum("bnd,bd->bn", h, ctx))
    att = att * model_in["mask"]
    g = jnp.einsum("bn,bnd->bd", att, h)                # (B, D)

    return mlp_apply(params["head"], g)


def make_gnn(in_dim: int, cfg: GNNConfig):
    with jax.threefry_partitionable(False):     # as make_nn's weights
        params = init_gnn(jax.random.PRNGKey(cfg.seed), in_dim, cfg)
    return params, gnn_apply
