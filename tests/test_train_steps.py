"""Training substrate: optimizer, grad accumulation, compression."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model_api
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update, cosine_schedule
from repro.optim.compression import compress_int8, compressed_psum, decompress_int8
from repro.train.steps import init_train_state, make_train_step


def test_adamw_descends_quadratic():
    p = {"w": jnp.asarray([5.0, -3.0])}
    opt = adamw_init(p)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                      total_steps=10**9, min_lr_ratio=1.0)
    for _ in range(200):
        g = {"w": 2 * p["w"]}
        p, opt, m = adamw_update(p, g, opt, cfg)
    assert float(jnp.max(jnp.abs(p["w"]))) < 1e-2


def test_cosine_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    lrs = [float(cosine_schedule(cfg, jnp.asarray(s))) for s in range(0, 110, 5)]
    assert lrs[1] < lrs[2]                     # warmup rising
    assert abs(lrs[2] - 1.0) < 0.26            # near peak after warmup
    assert abs(lrs[-1] - 0.1) < 1e-3           # decays to min ratio


def test_grad_clipping_bounds_update():
    p = {"w": jnp.zeros(3)}
    opt = adamw_init(p)
    cfg = AdamWConfig(lr=1e-3, clip_norm=1.0, weight_decay=0.0)
    _, _, m = adamw_update(p, {"w": jnp.asarray([1e6, 0.0, 0.0])}, opt, cfg)
    assert float(m["grad_norm"]) > 1e5         # raw norm reported


def test_grad_accum_matches_large_batch():
    cfg = get_config("qwen2-72b", smoke=True)
    rng = jax.random.PRNGKey(0)
    state = init_train_state(cfg, rng)
    batch = model_api.smoke_batch(cfg, "train", rng, batch=4, seq=32)
    s1, m1 = jax.jit(make_train_step(cfg))(state, batch)
    cfg2 = dataclasses.replace(cfg, grad_accum=2)
    s2, m2 = jax.jit(make_train_step(cfg2))(state, batch)
    # same data, same total gradient (mean over microbatches)
    l1 = jax.tree.leaves(s1.params)
    l2 = jax.tree.leaves(s2.params)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_int8_compression_roundtrip():
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (256,)) * 3.0
    q, s = compress_int8(x)
    y = decompress_int8(q, s)
    assert q.dtype == jnp.int8
    assert float(jnp.max(jnp.abs(x - y))) <= float(s) * 0.51


def test_compressed_psum_error_feedback():
    """Error feedback: quantization residual carried, not lost."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    g = jax.random.normal(jax.random.PRNGKey(1), (64,))
    res = jnp.zeros((64,))

    def f(g, r):
        return compressed_psum(g, r, "pod")

    out, new_res = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=(P(), P()))(g, res)
    # single participant: mean == dequantized value; residual = quant error
    np.testing.assert_allclose(np.asarray(out + new_res), np.asarray(g),
                               atol=1e-5)
