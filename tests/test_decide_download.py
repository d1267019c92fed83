"""Every decide path brings its results to the host in one transfer.

Each compiled decide function returns its outputs packed as the rows of
one float64 array; ``_download`` moves that array in one transfer and casts
the rows back. These tests run every path — policy, priced, fused with and
without observed tokens, the sharded policy and fused stages at K=1 and K=2
— and check that tokens, a, b and runtime come back bitwise equal to the
unpacked outputs of the same math, with their dtypes, and that every
``decide.download`` span made exactly one transfer."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import AllocationRequest, DecisionContext
from repro.core.allocator import (AllocationPolicy, choose_tokens_jnp,
                                  choose_tokens_priced_jnp)
from repro.core.models import build_model
from repro.core.models.nn import NNConfig
from repro.core.pcc import PCCScaler
from repro.obs import Obs
from repro.serve import AllocationService, ShardedAllocationService
from repro.serve.batching import batch_bucket

POLICY = AllocationPolicy(max_slowdown=0.05)
N = 40
# za = 100 decodes to a = -100: the gain cut-off clips at max_tokens;
# za = -20 decodes to a ~ -2e-9: the flat curve takes min_tokens
ZA_MAX, ZA_MIN = 100.0, -20.0


def tiny_model(out_dtype=None):
    """An nn engine whose forward is one float32 affine map of 3 features:
    z = features @ w + c, so a row's features set its (za, zb)."""
    model = build_model("nn", cfg=NNConfig(hidden=()))
    model._mu = jnp.zeros(3, jnp.float32)
    model._sd = jnp.ones(3, jnp.float32)
    model.scaler = PCCScaler(mu_a=0.0, sd_a=1.0, mu_b=0.0, sd_b=1.0)
    model._params = {"w": jnp.asarray([[1.0, 0.0], [0.0, 1.0],
                                       [0.25, -0.125]], jnp.float32),
                     "c": jnp.zeros(2, jnp.float32)}

    def apply(p, model_in):
        z = model_in["features"] @ p["w"] + p["c"]
        return z if out_dtype is None else z.astype(out_dtype)

    model._apply = apply
    return model


def features(seed=0, n=N):
    rng = np.random.RandomState(seed)
    x = np.stack([rng.uniform(-1.0, 5.0, n), rng.uniform(-1.0, 6.0, n),
                  rng.uniform(-2.0, 2.0, n)], 1).astype(np.float32)
    x[0, :] = (ZA_MAX, 1.0, 0.0)
    x[1, :] = (ZA_MIN, 1.0, 0.0)
    return x


def params_ab(seed=0, n=N):
    rng = np.random.RandomState(seed)
    a = rng.uniform(-3.0, -1e-3, n)
    b = np.exp(rng.uniform(-1.0, 9.0, n))
    a[0], a[1] = -1000.0, 0.5          # max_tokens, then min_tokens
    return a, b


def observed(n=N, seed=1):
    obs = np.random.RandomState(seed).randint(2, 7000, n).astype(np.int64)
    obs[1] = POLICY.min_tokens
    obs[0] = POLICY.max_tokens
    return obs


# ------------------------------------------------ the unpacked math ------
def policy_math(price, obs):
    def f(a, b, p, o):
        o = o if obs else None
        toks = (choose_tokens_jnp(a, b, POLICY, o) if not price
                else choose_tokens_priced_jnp(a, b, POLICY, p, o))
        return toks, b * toks.astype(a.dtype) ** a
    return f


def fused_math(model, obs):
    def f(params, x, o):
        a, b = model.scaler.decode(model.serve_apply(params, {"features": x}))
        a64, b64 = a.astype(jnp.float64), b.astype(jnp.float64)
        toks = choose_tokens_jnp(a64, b64, POLICY, o if obs else None)
        return toks, a, b, b64 * toks.astype(jnp.float64) ** a64
    return f


def pad(x, Bp, fill=0):
    out = np.full((Bp,) + x.shape[1:], fill, x.dtype)
    out[:x.shape[0]] = x
    return out


def reference(kind, model, shard_of, floor, K, a=None, b=None, price=None,
              x=None, obs=None):
    """Each shard's rows through the unpacked math at the padded shape the
    service decides them at; (tokens, a, b, runtime) in input order."""
    n = shard_of.size
    Bp = batch_bucket(int(np.bincount(shard_of, minlength=K).max()), floor)
    toks, ra, rb, rt = (np.empty(n, np.int64), None, None,
                        np.empty(n, np.float64))
    for k in range(K):
        m = shard_of == k
        if not m.any():
            continue
        o = pad(obs[m] if obs is not None else np.zeros(m.sum(), np.int64),
                Bp)
        with jax.enable_x64(True):
            if kind == "fused":
                out = jax.jit(fused_math(model, obs is not None))(
                    model.params, jnp.asarray(pad(x[m], Bp)), jnp.asarray(o))
                out = [np.asarray(v)[:m.sum()] for v in out]
                if ra is None:
                    ra = np.empty(n, out[1].dtype)
                    rb = np.empty(n, out[2].dtype)
                toks[m], ra[m], rb[m], rt[m] = out
            else:
                p = pad(price[m] if price is not None
                        else np.ones(m.sum()), Bp, fill=1.0)
                out = jax.jit(policy_math(price is not None,
                                          obs is not None))(
                    jnp.asarray(pad(a[m], Bp)), jnp.asarray(pad(b[m], Bp)),
                    jnp.asarray(p), jnp.asarray(o))
                toks[m], rt[m] = [np.asarray(v)[:m.sum()] for v in out]
    if kind != "fused":
        ra, rb = a, b
    return toks, ra, rb, rt


def same_bits(x, y):
    x, y = np.asarray(x), np.asarray(y)
    return (x.dtype == y.dtype and x.shape == y.shape
            and np.array_equal(np.ascontiguousarray(x).view(np.uint8),
                               np.ascontiguousarray(y).view(np.uint8)))


PATHS = [(kind, obs) for kind in ("policy", "priced", "fused")
         for obs in (False, True)]
SHARDED = [(kind, obs, K) for K in (1, 2)
           for kind, obs in (("policy", True), ("priced", True),
                             ("fused", True), ("fused", False))]


def _decide(svc, kind, obs, shard_of=None):
    a, b = params_ab()
    x = features()
    o = observed() if obs else None
    price = (np.random.RandomState(3).uniform(0.5, 4.0, N)
             if kind == "priced" else None)
    req = (AllocationRequest(model_in={"features": x}, observed_tokens=o)
           if kind == "fused"
           else AllocationRequest(a=a, b=b, observed_tokens=o))
    d = svc.decide(req, DecisionContext(price=price, observed=obs,
                                        shard_of=shard_of))
    return d, dict(a=a, b=b, price=price, x=x, obs=o)


def _check(d, want, kind, model, tracer, calls):
    toks, ra, rb, rt = want
    assert same_bits(d.tokens, toks) and d.tokens.dtype == np.int64
    assert same_bits(d.runtime, rt) and d.runtime.dtype == np.float64
    assert same_bits(d.a, ra) and same_bits(d.b, rb)
    dt = model.params["w"].dtype if kind == "fused" else np.float64
    assert d.a.dtype == dt and d.b.dtype == dt
    assert {POLICY.min_tokens, POLICY.max_tokens} <= set(d.tokens.tolist())
    downloads = [r for r in tracer.records() if r.name == "decide.download"]
    assert len(downloads) == calls
    assert all(r.attrs == {"transfers": 1} for r in downloads)


@pytest.mark.parametrize("kind,obs", PATHS)
def test_single_replica_paths_download_once_bit_for_bit(kind, obs):
    model = tiny_model()
    o = Obs.enabled()
    svc = AllocationService(model, POLICY, obs=o)
    d, inp = _decide(svc, kind, obs)
    want = reference(kind, model, np.zeros(N, np.int64), svc.batch_floor,
                     1, a=inp["a"], b=inp["b"], price=inp["price"],
                     x=inp["x"], obs=inp["obs"])
    _check(d, want, kind, model, o.tracer, calls=1)


@pytest.mark.parametrize("kind,obs,K", SHARDED)
def test_sharded_paths_download_once_bit_for_bit(kind, obs, K):
    model = tiny_model()
    o = Obs.enabled()
    fabric = ShardedAllocationService(AllocationService(model, POLICY, obs=o),
                                      n_shards=K)
    shard_of = np.random.RandomState(5).randint(0, K, N)
    d, inp = _decide(fabric, kind, obs, shard_of)
    want = reference(kind, model, shard_of, fabric.service.batch_floor, K,
                     a=inp["a"], b=inp["b"], price=inp["price"], x=inp["x"],
                     obs=inp["obs"])
    _check(d, want, kind, model, o.tracer, calls=1)
    np.testing.assert_array_equal(d.shard, shard_of)


def test_a_priced_fused_decision_downloads_once_per_stage():
    """The fused stage, then the priced twin on its decoded (a, b): two
    compiled calls, one transfer each."""
    o = Obs.enabled()
    svc = AllocationService(tiny_model(), POLICY, obs=o)
    price = np.full(N, 2.0)
    d = svc.decide(AllocationRequest(model_in={"features": features()}),
                   DecisionContext(price=price))
    assert d.tokens.dtype == np.int64 and d.runtime.dtype == np.float64
    downloads = [r for r in o.tracer.records()
                 if r.name == "decide.download"]
    assert [r.attrs for r in downloads] == [{"transfers": 1}] * 2


def test_a_model_that_decodes_off_its_parameters_dtype_is_refused():
    """The fused layout takes (a, b)'s dtype from the model's parameters;
    a forward that leaves it fails when traced, not with wrong rows."""
    svc = AllocationService(tiny_model(out_dtype=jnp.float64), POLICY)
    with pytest.raises(TypeError, match="decodes"):
        svc.decide(AllocationRequest(model_in={"features": features()}))


def test_shard_map_paths_download_once_per_device_bit_for_bit():
    """K=2 with one device per shard: the fabric takes ``jax.shard_map``
    and still makes one transfer per decide call, bit for bit the lax.map
    path's rows."""
    script = r"""
import numpy as np
from repro.launch.mesh import make_allocation_mesh
from repro.obs import Obs
from repro.serve import AllocationService, ShardedAllocationService
import test_decide_download as t

model = t.tiny_model()
shard_of = np.random.RandomState(5).randint(0, 2, t.N)
for kind, obs in (("policy", True), ("priced", False), ("fused", True)):
    o = Obs.enabled()
    fab = ShardedAllocationService(AllocationService(model, t.POLICY, obs=o),
                                   n_shards=2, mesh=make_allocation_mesh(2))
    assert fab.mesh is not None, "expected the shard_map path"
    d, inp = t._decide(fab, kind, obs, shard_of)
    want = t.reference(kind, model, shard_of, fab.service.batch_floor, 2,
                       a=inp["a"], b=inp["b"], price=inp["price"],
                       x=inp["x"], obs=inp["obs"])
    t._check(d, want, kind, model, o.tracer, calls=1)
print("SHARD_MAP_DOWNLOAD_OK")
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(filter(None, [
                   "src", "tests", os.environ.get("PYTHONPATH")])))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARD_MAP_DOWNLOAD_OK" in proc.stdout
