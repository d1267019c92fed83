"""Jit'd public wrappers for the Pallas kernels.

Each wrapper handles layout (the model zoo uses (B, S, H, D); kernels take
(B, H, S, D)), dtype promotion, and backend dispatch: on a TPU the kernels
compile through Mosaic; on a CPU, which has no Mosaic, the model kernels and
the skyline kernel run in interpret mode (Python-level execution of the
kernel body, which the tests check against the references).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import cluster_step as _cs
from repro.kernels import flash_attention as _fa
from repro.kernels import skyline as _sky
from repro.kernels import ssd as _ssd

__all__ = ["flash_attention", "ssd_scan", "arepas_runtimes",
           "cluster_epoch_step", "cluster_impl", "cluster_resize_step"]


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


# ------------------------------------------------------------- attention ---
# Autodiff: Pallas kernels carry no JVP rule, so training wires through a
# custom_vjp — forward is the kernel; backward recomputes through the
# reference formulation under XLA (flash-style backward Pallas kernel is the
# natural next step on real hardware; the roofline analysis accounts for the
# forward kernel only).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, block_q, block_k, interpret):
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    out = _fa.flash_attention_bhsd(qt, kt, vt, causal=causal,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)
    return jnp.swapaxes(out, 1, 2)


def _ref_attention_bshd(q, k, v, causal):
    from repro.kernels.ref import attention_ref_bhsd
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    return jnp.swapaxes(attention_ref_bhsd(qt, kt, vt, causal=causal), 1, 2)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    return _flash_attention(q, k, v, causal, block_q, block_k, interpret), (q, k, v)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda a, b, c: _ref_attention_bshd(a, b, c, causal),
                     q, k, v)
    return vjp(g)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    block_q: int = _fa.DEFAULT_BLOCK_Q,
                    block_k: int = _fa.DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, S, Hq, D); k/v: (B, S, Hkv, D). Returns (B, S, Hq, D)."""
    if interpret is None:
        interpret = _interpret_default()
    return _flash_attention(q, k, v, causal, block_q, block_k, interpret)


# ------------------------------------------------------------------- SSD ---
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_scan(x, dt, A, Bm, Cm, chunk, interpret):
    return _ssd.ssd_chunk_scan(x, dt, A, Bm, Cm, chunk=chunk,
                               interpret=interpret)


def _ssd_ref(x, dt, A, Bm, Cm, chunk):
    from repro.models.layers import ssd_chunked
    return ssd_chunked(x, dt, A, Bm, Cm, chunk)[0]


def _ssd_fwd(x, dt, A, Bm, Cm, chunk, interpret):
    return _ssd_scan(x, dt, A, Bm, Cm, chunk, interpret), (x, dt, A, Bm, Cm)


def _ssd_bwd(chunk, interpret, res, g):
    x, dt, A, Bm, Cm = res
    _, vjp = jax.vjp(lambda *a: _ssd_ref(*a, chunk), x, dt, A, Bm, Cm)
    return vjp(g)


_ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 128,
             interpret: Optional[bool] = None) -> jax.Array:
    """Mamba-2 SSD over (B, S, H, P) values; see kernels/ssd.py."""
    if interpret is None:
        interpret = _interpret_default()
    return _ssd_scan(x, dt, A, Bm, Cm, chunk, interpret)


@functools.partial(jax.jit, static_argnames=("time_block", "interpret"))
def arepas_runtimes(skylines: jax.Array, valid_lens: jax.Array,
                    allocs: jax.Array, *, time_block: int = 512,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Bulk AREPAS: (J, Smax) x (J, K) -> (J, K) simulated runtimes."""
    if interpret is None:
        interpret = _interpret_default()
    return _sky.skyline_runtimes(skylines, valid_lens, allocs,
                                 time_block=time_block, interpret=interpret)


# -------------------------------------------------------- cluster epoch ---
# impl: "pallas" (the f32 Mosaic kernel), "jnp" (the dtype-generic twin,
# float64 under ``jax.enable_x64``), "interpret" (the Pallas body run by the
# interpreter, for tests), or None: "pallas" on a TPU, and on a CPU — where
# no Mosaic kernel can run — the jitted twin. A caller that needs the f64
# twin on the TPU too passes impl="jnp" and says why.
_epoch_step_jit = jax.jit(_cs.epoch_step_ref)
_CLUSTER_IMPLS = ("jnp", "pallas", "interpret")


def cluster_impl(impl: Optional[str] = None) -> str:
    """The kernel body ``impl`` resolves to on this backend."""
    if impl is None:
        return "jnp" if _interpret_default() else "pallas"
    if impl not in _CLUSTER_IMPLS:
        raise ValueError(f"unknown cluster kernel impl {impl!r}; "
                         f"known: {_CLUSTER_IMPLS}")
    return impl


def cluster_epoch_step(end_s: jax.Array, tokens: jax.Array, free: jax.Array,
                       q_tok: jax.Array, q_end: jax.Array, now, *,
                       impl: Optional[str] = None,
                       lease_block: int = _cs.DEFAULT_LEASE_BLOCK):
    """Fused expire -> release -> admit -> scatter over (K, L) lease tables.

    Returns (new_end, new_tok, slot_of, n_admit, adm_tok, freed, n_expired);
    see kernels/cluster_step.py for the contract.
    """
    impl = cluster_impl(impl)
    if impl == "jnp":
        return _epoch_step_jit(end_s, tokens, free, q_tok, q_end,
                               jnp.asarray(now, end_s.dtype))
    return _cs.epoch_step_pallas(end_s, tokens, free, q_tok, q_end, now,
                                 lease_block=lease_block,
                                 interpret=(impl == "interpret"))


@functools.lru_cache(maxsize=None)
def _resize_step_jit(policy, cap: int, epoch_s: float):
    def f(a, b, price, obs, floor, done, cand_tok, cand_end, sky, lens, now):
        return _cs.resize_step_ref(a, b, price, obs, floor, done, cand_tok,
                                   cand_end, sky, lens, now, epoch_s,
                                   policy=policy, cap=cap)
    return jax.jit(f)


def cluster_resize_step(a, b, price, obs, floor, done, cand_tok, cand_end,
                        sky, lens, now, epoch_s, *, policy, cap: int,
                        impl: Optional[str] = None, time_block: int = 512):
    """Fused priced shrink decision + AREPAS re-simulation + repricing.

    Returns (tgt, sel, rt, new_end) per candidate; see cluster_step.py.
    ``policy`` is an AllocationPolicy (hashable — jit caches per policy).
    """
    impl = cluster_impl(impl)
    if impl == "jnp":
        fn = _resize_step_jit(policy, int(cap), float(epoch_s))
        return fn(a, b, price, obs, floor, done, cand_tok, cand_end,
                  sky, lens, jnp.asarray(now, jnp.asarray(a).dtype))
    return _cs.resize_step_pallas(a, b, price, obs, floor, done, cand_tok,
                                  cand_end, sky, lens, now, epoch_s,
                                  policy=policy, cap=cap,
                                  time_block=time_block,
                                  interpret=(impl == "interpret"))
