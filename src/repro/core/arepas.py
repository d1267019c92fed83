"""AREPAS — Area-Preserving Allocation Simulator (paper §3, Algorithm 1).

Given one observed resource-consumption skyline (token usage per second),
synthesize the skyline — and hence the runtime — the same job would have at a
*lower* token allocation, under the core assumption that total work
(token-seconds = area under the skyline) is conserved.

Algorithm 1, faithfully:
  1. find the timestamps where the skyline crosses the new allocation ``Nt``;
  2. split the skyline into contiguous sections entirely over / under ``Nt``;
  3. under-cap sections are copied unchanged;
  4. over-cap sections are flattened to height ``Nt`` and stretched to
     ``int(area / Nt)`` seconds (area-preserving up to integer truncation);
  5. concatenate sections in order.

Two implementations:
  * ``simulate_skyline`` / ``simulate_runtime``: exact numpy oracle
    (reference semantics, returns the full simulated skyline).
  * ``simulate_runtime_jax``: fully vectorized jnp version (segment-sum over
    crossing-delimited sections) that jits/vmaps for bulk augmentation of
    thousands of jobs; bitwise-equal runtimes vs the oracle (see
    tests/test_arepas.py hypothesis sweep).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "floor_div",
    "simulate_skyline",
    "simulate_runtime",
    "simulate_runtime_jax",
    "simulate_runtime_batch",
    "simulate_runtime_batch_jit",
    "augmentation_grid",
    "skyline_area",
    "peak_allocation",
]


# ------------------------------------------------------------- numpy oracle --
def simulate_skyline(skyline: np.ndarray, new_alloc: int) -> np.ndarray:
    """Algorithm 1: simulate the skyline at allocation ``new_alloc``.

    skyline: (S,) non-negative per-second token usage of the observed run.
    Returns the simulated per-second skyline (length = simulated runtime).
    """
    sog = np.asarray(skyline, dtype=np.float64)
    assert sog.ndim == 1 and sog.size > 0, sog.shape
    nt = float(new_alloc)
    assert nt > 0, new_alloc

    # sectionStartIDs: crossings of the allocation threshold.
    sign = np.sign(sog - nt)
    starts = [0] + [i for i in range(1, len(sog)) if sign[i] != sign[i - 1]]
    starts.append(len(sog))

    out = []
    for lo, hi in zip(starts[:-1], starts[1:]):
        sec = sog[lo:hi]
        if sec[0] > nt:  # over-allocated: flatten at Nt, stretch to area/Nt
            sec_area = float(np.sum(sec))
            new_len = int(sec_area / nt)
            out.append(np.full(new_len, nt))
        else:            # under the new cap: copy verbatim
            out.append(sec)
    return np.concatenate(out) if out else np.zeros(0)


def simulate_runtime(skyline: np.ndarray, new_alloc: int) -> int:
    """Simulated runtime (seconds) at ``new_alloc`` — len of Algorithm 1 output."""
    return int(simulate_skyline(skyline, new_alloc).size)


def skyline_area(skyline: np.ndarray) -> float:
    """Total work in token-seconds (the conserved quantity)."""
    return float(np.sum(np.asarray(skyline, dtype=np.float64)))


def peak_allocation(skyline: np.ndarray) -> int:
    return int(np.max(np.asarray(skyline)))


# ------------------------------------------------------------ jax vectorized --
def floor_div(x, n):
    """Exact ``floor(x / n)`` for integer-valued f32 ``x >= 0`` and
    ``n >= 1`` below 2^24: the quotient is corrected by exact integer
    products, so it holds whether or not the backend's division is
    correctly rounded."""
    q = jnp.floor(x / n)
    q = jnp.where(q * n > x, q - 1.0, q)
    return jnp.where((q + 1.0) * n <= x, q + 1.0, q)


def simulate_runtime_jax(skyline: jax.Array, valid_len: jax.Array,
                         new_alloc: jax.Array) -> jax.Array:
    """Vectorizable/jittable runtime simulation (exact vs the numpy oracle).

    skyline:   (Smax,) fixed-size padded per-second usage (pad with anything;
               only the first ``valid_len`` entries count).
    valid_len: () int32 — true skyline length.
    new_alloc: () — allocation to simulate.

    Section decomposition without data-dependent shapes: a section id per
    second via cumsum of sign-change indicators; over-section areas via
    segment_sum; runtime = (#under seconds) + sum_over floor(area / Nt).

    Exactness: skylines are integer token counts, so areas are integers
    (< 2^24, exactly representable in f32), and ``floor_div`` takes their
    exact integer floor — bitwise-equal to the numpy/f64 oracle.
    """
    s = skyline.astype(jnp.float32)
    smax = s.shape[0]
    idx = jnp.arange(smax)
    valid = idx < valid_len
    nt = new_alloc.astype(jnp.float32)

    sign = jnp.sign(s - nt)
    prev = jnp.concatenate([sign[:1], sign[:-1]])
    boundary = jnp.where(valid & (idx > 0), sign != prev, False)
    seg_id = jnp.cumsum(boundary.astype(jnp.int32))

    over = (s > nt) & valid
    under = (~(s > nt)) & valid

    # Over-section areas; a segment is "over" iff any of its seconds is over
    # (sections are homogeneous by construction, so any == all).
    seg_area = jax.ops.segment_sum(jnp.where(over, s, 0.0), seg_id,
                                   num_segments=smax)
    seg_is_over = jax.ops.segment_max(over.astype(jnp.int32), seg_id,
                                      num_segments=smax)
    over_len = jnp.sum(floor_div(seg_area, nt) * seg_is_over)
    return (over_len + jnp.sum(under)).astype(jnp.int32)


def simulate_runtime_batch(skylines: jax.Array, valid_lens: jax.Array,
                           allocs: jax.Array) -> jax.Array:
    """(J, Smax) skylines x (J, K) allocations -> (J, K) runtimes (jit+vmap)."""
    fn = jax.vmap(jax.vmap(simulate_runtime_jax, in_axes=(None, None, 0)),
                  in_axes=(0, 0, 0))
    return fn(skylines, valid_lens, allocs)


simulate_runtime_batch_jit = jax.jit(simulate_runtime_batch)
_sim_batch_jit = simulate_runtime_batch_jit   # back-compat alias


# -------------------------------------------------------- augmentation grid --
def augmentation_grid(observed_tokens: int,
                      fractions: Sequence[float] = (1.0, 0.8, 0.6, 0.2),
                      ) -> np.ndarray:
    """Token allocations to synthesize for one job (paper re-executes at
    100/80/60/20% and trains XGBoost with 80/60% + over-allocated 120/140%)."""
    allocs = np.unique(np.maximum(
        1, np.round(np.asarray(fractions) * observed_tokens)).astype(np.int64))
    return allocs[::-1]  # descending: full allocation first


def augment_job(skyline: np.ndarray,
                observed_tokens: int,
                fractions: Sequence[float] = (1.0, 0.8, 0.6, 0.4, 0.2),
                over_fractions: Sequence[float] = (1.2, 1.4),
                ) -> Tuple[np.ndarray, np.ndarray]:
    """AREPAS-augment one job: returns (allocs, runtimes).

    Below the observed allocation runtimes come from Algorithm 1; above it
    ("over-allocated jobs") the runtime is floored at the peak-allocation
    runtime (paper §4.4) — more tokens than the peak cannot help.
    """
    base_runtime = len(skyline)
    allocs, runtimes = [], []
    for f in sorted(set(fractions) | set(over_fractions)):
        a = max(1, int(round(f * observed_tokens)))
        if f >= 1.0:
            r = base_runtime if f == 1.0 else base_runtime  # floored at peak
        else:
            r = simulate_runtime(skyline, a)
        allocs.append(a)
        runtimes.append(r)
    return np.asarray(allocs, np.int64), np.asarray(runtimes, np.int64)
