"""Bulk AREPAS skyline simulation as a Pallas TPU kernel.

The paper's data-augmentation pass is the TASQ pipeline's data-path hot
spot: every job x every allocation grid point needs an Algorithm-1 runtime
(production: O(100k jobs/day) x K allocations x ~1e3-1e5-second skylines).
Each (job, alloc) simulation is a *segmented reduction* over the skyline —
embarrassingly parallel across (job, alloc) and streamable along time.

TPU adaptation (vs the sequential CPU loop):
  * grid (jobs, allocs, time-blocks), time innermost: the open-section
    carry (previous over-flag, running over-cap area, runtime accumulator)
    lives in SMEM scalars across time blocks;
  * every vector is a (1, T) row on the lanes. Section detection inside a
    block is data-parallel VPU work (sign changes -> a log-step lane prefix
    sum of section ids); section areas use a one-hot matmul (T x T on the
    MXU, full f32 precision) instead of a scatter — TPUs hate scatters;
  * completed over-cap sections contribute floor(area/alloc) seconds;
    under-cap seconds contribute their count; a section still open at the
    block edge is carried, and flushed at the final block.

Exactness: integer skylines keep every quantity < 2^24 exactly in f32, and
``floor_div`` corrects the quotient with exact integer products, so the
kernel matches the f64 oracle (core/arepas.py) whatever the rounding of
the device's division.

``lane_iota``, ``lane_cumsum`` and ``arepas_block`` are shared with the
fused resize kernel (kernels/cluster_step.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.arepas import floor_div

__all__ = ["skyline_runtimes", "call_x32", "lane_iota", "lane_cumsum",
           "onehot_matmul", "arepas_block", "arepas_runtime"]

DEFAULT_TIME_BLOCK = 512


def call_x32(kernel_call, *args):
    """Trace a ``pallas_call`` with 32-bit types. The kernels are f32/i32
    by construction; under ``jax.enable_x64`` their index maps and
    constants would come out 64-bit, which Mosaic cannot lower. ``args``
    must already be 32-bit."""
    with jax.enable_x64(False):
        return kernel_call(*args)


def lane_iota(n: int, dtype=jnp.int32) -> jax.Array:
    """(1, n) row 0..n-1 (Mosaic has no 1-D or float iota)."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1).astype(dtype)


def lane_cumsum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a (1, n) f32 row: log2(n) lane rotations
    with shifted adds (exact for integer values < 2^24)."""
    n = x.shape[1]
    lane = lane_iota(n)
    s = 1
    while s < n:
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0.0)
        s *= 2
    return x


def onehot_matmul(x: jax.Array, onehot: jax.Array, contract: int
                  ) -> jax.Array:
    """(1, n) row times a 0/1 matrix on the MXU, contracting the matrix's
    dim ``contract``. Full f32 precision: the default may round operands
    through bf16, which corrupts token counts above 256 and end times."""
    return jax.lax.dot_general(
        x, onehot, (((1,), (contract,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def arepas_block(s: jax.Array, valid: jax.Array, nt, carry_ref) -> None:
    """One time block of the AREPAS segmented reduction.

    ``s``/``valid``: (1, T) skyline block and its in-length mask; ``nt``:
    the scalar allocation. ``carry_ref[0:3]`` (SMEM) holds the previous
    block's over-flag, the open over-section's area and the runtime
    accumulator; they are updated in place.
    """
    T = s.shape[1]
    lane = lane_iota(T)
    lane_f = lane.astype(jnp.float32)
    over = (s > nt) & valid
    over_f = over.astype(jnp.float32)
    first = jnp.max(jnp.where(lane == 0, over_f, 0.0)) > 0.5
    last = jnp.max(jnp.where(lane == T - 1, over_f, 0.0)) > 0.5

    prev_over = carry_ref[0] > 0.5
    open_area = carry_ref[1]
    # Carried over-section: if it ends exactly at the block boundary, flush
    # it now; if it continues into element 0, merge its area into segment 0.
    acc = carry_ref[2] + jnp.where(prev_over & ~first,
                                   floor_div(open_area, nt), 0.0)
    carried = jnp.where(prev_over & first, open_area, 0.0)

    # section ids within the block (element 0 starts section 0)
    prev = jnp.where(lane == 0, over_f, pltpu.roll(over_f, 1, 1))
    seg_id = lane_cumsum((over_f != prev).astype(jnp.float32))   # (1, T)

    # per-section over-area via a (section x time) one-hot matmul
    seg_rows = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    onehot = (seg_rows == seg_id.astype(jnp.int32)).astype(jnp.float32)
    areas = onehot_matmul(jnp.where(over, s, 0.0), onehot, 1)     # (1, T)
    seg_over = onehot_matmul(over_f, onehot, 1) > 0.5
    areas = areas + jnp.where(lane == 0, carried, 0.0)

    # the block's last section stays open if the block ends over-cap
    is_open = lane_f == jnp.where(last, jnp.max(seg_id), -1.0)
    closed_over = seg_over & ~is_open
    acc = acc + jnp.sum(jnp.where(closed_over, floor_div(areas, nt), 0.0))
    acc = acc + jnp.sum((~over & valid).astype(jnp.float32))

    carry_ref[0] = last.astype(jnp.float32)
    carry_ref[1] = jnp.sum(jnp.where(is_open, areas, 0.0))
    carry_ref[2] = acc


def arepas_runtime(carry_ref, nt):
    """Final runtime after the last block: flush the open section."""
    return carry_ref[2] + jnp.where(carry_ref[0] > 0.5,
                                    floor_div(carry_ref[1], nt), 0.0)


def _skyline_kernel(sky_ref, len_ref, alloc_ref, out_ref, carry_ref, *,
                    tblock: int, n_tblocks: int, n_allocs: int):
    k = pl.program_id(1)
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        for i in range(3):
            carry_ref[i] = 0.0

    # the job's (1, K) allocation row and (1, K) output row stay resident
    # across its allocations; column k is this program's
    col = lane_iota(n_allocs) == k
    nt = jnp.sum(jnp.where(col, alloc_ref[...], 0.0))
    vlen = jnp.sum(len_ref[...])
    valid = (it * tblock + lane_iota(tblock)).astype(jnp.float32) < vlen
    arepas_block(sky_ref[...], valid, nt, carry_ref)

    @pl.when(it == n_tblocks - 1)
    def _finalize():
        rt = arepas_runtime(carry_ref, nt).astype(jnp.int32)
        out_ref[...] = jnp.where(col, rt, out_ref[...])


def skyline_runtimes(skylines: jax.Array, valid_lens: jax.Array,
                     allocs: jax.Array, *, time_block: int = DEFAULT_TIME_BLOCK,
                     interpret: bool = False) -> jax.Array:
    """(J, Smax) skylines x (J, K) allocations -> (J, K) int32 runtimes."""
    J, Smax = skylines.shape
    K = allocs.shape[1]
    tb = min(time_block, Smax)
    assert Smax % tb == 0, (Smax, tb)
    ntb = Smax // tb

    kernel = functools.partial(_skyline_kernel, tblock=tb, n_tblocks=ntb,
                               n_allocs=K)
    job_row = lambda n: pl.BlockSpec((None, 1, n), lambda j, k, t: (j, 0, 0))
    call = pl.pallas_call(
        kernel,
        grid=(J, K, ntb),
        in_specs=[
            pl.BlockSpec((None, 1, tb), lambda j, k, t: (j, 0, t)),
            job_row(1),
            job_row(K),
        ],
        out_specs=job_row(K),
        out_shape=jax.ShapeDtypeStruct((J, 1, K), jnp.int32),
        scratch_shapes=[pltpu.SMEM((3,), jnp.float32)],
        interpret=interpret,
    )
    out = call_x32(call, skylines.astype(jnp.float32).reshape(J, 1, Smax),
                   valid_lens.astype(jnp.float32).reshape(J, 1, 1),
                   allocs.astype(jnp.float32).reshape(J, 1, K))
    return out.reshape(J, K)
