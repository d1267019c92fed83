"""Fused cluster epoch step as Pallas TPU kernels.

The cluster hot loop (cluster/simulator.py) spends its epoch budget on four
chained table sweeps over the stacked (K, L) ``PoolShards`` lease tables:
lease expiry -> free-token release -> policy-ordered prefix-sum admission ->
lease scatter. Run separately they cost four kernel launches plus
host<->device round-trips per epoch; fused they are one streaming pass over
the lease tables — the whole epoch is memory-bandwidth bound on the (K, L)
table traffic.

Two kernels, each with a pure-jnp twin:

  * ``epoch_step_pallas`` / ``epoch_step_ref`` — the fused epoch step. Grid
    (K, 2, L-blocks) with a two-phase sweep per shard: phase 0 scans expiry
    and accumulates the freed-token total in SMEM carries; phase 1
    re-derives the expiry mask per block (idempotent), turns the
    policy-ordered queue into an admitted prefix via a lane prefix sum
    against ``free + freed``, and scatters admitted leases into free slots
    with a one-hot matmul (queue rank x slot on the MXU — TPUs hate
    scatters).
  * ``resize_step_pallas`` / ``resize_step_ref`` — the fused elastic-resize
    path: the priced allocation decision (gain cut-off + fixed-iteration
    slowdown bisection, core/allocator.py) runs in the first time-block,
    then the same streaming AREPAS segmented reduction as kernels/skyline.py
    re-simulates the runtime at the shrunk allocation — one launch per
    pressure event instead of a decide -> simulate -> reprice cascade.

Exactness: token counts, slot ranks and AREPAS areas are integers < 2^24,
exact in f32 (same argument as kernels/skyline.py), and the one-hot matmuls
run at full f32 precision. Lease *end times* in the Pallas kernels are f32
— Mosaic has no f64 — so they are exact only where times are: whole
seconds below 2^24, as in ``FusedReplay``. The jnp twins are dtype-generic
and, run in float64 under ``jax.enable_x64``, are bitwise-identical to the
unfused epoch loop (tests/test_cluster.py parity matrix); the simulator,
whose pool keeps float64 end times, runs them on every backend.

The Pallas kernels compile for the TPU (tests/test_tpu_compile.py) and run
under ``interpret=True`` on the CPU, for the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.allocator import _BISECT_ITERS, AllocationPolicy
from repro.core.allocator import choose_tokens_priced_jnp
from repro.core.arepas import simulate_runtime_batch
from repro.kernels.skyline import (arepas_block, arepas_runtime, call_x32,
                                   lane_cumsum, lane_iota, onehot_matmul)

__all__ = ["epoch_step_ref", "epoch_step_pallas",
           "resize_step_ref", "resize_step_pallas",
           "EPOCH_STEP_SUPPORTS_PREEMPTION"]

DEFAULT_LEASE_BLOCK = 128

# The fused epoch step has no preempt phase: it expires, releases, admits
# and scatters, but cannot checkpoint a victim lease's remaining work back
# into the queue (that requires the host-side work-done fraction and a
# fresh routed decision). The simulator consults this flag and falls back
# — loudly — to the unfused admission loop when preemption is enabled;
# seeded no-preemption replays stay on the fused path and remain
# decision-identical to the unfused loop. Flip only together with a kernel
# preempt phase and a parity test.
EPOCH_STEP_SUPPORTS_PREEMPTION = False


# ------------------------------------------------------------- jnp twins ---
def epoch_step_ref(end_s: jax.Array, tokens: jax.Array, free: jax.Array,
                   q_tok: jax.Array, q_end: jax.Array, now: jax.Array):
    """Fused epoch step, pure jnp: expire -> release -> admit -> scatter.

    end_s/tokens: (K, L) lease tables (inf / 0 in empty slots).
    free:         (K,) free tokens per shard *before* this epoch's expiry.
    q_tok/q_end:  (K, Q) policy-ordered queue heads, zero-padded past each
                  shard's queue; ``q_end[k, i]`` is the lease end time query
                  i would get if admitted now.
    now:          () epoch timestamp.

    Returns (new_end, new_tok, slot_of, n_admit, adm_tok, freed, n_expired):
    the updated tables, the lease slot each queue position landed in (-1 if
    not admitted), and per-shard admitted/freed totals. Admission is the
    longest queue prefix whose token sum fits ``free + freed`` AND whose
    length fits the post-expiry open lease slots — each clause keeps the
    admitted set a prefix (queue entries hold >= 1 token each), so this is
    identical to the unfused cumsum/searchsorted loop whenever that loop is
    well-defined, and degrades to admit-what-fits (instead of leaking
    tokens into leases that were never scattered) when the lease table is
    the binding constraint. The i-th admitted query takes the i-th free
    slot in slot order, matching ``PoolShards.acquire_batch``.
    """
    K, L = end_s.shape
    Q = q_tok.shape[1]
    expired = (tokens > 0) & (end_s <= now)
    freed = jnp.sum(jnp.where(expired, tokens, 0), axis=1)
    n_expired = jnp.sum(expired, axis=1)
    tok1 = jnp.where(expired, 0, tokens)
    end1 = jnp.where(expired, jnp.inf, end_s)

    free_after = free + freed
    open_slots = jnp.sum(tok1 == 0, axis=1)
    csum = jnp.cumsum(q_tok, axis=1)
    adm = ((csum <= free_after[:, None]) & (q_tok > 0)
           & (jnp.arange(Q)[None, :] < open_slots[:, None]))
    n_admit = jnp.sum(adm, axis=1)
    adm_tok = jnp.sum(jnp.where(adm, q_tok, 0), axis=1)

    free_slot = tok1 == 0
    rank = jnp.cumsum(free_slot, axis=1) - 1          # slot-order free rank
    take = free_slot & (rank < n_admit[:, None])
    src = jnp.clip(rank, 0, Q - 1)
    new_tok = jnp.where(take, jnp.take_along_axis(q_tok, src, axis=1), tok1)
    new_end = jnp.where(take, jnp.take_along_axis(q_end, src, axis=1), end1)

    # invert slot -> queue-rank into queue-rank -> slot via a dummy column
    col = jnp.where(take, src, Q)
    slot_of = jnp.full((K, Q + 1), -1, jnp.int32)
    slot_of = slot_of.at[jnp.arange(K)[:, None], col].set(
        jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (K, L)))[:, :Q]
    return new_end, new_tok, slot_of, n_admit, adm_tok, freed, n_expired


def resize_step_ref(a: jax.Array, b: jax.Array, price: jax.Array,
                    obs: jax.Array, floor: jax.Array, done: jax.Array,
                    cand_tok: jax.Array, cand_end: jax.Array,
                    sky: jax.Array, lens: jax.Array, now: jax.Array,
                    epoch_s: float, *, policy: AllocationPolicy, cap: int):
    """Fused elastic resize, pure jnp: priced decision + AREPAS + reprice.

    Per-candidate (C,) PCC params / price / observed tokens / deadline
    floor / completed-work fraction / current lease, plus (C, Smax) padded
    skylines. Returns (tgt, sel, rt, new_end): the shrunk allocation, the
    shrink-worthwhile mask, the re-simulated runtime at ``tgt``, and the
    repriced lease end. Mirrors cluster/simulator.py step 4 exactly — the
    decision comes from ``choose_tokens_priced_jnp`` (bitwise-equal to the
    scalar oracle in float64) and the runtime from the exact AREPAS batch.
    """
    tgt = jnp.minimum(choose_tokens_priced_jnp(a, b, policy, price, obs),
                      cap)
    tgt = jnp.maximum(tgt, floor.astype(tgt.dtype))
    sel = (tgt < cand_tok) & ((cand_end - now) > epoch_s)
    rt = simulate_runtime_batch(sky, lens, jnp.maximum(tgt, 1)[:, None])[:, 0]
    rt = jnp.maximum(rt, 1).astype(cand_tok.dtype)
    remaining = jnp.maximum(jnp.round(rt.astype(a.dtype) * (1.0 - done)), 1.0)
    return tgt, sel, rt, now + remaining


# ------------------------------------------------- fused epoch kernel -------
# Layout: every vector is a (1, n) row on the lanes. The (K, L) tables and
# (K, Q) queue heads are viewed as (K, 1, n) with the shard dim squeezed,
# so each block is a whole-height (1, n) tile; per-shard scalars (free,
# the outputs' (K,) totals, the carries) and ``now`` live in SMEM.
def _epoch_kernel(end_ref, tok_ref, free_ref, qtok_ref, qend_ref, now_ref,
                  nend_ref, ntok_ref, slot_ref, nadm_ref, admtok_ref,
                  freed_ref, nexp_ref, carry_ref, slot_acc, *,
                  lblock: int, n_lblocks: int, n_queue: int):
    k = pl.program_id(0)
    p = pl.program_id(1)                  # 0: expiry scan, 1: admit+scatter
    t = pl.program_id(2)

    # carry: 0 freed tokens, 1 expired leases, 2 running free-slot rank,
    # 3 admitted count, 4 admitted tokens, 5 open slots after expiry
    @pl.when((p == 0) & (t == 0))
    def _init():
        for i in range(6):
            carry_ref[i] = 0.0
        slot_acc[...] = jnp.zeros_like(slot_acc)

    now = now_ref[0]
    end = end_ref[...]
    tok = tok_ref[...]
    expired = (tok > 0.0) & (end <= now)
    tok1 = jnp.where(expired, 0.0, tok)
    end1 = jnp.where(expired, jnp.inf, end)
    free_slot = (tok1 == 0.0).astype(jnp.float32)

    @pl.when(p == 0)
    def _phase_expire():
        carry_ref[0] = carry_ref[0] + jnp.sum(jnp.where(expired, tok, 0.0))
        carry_ref[1] = carry_ref[1] + jnp.sum(expired.astype(jnp.float32))
        carry_ref[5] = carry_ref[5] + jnp.sum(free_slot)

    # Admission decision once per shard: the queue row fits in VMEM, so the
    # prefix-sum fit test is a single lane prefix sum against free + freed,
    # capped by the open lease slots counted during the expiry phase.
    @pl.when((p == 1) & (t == 0))
    def _decide():
        qt = qtok_ref[...]
        free_after = free_ref[k] + carry_ref[0]
        qidx = lane_iota(n_queue, jnp.float32)
        adm = ((lane_cumsum(qt) <= free_after) & (qt > 0.0)
               & (qidx < carry_ref[5]))
        carry_ref[2] = 0.0
        carry_ref[3] = jnp.sum(adm.astype(jnp.float32))
        carry_ref[4] = jnp.sum(jnp.where(adm, qt, 0.0))

    @pl.when(p == 1)
    def _phase_admit():
        rank_base = carry_ref[2]
        rank = rank_base + lane_cumsum(free_slot) - 1.0          # (1, Lb)
        take = (free_slot > 0.0) & (rank < carry_ref[3])

        # queue-rank -> slot gather as a (Q, Lb) one-hot matmul (ranks are
        # exact integers in f32, so the equality test is exact)
        qrows = jax.lax.broadcasted_iota(jnp.int32, (n_queue, lblock), 0)
        oh = ((qrows == rank.astype(jnp.int32)) & take).astype(jnp.float32)
        ntok_ref[...] = jnp.where(take, onehot_matmul(qtok_ref[...], oh, 0),
                                  tok1)
        nend_ref[...] = jnp.where(take, onehot_matmul(qend_ref[...], oh, 0),
                                  end1)

        # slot-of inverse: accumulate (slot index + 1) per queue rank
        lidx = (t * lblock + lane_iota(lblock)).astype(jnp.float32)
        slot_acc[...] = slot_acc[...] + onehot_matmul(lidx + 1.0, oh, 1)
        carry_ref[2] = rank_base + jnp.sum(free_slot)

    @pl.when((p == 1) & (t == n_lblocks - 1))
    def _finalize():
        slot_ref[...] = (slot_acc[...] - 1.0).astype(jnp.int32)
        nadm_ref[k] = carry_ref[3].astype(jnp.int32)
        admtok_ref[k] = carry_ref[4].astype(jnp.int32)
        freed_ref[k] = carry_ref[0].astype(jnp.int32)
        nexp_ref[k] = carry_ref[1].astype(jnp.int32)


# jitted: the kernel body is a fresh closure on every trace, so an eager
# call would lower and compile the Mosaic kernel again at every launch
@functools.partial(jax.jit, static_argnames=("lease_block", "interpret"))
def epoch_step_pallas(end_s: jax.Array, tokens: jax.Array, free: jax.Array,
                      q_tok: jax.Array, q_end: jax.Array, now: jax.Array, *,
                      lease_block: int = DEFAULT_LEASE_BLOCK,
                      interpret: bool = False):
    """Pallas twin of ``epoch_step_ref``: one launch per epoch, f32 tables.

    Returns the same 7-tuple; end times and token counts come back f32/i32.
    """
    K, L = end_s.shape
    Q = q_tok.shape[1]
    lb = min(lease_block, L)
    assert L % lb == 0, (L, lb)
    nlb = L // lb

    kernel = functools.partial(_epoch_kernel, lblock=lb, n_lblocks=nlb,
                               n_queue=Q)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    lease = pl.BlockSpec((None, 1, lb), lambda k, p, t: (k, 0, t))
    # the admit phase writes every lease block; during the expiry phase the
    # output stays parked on block 0, so nothing is written back twice
    lease_out = pl.BlockSpec((None, 1, lb), lambda k, p, t: (k, 0, t * p))
    queue = pl.BlockSpec((None, 1, Q), lambda k, p, t: (k, 0, 0))
    rows = lambda x: x.astype(jnp.float32).reshape(K, 1, -1)
    call = pl.pallas_call(
        kernel,
        grid=(K, 2, nlb),
        in_specs=[lease, lease, smem, queue, queue, smem],
        out_specs=[lease_out, lease_out, queue, smem, smem, smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((K, 1, L), jnp.float32),
            jax.ShapeDtypeStruct((K, 1, L), jnp.float32),
            jax.ShapeDtypeStruct((K, 1, Q), jnp.int32),
            jax.ShapeDtypeStruct((K,), jnp.int32),
            jax.ShapeDtypeStruct((K,), jnp.int32),
            jax.ShapeDtypeStruct((K,), jnp.int32),
            jax.ShapeDtypeStruct((K,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((8,), jnp.float32),
                        pltpu.VMEM((1, Q), jnp.float32)],
        interpret=interpret,
    )
    out = call_x32(call, rows(end_s), rows(tokens), free.astype(jnp.float32),
                   rows(q_tok), rows(q_end),
                   jnp.asarray(now, jnp.float32).reshape(1))
    new_end, new_tok_f, slot_of, n_admit, adm_tok, freed, n_expired = out
    return (new_end.reshape(K, L), new_tok_f.reshape(K, L).astype(jnp.int32),
            slot_of.reshape(K, Q), n_admit, adm_tok, freed, n_expired)


# ------------------------------------------------ fused resize kernel -------
def _resize_kernel(a_ref, b_ref, pr_ref, obs_ref, flr_ref, done_ref,
                   ctok_ref, cend_ref, sky_ref, len_ref, now_ref,
                   tgt_ref, sel_ref, rt_ref, nend_ref, carry_ref, *,
                   tblock: int, n_tblocks: int, epoch_s: float,
                   min_gain: float, max_slowdown: float, min_tokens: int,
                   max_tokens: int, cap: int):
    c = pl.program_id(0)
    it = pl.program_id(1)

    # Decision preamble in the first time-block: gain cut-off + the same
    # fixed-iteration slowdown bisection as choose_tokens_priced_jnp, then
    # min(cap) / max(deadline floor) — carried as the AREPAS allocation.
    # The scalar unit has no pow, so the decision runs on one lane row.
    @pl.when(it == 0)
    def _decide():
        row = lambda ref: jnp.full((1, 128), ref[c], jnp.float32)
        a, b, price, hi = row(a_ref), row(b_ref), row(pr_ref), row(obs_ref)
        lo0 = jnp.float32(min_tokens)
        eff_gain = max(min_gain, 1e-9) * price
        t_gain = jnp.clip(jnp.round(jnp.abs(a) / eff_gain), lo0, hi)
        t_gain = jnp.where(a >= 0, lo0, t_gain)
        if max_slowdown > 0:
            limit = (1.0 + max_slowdown * price) * (b * hi ** a)

            def body(_, st):
                lo, hi_s = st
                cond = lo < hi_s
                mid = jnp.floor((lo + hi_s) / 2)
                ok = b * mid ** a <= limit
                return (jnp.where(cond & ~ok, mid + 1, lo),
                        jnp.where(cond & ok, mid, hi_s))

            lo, _ = jax.lax.fori_loop(0, _BISECT_ITERS, body,
                                      (jnp.full_like(hi, lo0), hi))
            t_gain = jnp.maximum(jnp.minimum(t_gain, jnp.float32(max_tokens)),
                                 lo)
        nt = jnp.maximum(jnp.minimum(t_gain, jnp.float32(cap)), flr_ref[c])
        carry_ref[0] = 0.0            # prev block ended over-cap
        carry_ref[1] = 0.0            # open over-section area
        carry_ref[2] = 0.0            # runtime accumulator
        carry_ref[3] = jnp.max(nt)

    # Streaming AREPAS segmented reduction at the shrunk allocation — the
    # same carry-across-time-blocks scheme as kernels/skyline.py.
    nt = carry_ref[3]
    valid = it * tblock + lane_iota(tblock) < len_ref[c]
    arepas_block(sky_ref[...], valid, nt, carry_ref)

    @pl.when(it == n_tblocks - 1)
    def _finalize():
        rt = jnp.maximum(arepas_runtime(carry_ref, nt), 1.0)
        now = now_ref[0]
        sel = (nt < ctok_ref[c]) & ((cend_ref[c] - now) > epoch_s)
        remaining = jnp.maximum(
            jnp.max(jnp.round(jnp.full((1, 128), rt * (1.0 - done_ref[c]),
                                       jnp.float32))), 1.0)
        tgt_ref[c] = nt.astype(jnp.int32)
        sel_ref[c] = sel.astype(jnp.int32)
        rt_ref[c] = rt.astype(jnp.int32)
        nend_ref[c] = now + remaining


@functools.partial(jax.jit, static_argnames=(
    "epoch_s", "policy", "cap", "time_block", "interpret"))
def resize_step_pallas(a: jax.Array, b: jax.Array, price: jax.Array,
                       obs: jax.Array, floor: jax.Array, done: jax.Array,
                       cand_tok: jax.Array, cand_end: jax.Array,
                       sky: jax.Array, lens: jax.Array, now: jax.Array,
                       epoch_s: float, *, policy: AllocationPolicy, cap: int,
                       time_block: int = 512, interpret: bool = False):
    """Pallas twin of ``resize_step_ref``: decision + AREPAS in one launch.

    Returns (tgt i32, sel i32 mask, rt i32, new_end f32), each (C,).
    """
    C, Smax = sky.shape
    tb = min(time_block, Smax)
    assert Smax % tb == 0, (Smax, tb)
    ntb = Smax // tb

    kernel = functools.partial(
        _resize_kernel, tblock=tb, n_tblocks=ntb, epoch_s=float(epoch_s),
        min_gain=policy.min_gain, max_slowdown=policy.max_slowdown,
        min_tokens=policy.min_tokens, max_tokens=policy.max_tokens,
        cap=int(cap))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    f32 = lambda x: jnp.asarray(x).astype(jnp.float32)
    call = pl.pallas_call(
        kernel,
        grid=(C, ntb),
        in_specs=[smem] * 8 + [
            pl.BlockSpec((None, 1, tb), lambda c, t: (c, 0, t)),
            smem, smem],
        out_specs=[smem] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((C,), jnp.int32),
            jax.ShapeDtypeStruct((C,), jnp.int32),
            jax.ShapeDtypeStruct((C,), jnp.int32),
            jax.ShapeDtypeStruct((C,), jnp.float32),
        ],
        scratch_shapes=[pltpu.SMEM((4,), jnp.float32)],
        interpret=interpret,
    )
    return call_x32(call, f32(a), f32(b), f32(price), f32(obs), f32(floor),
                    f32(done), f32(cand_tok), f32(cand_end),
                    f32(sky).reshape(C, 1, Smax),
                    jnp.asarray(lens).astype(jnp.int32), f32(now).reshape(1))
