"""Serving layer: batched, jit-compiled PCC allocation decisions.

``AllocationService`` turns any registered ``PCCModel`` into an online
allocator behind the typed protocol (``repro.api``):
``decide(AllocationRequest, DecisionContext) -> AllocationDecision`` runs
features -> scaled params -> decode -> allocation policy in one compiled
call per (model, batch bucket), with priced/unpriced, sharded/unsharded,
and observed/unobserved selected by context *fields* rather than separate
methods (the legacy method matrix survives as deprecation shims for one
release). ``MicroBatcher`` queues single-job requests and drains them
through ``decide`` in padded batches. ``ShardedAllocationService`` serves
N replicas of one model behind the same protocol — shard-tagged rows are
stacked into (K, Bp) blocks and decided in one compiled call under
``jax.shard_map`` (a loop on 1-device hosts), with ``ReplicaState``
keeping per-replica counters observable.

The streaming serving plane (``repro.serve.plane`` / ``repro.serve.aot``)
puts this behind a continuously-warm hot path: ``warm_allocation_stack``
AOT-compiles the whole executable grid at startup (zero traces under
traffic), and ``ServingPlane`` drains a bounded ``Backlog`` of arrival
events through worker-owned micro-batchers with backpressure.
"""
from repro.api.types import (
    AllocationDecision,
    AllocationRequest,
    DecisionContext,
    Provenance,
)
from repro.serve.aot import (
    WarmupConfig,
    WarmupReport,
    warm_allocation_stack,
    warm_fabric,
    warm_service,
)
from repro.serve.batching import (
    MicroBatcher,
    batch_bucket,
    node_bucket,
    pad_to,
    shard_positions,
)
from repro.serve.plane import Backlog, ServingPlane
from repro.serve.service import (
    AllocationResult,
    AllocationService,
    ReplicaState,
    ShardedAllocationService,
)

__all__ = [
    "AllocationDecision",
    "AllocationRequest",
    "AllocationResult",
    "AllocationService",
    "Backlog",
    "DecisionContext",
    "MicroBatcher",
    "Provenance",
    "ReplicaState",
    "ServingPlane",
    "ShardedAllocationService",
    "WarmupConfig",
    "WarmupReport",
    "batch_bucket",
    "node_bucket",
    "pad_to",
    "shard_positions",
    "warm_allocation_stack",
    "warm_fabric",
    "warm_service",
]
