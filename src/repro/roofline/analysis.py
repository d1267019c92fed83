"""Three-term roofline analysis from a compiled dry-run artifact.

This container has no TPU, so instead of wall-clock MFU we derive, per
(arch x shape x mesh):

  compute term    = HLO_FLOPs        / (chips * peak_FLOPs)
  memory term     = HLO_bytes        / (chips * HBM_bw)
  collective term = collective_bytes / (chips * link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``. Collective
bytes are NOT in cost_analysis: we parse the optimized HLO text and sum the
shaped payload of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute.

Note on totals: XLA's cost_analysis on an SPMD-partitioned module reports
the *per-partition* program, so terms divide by per-chip peaks directly;
``normalize="global"`` multiplies by chip count first when an unpartitioned
(single-device-program) module is analyzed. The dry-run driver verifies
which convention holds by comparing against the analytic 6ND model and
records the ratio (MODEL_FLOPS / HLO_FLOPs) in every report row.

Hardware model (TPU v5e): 197 TFLOP/s bf16; 819 GB/s HBM; ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, Optional, Tuple

__all__ = ["HW", "Hardware", "collective_bytes", "roofline_terms",
           "RooflineReport", "parse_hlo_collectives", "KernelRoofline",
           "kernel_roofline", "host_copy_bandwidth"]


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str = "tpu-v5e"
    peak_flops: float = 197e12        # bf16 per chip
    hbm_bw: float = 819e9             # bytes/s per chip
    link_bw: float = 50e9             # bytes/s per ICI link
    hbm_per_chip: float = 16e9        # capacity (fit check)


HW = Hardware()

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# e.g. "bf16[16,4096,1024]{2,1,0}" or "f32[]"; tuple shapes handled by findall
_SHAPE_RE = re.compile(r"\b([a-z]\w*?)\[([\d,]*)\]")
_COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                     "all-to-all", "collective-permute")
# "%all-gather.7 = bf16[...] all-gather(" — capture result shapes + kind
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[a-z][^=]*?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
# replica_groups={{0,1,..},{..}} or iota form replica_groups=[8,32]<=[256]...
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shapes(shape_str: str):
    out = []
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append(n * _DTYPE_BYTES[dtype])
    return out


def _group_size(line: str) -> int:
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        return m.group(1).count(",") + 1
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))   # [num_groups, group_size]
    return 2  # unknown: conservative minimum


def _wire_bytes(kind: str, shapes, n: int) -> float:
    """Per-chip ICI wire traffic for a ring implementation of the op.

    R = result bytes (for -start tuples the result is the last/largest
    component). all-gather: (n-1)/n * R; all-reduce: 2(n-1)/n * R (reduce-
    scatter + all-gather phases); reduce-scatter: (n-1) * R (operand is
    n*R); all-to-all: (n-1)/n * R; collective-permute: R.
    """
    if not shapes:
        return 0.0
    if kind == "all-gather":
        r = max(shapes)
        return (n - 1) / n * r
    r = shapes[-1]
    if kind == "all-reduce":
        return 2.0 * (n - 1) / n * r
    if kind == "reduce-scatter":
        return float(n - 1) * r
    if kind == "all-to-all":
        return (n - 1) / n * r
    return float(max(shapes))      # collective-permute


def parse_hlo_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-chip collective wire bytes per kind, parsed from optimized HLO.

    Async ``-start``/``-done`` pairs are counted once (on the -start).
    """
    out: Dict[str, Dict[str, float]] = {
        k: {"bytes": 0.0, "count": 0} for k in _COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        result_shapes, kind, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-done":
            continue
        b = _wire_bytes(kind, _shapes(result_shapes), _group_size(line))
        out[kind]["bytes"] += b
        out[kind]["count"] += 1
    return out


def collective_bytes(hlo_text: str) -> float:
    per = parse_hlo_collectives(hlo_text)
    return float(sum(v["bytes"] for v in per.values()))


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float                 # per-chip program FLOPs
    hlo_bytes: float                 # per-chip HBM traffic
    coll_bytes: float                # per-chip collective payload
    model_flops: float               # analytic 6*N*D (global, per step)
    compute_s: float
    memory_s: float
    collective_s: float
    bytes_per_device: float = 0.0    # from memory_analysis (fit check)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / (global HLO FLOPs) — remat/redundancy waste."""
        total = self.hlo_flops * self.chips
        return self.model_flops / total if total > 0 else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved if the step ran at the
        max-term time: useful compute time / roofline step time."""
        t_useful = self.model_flops / (self.chips * HW.peak_flops)
        return t_useful / self.step_time_s if self.step_time_s > 0 else 0.0

    def row(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "compute_ms": round(self.compute_s * 1e3, 3),
            "memory_ms": round(self.memory_s * 1e3, 3),
            "collective_ms": round(self.collective_s * 1e3, 3),
            "dominant": self.dominant,
            "step_ms": round(self.step_time_s * 1e3, 3),
            "useful_flops_frac": round(self.useful_flops_fraction, 4),
            "roofline_frac": round(self.roofline_fraction, 4),
            "bytes_per_device_gb": round(self.bytes_per_device / 1e9, 3),
        }


def roofline_terms(*, arch: str, shape: str, mesh: str, chips: int,
                   hlo_flops: float, hlo_bytes: float, coll_bytes: float,
                   model_flops: float, bytes_per_device: float = 0.0,
                   hw: Hardware = HW) -> RooflineReport:
    """All inputs are per-chip program quantities (XLA SPMD convention)."""
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh, chips=chips,
        hlo_flops=hlo_flops, hlo_bytes=hlo_bytes, coll_bytes=coll_bytes,
        model_flops=model_flops,
        compute_s=hlo_flops / hw.peak_flops,
        memory_s=hlo_bytes / hw.hbm_bw,
        collective_s=coll_bytes / hw.link_bw,
        bytes_per_device=bytes_per_device,
    )


# ----------------------------------------------------- streaming kernels ---
# The fused cluster-epoch kernels (kernels/cluster_step.py) do essentially
# no arithmetic per byte — a replay epoch reads the (K, L) lease tables and
# the (K, Q) queue head, and writes them back.  Their roofline is therefore
# one-term: wall time vs. the time the memory system needs to move the
# analytic traffic.  ``bytes_per_launch`` is analytic (summed from the
# operand/result shapes), not measured — the point is a stable,
# host-independent denominator for the CI regression gate.
@dataclasses.dataclass
class KernelRoofline:
    kernel: str                       # e.g. "cluster_epoch_step"
    launches: int
    bytes_per_launch: float           # analytic operand+result traffic
    wall_s: float                     # total wall across all launches
    items: int = 0                    # events (or candidates) processed
    measured_bw: float = 0.0          # host copy bandwidth (CPU baseline)
    hw: Hardware = HW

    @property
    def total_bytes(self) -> float:
        return self.launches * self.bytes_per_launch

    @property
    def achieved_bw(self) -> float:
        """Bytes actually streamed per wall second."""
        return self.total_bytes / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def bound_s(self) -> float:
        """Memory-bound time at ``hw``'s published HBM peak (v5e): a
        projection from a constant, whatever device ran the launches —
        not a measurement."""
        return self.total_bytes / self.hw.hbm_bw

    @property
    def bound_fraction(self) -> float:
        """Fraction of the memory roofline achieved.  On the CPU container
        this is tiny (launch overhead dominates the small tables); compare
        against ``measured_bw`` for the host-relative number."""
        return self.bound_s / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def host_fraction(self) -> float:
        """achieved_bw / measured host copy bandwidth (0 if unmeasured)."""
        if self.measured_bw <= 0:
            return 0.0
        return self.achieved_bw / self.measured_bw

    def row(self) -> Dict:
        return {
            "kernel": self.kernel,
            "launches": self.launches,
            "bytes_per_launch": int(self.bytes_per_launch),
            "total_gb": round(self.total_bytes / 1e9, 4),
            "wall_s": round(self.wall_s, 4),
            "items": self.items,
            "items_per_s": (round(self.items / self.wall_s, 1)
                            if self.wall_s > 0 else None),
            "achieved_gb_s": round(self.achieved_bw / 1e9, 4),
            "hbm_bound_frac": round(self.bound_fraction, 6),
            "host_bw_frac": round(self.host_fraction, 4),
            "v5e_peak_bound_s": round(self.bound_s, 6),
        }


def kernel_roofline(kernel: str, *, launches: int, bytes_per_launch: float,
                    wall_s: float, items: int = 0, measured_bw: float = 0.0,
                    hw: Hardware = HW) -> KernelRoofline:
    return KernelRoofline(kernel=kernel, launches=launches,
                          bytes_per_launch=bytes_per_launch, wall_s=wall_s,
                          items=items, measured_bw=measured_bw, hw=hw)


def host_copy_bandwidth(n_bytes: int = 1 << 26, reps: int = 3) -> float:
    """Measured host memcpy bandwidth (bytes/s, read+write counted once):
    the honest local ceiling for a streaming kernel on this container."""
    import time

    import numpy as np
    src = np.ones(n_bytes // 8, np.float64)
    dst = np.empty_like(src)
    np.copyto(dst, src)                       # touch both buffers
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        best = min(best, time.perf_counter() - t0)
    return src.nbytes / best
