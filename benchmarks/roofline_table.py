"""Render the §Roofline markdown table from dry-run JSON records.

Run:  PYTHONPATH=src python -m benchmarks.roofline_table [--records results/dryrun]
                 [--fused results/fused_roofline.json]

``--fused`` appends the streaming-kernel section: one row per fused
cluster kernel (cluster_epoch_step / cluster_resize_step) from the
fused_cluster benchmark artifact — launches, analytic bytes/launch,
achieved bandwidth, fraction of the measured host copy bandwidth, and
the time the traffic would take at the published v5e HBM peak (a
projection, not a device measurement).
"""
import argparse
import glob
import json
import os

from repro.configs import ARCH_IDS, SHAPES


def fmt_ms(v: float) -> str:
    if v >= 1000:
        return f"{v/1000:.1f}s"
    if v >= 1:
        return f"{v:.0f}ms"
    return f"{v:.2f}ms"


def fused_table(path: str) -> None:
    """Per-fused-kernel roofline rows from the fused_cluster artifact."""
    art = json.load(open(path))
    print()
    print(f"### Fused cluster kernels "
          f"({art['events_per_s']:,.0f} ev/s on {art['n_events']:,} events; "
          f"host copy {art['host_copy_gb_s']:.1f} GB/s)")
    print()
    print("| kernel | launches | KB/launch | GB total | wall | "
          "items/s | GB/s | host-bw% | v5e HBM bound (projected) |")
    print("|---|---|---|---|---|---|---|---|---|")
    for k in art["kernels"]:
        ips = f"{k['items_per_s']:,.0f}" if k["items_per_s"] else "—"
        print(f"| {k['kernel']} | {k['launches']} "
              f"| {k['bytes_per_launch']/1024:.0f} "
              f"| {k['total_gb']:.3f} | {fmt_ms(k['wall_s']*1e3)} "
              f"| {ips} "
              f"| {k['achieved_gb_s']:.2f} "
              f"| {100*k['host_bw_frac']:.1f}% "
              f"| {fmt_ms(k['v5e_peak_bound_s']*1e3)} |")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", default="results/dryrun")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--fused", default="",
                    help="fused_cluster roofline artifact "
                         "(results/fused_roofline.json)")
    args = ap.parse_args()

    print("| arch | shape | compute | memory | collective | dominant | "
          "step | useful | roofline% | GB/dev |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for arch in ARCH_IDS:
        for shape in SHAPES:
            p = os.path.join(args.records, f"{arch}_{shape}_{args.mesh}.json")
            if not os.path.exists(p):
                continue
            r = json.load(open(p))
            if "skipped" in r:
                print(f"| {arch} | {shape} | — | — | — | skipped | — | — | — | — |")
                continue
            if "error" in r:
                print(f"| {arch} | {shape} | — | — | — | ERROR | — | — | — | — |")
                continue
            rr = r["roofline"]
            print(f"| {arch} | {shape} | {fmt_ms(rr['compute_ms'])} "
                  f"| {fmt_ms(rr['memory_ms'])} | {fmt_ms(rr['collective_ms'])} "
                  f"| {rr['dominant']} | {fmt_ms(rr['step_ms'])} "
                  f"| {rr['useful_flops_frac']:.2f} "
                  f"| {100*rr['roofline_frac']:.2f}% "
                  f"| {rr['bytes_per_device_gb']:.1f} |")
    if args.fused and os.path.exists(args.fused):
        fused_table(args.fused)


if __name__ == "__main__":
    main()
