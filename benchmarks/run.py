"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (the harness contract).

  fig3   — sequential sizes x update ratios      (paper Fig. 3)
  fig4   — lane-batch ("thread") sweep           (paper Figs. 4/5)
  fig6   — 128-lane size sweep                   (paper Figs. 6/7)
  fig8   — dependent-gather / node-access counters (paper Fig. 8 / App. A)
  fatnode — node-width sweep B ∈ {1,8,32,128}: modeled gather depth, tile
           bytes, scalar-vs-fat bit-equivalence (beyond-paper layout)
  skew   — Zipf-routed sharded launch: dense vs clustered DMA (beyond-paper)
  mesh   — mesh-distributed index: per-device HBM + lane balance (beyond-
           paper; multi-device cases need the XLA_FLAGS forced host
           devices, else only D=1 runs — the standalone module sets them)
  macro  — YCSB A/B/C + TPC-C-like store workloads (paper Figs. 9/10)

Roofline/dry-run numbers live in results/ (benchmarks.roofline), not here —
they are static analyses, not wall-clock calls.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig3_sequential, fig4_batch_sweep,
                            fig6_size_sweep, fig8_access_counters,
                            fig_fat_node, fig_mesh_index, fig_shard_skew,
                            fig_sync_modes, macro_store)

    suites = [
        ("fig3", fig3_sequential.run),
        ("fig4", fig4_batch_sweep.run),
        ("fig6", fig6_size_sweep.run),
        ("fig8", fig8_access_counters.run),
        ("fatnode", fig_fat_node.run),
        ("skew", fig_shard_skew.run),
        ("mesh", fig_mesh_index.run),
        ("sync", fig_sync_modes.run),
        ("macro", macro_store.run),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else ""
    print("name,us_per_call,derived")
    for name, fn in suites:
        if only and only != name:
            continue
        t0 = time.time()
        for row in fn():
            print(row, flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
