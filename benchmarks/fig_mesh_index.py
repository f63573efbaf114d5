"""Mesh-distributed index benchmark: per-device HBM scaling + lane balance.

The mesh index (``core.mesh_index``) partitions the key space across the
devices of a 1-D ``("index",)`` mesh; each device holds ``1/D`` of the
table and serves only the lanes routed to its slice.  This sweep records,
for D ∈ {1, 2, 4, 8} (clamped to the devices present):

* ``state_bytes_per_device`` — resident index bytes per device (the HBM
  scaling claim: ~``1/D`` of the single-device table);
* ``model_bytes_per_device`` — modeled worst-case per-device HBM->VMEM
  index-tile traffic of the kernel path
  (``kernels.mesh_launch.dma_model_bytes_mesh`` vs the single-device
  ``kernels.ops.dma_model_bytes`` denominator);
* ``routed_balance`` — max/mean routed-lane count across devices for a
  uniform and a Zipf(1.2) batch (1.0 = perfectly balanced; Zipf shows the
  skew the DeviceLoadStats counters surface);
* ``us_per_call`` — wall time of ``search_mesh`` vs single-device
  ``search_sharded`` (simulated host devices: trend, not absolute).

Every mesh result is asserted bit-identical to the single-device engine
before it is timed — the benchmark doubles as an equivalence check.

Multi-device CPU runs need ``XLA_FLAGS=--xla_force_host_platform_device_
count=8`` set before jax initializes; this module sets it when imported
first (the standalone ``python -m benchmarks.fig_mesh_index`` path).
``python -m benchmarks.fig_mesh_index`` records the sweep to
``BENCH_mesh_index.json`` next to the repo root as a regression snapshot.
"""
from __future__ import annotations

import json
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench, csv_row, zipf_queries
from repro.core import mesh_index as mi
from repro.core import sharded as shd
from repro.kernels import mesh_launch as ml
from repro.kernels import ops as kops
from repro.launch import mesh as lmesh

N_KEYS = 2**13
BATCH = 1024
N_SHARDS = 8                     # per-device range shards
LEVELS = 12
SPAN = 1 << 22

_SNAPSHOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_mesh_index.json")


def _tree_bytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(tree) if hasattr(a, "dtype"))


def _balance(counts: np.ndarray) -> float:
    return float(counts.max() / max(counts.mean(), 1e-9))


def run() -> list:
    rows, snap = [], []
    rng = np.random.default_rng(0)
    keys = np.sort(rng.choice(SPAN, N_KEYS, replace=False)).astype(np.int32)
    vals = (keys * 3).astype(np.int32)
    ref = shd.build_sharded(jnp.asarray(keys), jnp.asarray(vals),
                            n_shards=N_SHARDS, levels=LEVELS)
    batches = {
        "uniform": jnp.asarray(rng.integers(0, SPAN, BATCH).astype(np.int32)),
        "zipf": zipf_queries(keys, BATCH),
    }
    expect = {d: shd.search_sharded(ref, q) for d, q in batches.items()}
    single_state = _tree_bytes(ref)
    single_model = kops.dma_model_bytes(ref, BATCH)
    t_single = bench(lambda s, qq: shd.search_sharded(s, qq)[1],
                     ref, batches["uniform"], iters=3, warmup=1)

    avail = len(jax.devices())
    for D in [d for d in (1, 2, 4, 8) if d <= avail]:
        mesh = lmesh.make_index_mesh(D)
        mx = mi.build_mesh_index(jnp.asarray(keys), jnp.asarray(vals),
                                 mesh=mesh, n_devices=D, n_shards=N_SHARDS,
                                 levels=LEVELS)
        state_dev = _tree_bytes(mx.local) // D
        model_dev = ml.dma_model_bytes_mesh(mx, BATCH)
        entry = {
            "n_devices": D, "batch": BATCH, "n_keys": N_KEYS,
            "local_shards": mx.local_shards,
            "state_bytes_per_device": state_dev,
            "state_bytes_single": single_state,
            "state_scaling": round(single_state / max(state_dev, 1), 2),
            "model_bytes_per_device": int(model_dev),
            "model_bytes_single": int(single_model),
            "us_per_call_single": t_single * 1e6,
        }
        for dist, q in batches.items():
            f, v = mi.search_mesh(mx, q, mesh=mesh)
            ef, ev = expect[dist]
            np.testing.assert_array_equal(np.asarray(f), np.asarray(ef))
            np.testing.assert_array_equal(np.asarray(v), np.asarray(ev))
            routed = np.bincount(np.asarray(mi.route_devices(mx, q)),
                                 minlength=D)
            bal = _balance(routed)
            t_mesh = bench(lambda m, qq, _mesh=mesh: mi.search_mesh(
                m, qq, mesh=_mesh)[1], mx, q, iters=3, warmup=1)
            entry[f"us_per_call_{dist}"] = t_mesh * 1e6
            entry[f"routed_balance_{dist}"] = round(bal, 3)
            rows.append(csv_row(
                f"mesh/D={D}/{dist}", t_mesh / BATCH * 1e6,
                f"routed_balance={bal:.3f};"
                f"state_bytes_per_device={state_dev};"
                f"model_bytes_per_device={model_dev}"))
        snap.append(entry)
    run.snapshot = snap
    return rows


def main() -> None:
    rows = run()
    for r in rows:
        print(r)
    with open(_SNAPSHOT, "w") as f:
        json.dump(run.snapshot, f, indent=2)
        f.write("\n")
    print(f"# snapshot -> {_SNAPSHOT}")


if __name__ == "__main__":
    main()
