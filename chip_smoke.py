"""Bring-up smoke test: the ordered index's served paths on a TPU.

    python chip_smoke.py [--seed N]           # one chip
    python chip_smoke.py --chips 4 [--seed N] # the mesh index on four chips

One chip runs two phases through the entry points a user calls:

* sample store (``IndexedSampleStore``): a foresight index at the
  DBx1000 YCSB scale the device holds, built from keys and rows made from
  ``--seed``; batches of ``lookup`` and ``get_batch``, ``ingest`` and
  ``evict`` of a few hundred keys, and a ``range_scan``;
* page table (``PageTable``, 65536 pages): sessions allocated, looked up
  and released, once on the XLA traversal loop and once on each family of
  Pallas kernel (foresight, fat nodes of 128 keys, no foresight).

``--chips 4`` runs only the mesh-distributed index (``core.mesh_index``)
at four times the store's key count, one key-range slice per chip.

Every answer is checked against ``core.oracle.DictOracle`` on the same op
stream.  Earlier lines report sizes, device bytes, compile seconds, the
engine of each phase and the mismatch count; the last line is one JSON
object naming the device.  The script exits non-zero, and prints no
result, without a TPU, on any mismatch and on any error.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

# DBx1000's YCSB table (SYNTH_TABLE_SIZE in config-std.h): 10 * 2**20 rows.
DBX1000_ROWS = 10 * 2**20
# The keys one chip holds: the store's apply_ops still copies the fused
# table twice per call (see STORE_CUT), which caps the index below the
# DBx1000 table on 16 GB of HBM.
STORE_KEYS = 8_000_000
STORE_CUT = ("the write path (apply_ops) keeps two copies of the fused "
             "table per call, so a 10*2**20-key index needs more than the "
             "chip's HBM; cut to the largest key count at the next "
             "smaller table capacity")
ROW_WIDTH = 8            # int32 tokens per stored row (seq_len + 1)
BATCH = 4096             # lookup batch
N_UPDATES = 256          # keys per ingest / evict batch
SCAN_MAX = 512           # range_scan output slots
PAGES = 65536            # the size kvcache.py names as a device's share


class Mismatch(AssertionError):
    pass


def _log(*parts) -> None:
    print(*parts, flush=True)


def _compile_clock():
    """Seconds JAX spends tracing, lowering and compiling, as a counter."""
    import jax
    total = [0.0]

    def listener(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            total[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listener)
    return lambda: total[0]


def _device_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")


def _timed(name: str, clock, fn, *args):
    """``fn(*args)`` to completion on the device; logs its wall seconds and
    the part of them spent compiling."""
    import jax
    c0, t0 = clock(), time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    _log(f"{name}: seconds={time.perf_counter() - t0:.3f} "
         f"(of which compile {clock() - c0:.3f})")
    return out


def _tree_bytes(tree) -> int:
    import jax
    return sum(int(a.nbytes) for a in jax.tree.leaves(tree))


def _unique_keys(rng, n: int, exclude=None):
    """``n`` distinct int32 keys in (KEY_MIN, KEY_MAX), sorted, none in
    ``exclude``."""
    import numpy as np
    out = np.empty(0, np.int64)
    while out.size < n:
        draw = rng.integers(1, 2**31 - 1, size=n - out.size + n // 8 + 16)
        out = np.unique(np.concatenate([out, draw]))
        if exclude is not None:
            out = out[~np.isin(out, exclude)]
    return np.sort(rng.permutation(out)[:n]).astype(np.int32)


def _check(name: str, got, want, mismatches: dict) -> None:
    import numpy as np
    got = np.asarray(got)
    want = np.asarray(want)
    bad = int(np.sum(got != want)) if got.shape == want.shape else -1
    mismatches[name] = mismatches.get(name, 0) + (bad if bad >= 0 else 1)
    if bad:
        raise Mismatch(f"{name}: {bad if bad > 0 else 'shape'} mismatches "
                       f"against DictOracle (got shape {got.shape}, want "
                       f"{want.shape})")


def _expect_lookup(oracle, q):
    import numpy as np
    found = np.array([k in oracle.d for k in q.tolist()])
    vals = np.array([oracle.d.get(k, -1) for k in q.tolist()], np.int32)
    return found, vals


# ---------------------------------------------------------------------------
# Phase: sample store
# ---------------------------------------------------------------------------

def store_phase(seed: int, n_keys: int, clock) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import skiplist as sl
    from repro.core.oracle import DictOracle
    from repro.data.store import IndexedSampleStore, StoreConfig

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    levels = math.ceil(math.log2(n_keys)) + 2        # fig6 convention
    keys = _unique_keys(rng, n_keys)
    rows = rng.integers(0, 2**31 - 1, size=(n_keys, ROW_WIDTH),
                        dtype=np.int32)
    oracle = DictOracle()
    oracle.d = dict(zip(keys.tolist(), range(n_keys)))
    _log(f"store.host_setup (keys, rows, oracle): "
         f"seconds={time.perf_counter() - t0:.3f}")
    cfg = StoreConfig(n_samples=n_keys, seq_len=ROW_WIDTH - 1,
                      index_levels=levels, foresight=True, use_kernel=False,
                      seed=seed)
    c0, t0 = clock(), time.time()
    store = IndexedSampleStore(cfg, rows=rows, keys=keys)
    jax.block_until_ready(store.index)
    build_s = time.time() - t0
    _log(f"store: keys={n_keys} levels={levels} "
         f"capacity={store.index.capacity} row_width={ROW_WIDTH} "
         f"build_seconds={build_s:.3f} (of which compile "
         f"{clock() - c0:.3f})")
    if n_keys < DBX1000_ROWS:
        _log(f"store: cut from DBx1000's {DBX1000_ROWS} rows to {n_keys}: "
             f"{STORE_CUT}")

    mism: dict = {}
    host_rows = rows

    # lookups: half present keys, half random (mostly absent)
    for b in range(3):
        q = np.concatenate([rng.choice(keys, BATCH // 2),
                            rng.integers(1, 2**31 - 1, BATCH // 2)
                            ]).astype(np.int32)
        found, vals = _timed(f"store.lookup[{b}]", clock, store.lookup,
                             jnp.asarray(q))
        want_f, want_v = _expect_lookup(oracle, q)
        _check("lookup.found", found, want_f, mism)
        _check("lookup.vals", vals, want_v, mism)

    q = rng.choice(keys, BATCH).astype(np.int32)
    q[::7] = rng.integers(1, 2**31 - 1, q[::7].size)
    got_rows, found = _timed("store.get_batch", clock, store.get_batch,
                             jnp.asarray(q))
    want_f, want_v = _expect_lookup(oracle, q)
    _check("get_batch.found", found, want_f, mism)
    _check("get_batch.rows", got_rows,
           host_rows[np.where(want_f, want_v, 0)], mism)

    # ingest: new keys plus a few already present (upserts report 0)
    new = _unique_keys(rng, N_UPDATES, exclude=keys)
    ing = np.concatenate([new[:-16], rng.choice(keys, 16)]).astype(np.int32)
    rng.shuffle(ing)
    ing_rows = rng.integers(0, n_keys, ing.size).astype(np.int32)
    res = _timed("store.ingest", clock, store.ingest, jnp.asarray(ing),
                 jnp.asarray(ing_rows))
    want = [oracle.insert(k, v) for k, v in zip(ing.tolist(),
                                                 ing_rows.tolist())]
    _check("ingest", res, np.array(want, np.int32), mism)

    # evict: ingested keys, original keys and absent keys
    ev = np.concatenate([ing[:N_UPDATES // 2], rng.choice(keys, 96),
                         _unique_keys(rng, 32, exclude=np.concatenate(
                             [keys, ing]))]).astype(np.int32)
    res = _timed("store.evict", clock, store.evict, jnp.asarray(ev))
    want = [oracle.delete(k) for k in ev.tolist()]
    _check("evict", res, np.array(want, np.int32), mism)

    q = np.concatenate([ing, ev, rng.choice(keys, BATCH - ing.size - ev.size)
                        ]).astype(np.int32)
    found, vals = _timed("store.lookup[after_updates]", clock, store.lookup,
                         jnp.asarray(q))
    want_f, want_v = _expect_lookup(oracle, q)
    _check("lookup_after_updates.found", found, want_f, mism)
    _check("lookup_after_updates.vals", vals, want_v, mism)

    lo = int(rng.choice(keys))
    hi = lo + (2**31 - 1 - lo) // 2
    sk, sv, cnt = _timed("store.range_scan", clock, store.range_scan, lo, hi,
                         SCAN_MAX)
    live = np.fromiter(oracle.d, np.int64, len(oracle.d))
    in_range = np.sort(live[(live >= lo) & (live < hi)])
    want_k = np.full(SCAN_MAX, 2**31 - 1, np.int64)
    want_k[:min(SCAN_MAX, in_range.size)] = in_range[:SCAN_MAX]
    want_v = np.array([oracle.d.get(int(k), -1) for k in want_k], np.int32)
    _check("range_scan.count", cnt, min(SCAN_MAX, in_range.size), mism)
    _check("range_scan.keys", sk, want_k, mism)
    _check("range_scan.vals", sv, want_v, mism)

    # the write path's device footprint at this size, from the compiler
    t0 = time.perf_counter()
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          store.index)
    op = jax.ShapeDtypeStruct((N_UPDATES,), jnp.int32)
    ma = jax.jit(sl.apply_ops, donate_argnums=(0,)).lower(
        shapes, op, op, op).compile().memory_analysis()
    _log(f"store: apply_ops memory_analysis: argument="
         f"{ma.argument_size_in_bytes} output={ma.output_size_in_bytes} "
         f"temp={ma.temp_size_in_bytes} alias={ma.alias_size_in_bytes} "
         f"(compiled in {time.perf_counter() - t0:.3f} s)")
    dev = jax.devices()[0]
    _log(f"store: index_bytes={_tree_bytes(store.index)} "
         f"rows_bytes={int(store.rows.nbytes)} {_device_bytes(dev)}")
    _log("store: engine=XLA traversal loop (search_fast reads, apply_ops "
         "scan writes, range_scan walk)")
    _log(f"store: oracle mismatches={sum(mism.values())} over "
         f"{len(mism)} checks")
    return mism


# ---------------------------------------------------------------------------
# Phase: page table
# ---------------------------------------------------------------------------

def page_table_phase(seed: int, use_kernel: bool, clock, *,
                     node_width: int = 1, foresight: bool = True) -> dict:
    import jax
    import numpy as np
    from repro.core.oracle import DictOracle
    from repro.serving.kvcache import PagedCacheConfig, PageTable, page_key

    rng = np.random.default_rng(seed + 1)
    c0 = clock()
    pt = PageTable(PagedCacheConfig(n_pages=PAGES, use_kernel=use_kernel,
                                    seed=seed, node_width=node_width,
                                    foresight=foresight))
    engine = "Pallas kernel" if use_kernel else "XLA traversal loop"
    variant = f"node_width={node_width} foresight={foresight}"
    tag0 = (f"page_table.{'pallas' if use_kernel else 'xla'}.nw{node_width}"
            f"{'' if foresight else '.base'}")
    oracle = DictOracle()
    mism: dict = {}
    live = {}                                   # seq -> n_blocks

    def alloc(seqs):
        for s in seqs:
            nb = int(rng.choice([PAGES // 1024, PAGES // 512,
                                 PAGES // 256]))
            pages = pt.alloc(np.full(nb, s), np.arange(nb))
            for b, p in enumerate(pages.tolist()):
                if not oracle.insert(int(page_key(s, b)), p):
                    raise Mismatch(f"page table: ({s}, {b}) mapped twice")
            live[s] = nb

    def check_all(tag):
        seqs = np.array(sorted(set(live) | set(range(0, 200, 3))), np.int64)
        blocks = rng.integers(0, PAGES // 128, 4096)
        sq = rng.choice(seqs, 4096)
        found, pages = pt.lookup(sq, blocks)
        q = page_key(sq, blocks).astype(np.int64)
        want_f, want_v = _expect_lookup(oracle, q)
        _check(f"{tag0}.{tag}.found", found, want_f, mism)
        _check(f"{tag0}.{tag}.pages", pages, want_v, mism)

    alloc(range(0, 96))
    check_all("after_alloc")
    for s in list(live)[::2]:
        freed = pt.release(s, live.pop(s))
        want = sum(oracle.delete(int(page_key(s, b))) for b in range(4096)
                   if int(page_key(s, b)) in oracle.d)
        _check(f"{tag0}.release", freed, want, mism)
    check_all("after_release")
    alloc(range(96, 160))
    check_all("after_realloc")
    if len(set(oracle.d.values())) != len(oracle.d):
        raise Mismatch("page table: one physical page mapped twice")
    _check(f"{tag0}.n_live", pt.n_live, len(oracle.d), mism)
    _log(f"page_table[{engine}, {variant}]: n_pages={PAGES} shards="
         f"{pt.index.n_shards} live_pages={pt.n_live} "
         f"compile_seconds={clock() - c0:.3f} "
         f"{_device_bytes(jax.devices()[0])}")
    _log(f"page_table[{engine}, {variant}]: engine={engine}; "
         f"oracle mismatches="
         f"{sum(mism.values())} over {len(mism)} checks")
    return mism


# ---------------------------------------------------------------------------
# Phase: the mesh index on four chips
# ---------------------------------------------------------------------------

def mesh_phase(seed: int, n_keys: int, n_devices: int, clock) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import mesh_index as mi
    from repro.core import skiplist as sl
    from repro.core.oracle import DictOracle
    from repro.launch.mesh import make_index_mesh

    rng = np.random.default_rng(seed + 2)
    mesh = make_index_mesh(n_devices)
    levels = math.ceil(math.log2(n_keys // n_devices)) + 2
    keys = _unique_keys(rng, n_keys)
    vals = np.arange(n_keys, dtype=np.int32)
    c0, t0 = clock(), time.time()
    mx = mi.build_mesh_index(jnp.asarray(keys), jnp.asarray(vals),
                             n_devices=n_devices, n_shards=4, levels=levels,
                             seed=seed, mesh=mesh)
    jax.block_until_ready(mx)
    _log(f"mesh: keys={n_keys} devices={n_devices} levels={levels} "
         f"shards_per_device={mx.local_shards} "
         f"shard_capacity={mx.shard_capacity} "
         f"build_seconds={time.time() - t0:.3f} (of which compile "
         f"{clock() - c0:.3f})")
    leaf = mx.local.shards.keys
    placement = sorted((s.index[0].start, s.device.id)
                       for s in leaf.addressable_shards)
    _log(f"mesh: slice placement (slice, device id)={placement} "
         f"sharding={leaf.sharding}")

    oracle = DictOracle()
    oracle.d = dict(zip(keys.tolist(), vals.tolist()))
    mism: dict = {}
    for b in range(3):
        q = np.concatenate([rng.choice(keys, BATCH // 2),
                            rng.integers(1, 2**31 - 1, BATCH // 2)
                            ]).astype(np.int32)
        found, got = mi.search_mesh(mx, jnp.asarray(q), mesh=mesh)
        want_f, want_v = _expect_lookup(oracle, q)
        _check("mesh.search.found", found, want_f, mism)
        _check("mesh.search.vals", got, want_v, mism)

    new = _unique_keys(rng, N_UPDATES, exclude=keys)
    ops = np.concatenate([np.full(N_UPDATES, sl.OP_INSERT),
                          np.full(N_UPDATES // 2, sl.OP_DELETE),
                          np.full(N_UPDATES // 2, sl.OP_READ)])
    ks = np.concatenate([new, rng.choice(keys, N_UPDATES // 2),
                         rng.choice(keys, N_UPDATES // 2)]).astype(np.int32)
    perm = rng.permutation(ks.size)
    ops, ks = ops[perm].astype(np.int32), ks[perm]
    vs = rng.integers(0, 2**30, ks.size).astype(np.int32)
    mx, res, _ = mi.apply_ops_mesh(mx, jnp.asarray(ops), jnp.asarray(ks),
                                   jnp.asarray(vs), mesh=mesh)
    want = []
    for o, k, v in zip(ops.tolist(), ks.tolist(), vs.tolist()):
        if o == sl.OP_INSERT:
            want.append(oracle.insert(k, v))
        elif o == sl.OP_DELETE:
            want.append(oracle.delete(k))
        else:
            want.append(k in oracle.d)
    _check("mesh.apply_ops", res, np.array(want, np.int32), mism)

    q = np.concatenate([ks, rng.choice(keys, BATCH - ks.size)]
                       ).astype(np.int32)
    found, got = mi.search_mesh(mx, jnp.asarray(q), mesh=mesh)
    want_f, want_v = _expect_lookup(oracle, q)
    _check("mesh.search_after_apply.found", found, want_f, mism)
    _check("mesh.search_after_apply.vals", got, want_v, mism)
    _check("mesh.total_n", mi.total_n_mesh(mx), len(oracle.d), mism)
    for dev in jax.devices()[:n_devices]:
        _log(f"mesh: device {dev.id}: {_device_bytes(dev)}")
    _log("mesh: engine=XLA traversal loop under shard_map (search_mesh, "
         "apply_ops_mesh)")
    _log(f"mesh: oracle mismatches={sum(mism.values())} over "
         f"{len(mism)} checks")
    return mism


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    src = pathlib.Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: {src / 'repro'} not found; run the script from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}; "
              "refusing to run on another backend", file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 3

    from repro.launch.compile_cache import enable_compile_cache
    _log(f"compile cache: {enable_compile_cache()}")
    clock = _compile_clock()
    _log(f"device: {devices[0].device_kind} x{len(devices)} "
         f"jax={jax.__version__} seed={args.seed}")

    t0 = time.time()
    if args.chips == 4:
        phases = [("mesh", lambda: mesh_phase(args.seed, 4 * STORE_KEYS, 4,
                                              clock))]
    else:
        phases = [("store", lambda: store_phase(args.seed, STORE_KEYS, clock)),
                  ("page_table[xla]",
                   lambda: page_table_phase(args.seed, False, clock)),
                  ("page_table[pallas]",
                   lambda: page_table_phase(args.seed, True, clock)),
                  ("page_table[pallas fat]",
                   lambda: page_table_phase(args.seed, True, clock,
                                            node_width=128)),
                  ("page_table[pallas base]",
                   lambda: page_table_phase(args.seed, True, clock,
                                            foresight=False))]
    mism = {}
    for name, phase in phases:
        t1, c1 = time.time(), clock()
        mism.update(phase())
        _log(f"{name}: phase_seconds={time.time() - t1:.3f} "
             f"compile_seconds={clock() - c1:.3f}")
    _log(f"total: seconds={time.time() - t0:.3f} compile_seconds="
         f"{clock():.3f} oracle_mismatches={sum(mism.values())}")
    if sum(mism.values()):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
