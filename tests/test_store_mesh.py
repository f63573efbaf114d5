"""The sample store over a mesh of devices (``StoreConfig.mesh_devices``)
against the one-device store and the plain reference
(``bench.reference.SortedIndex``): reads, inserts whose row lives on
another device, evictions, the spans and the mesh read's counters.

Four devices exist on the CPU only when
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` is set before JAX
starts, and the test process may have started it already.  So the checks
run in a child process (this file run as a script), which prints its
findings as one JSON line; each test asserts one of them.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
DEVICES = 4
N = 20_000
WIDTH = 8


def _xla_flags() -> str:
    kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    return " ".join(kept + [
        f"--xla_force_host_platform_device_count={DEVICES}"])


def run_child(script: pathlib.Path, timeout: float = 900) -> dict:
    """Run ``script`` on ``DEVICES`` host devices; its last line of output,
    as JSON."""
    env = {**os.environ, "XLA_FLAGS": _xla_flags(), "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, str(script)], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert p.returncode == 0, p.stderr[-6000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def found():
    return run_child(pathlib.Path(__file__))


def test_mesh_store_places_one_block_of_rows_per_device(found):
    assert found["devices"] == DEVICES
    assert found["row_blocks"] == [[-(-N // DEVICES), WIDTH]] * DEVICES


@pytest.mark.parametrize("batch", ["b1001", "b4096", "b3"])
def test_get_batch_matches_one_device_store_and_reference(found, batch):
    r = found["get_batch"][batch]
    assert r["missing"] > 0 and r["hits"] > 0
    assert r["rows_vs_one_device"] == 0 and r["found_vs_one_device"] == 0
    assert r["rows_vs_reference"] == 0 and r["found_vs_reference"] == 0


def test_lookup_matches_one_device_store_and_reference(found):
    r = found["lookup"]
    assert r["found_vs_one_device"] == r["vals_vs_one_device"] == 0
    assert r["found_vs_reference"] == r["vals_vs_reference"] == 0


def test_ingest_then_reads_of_rows_on_other_devices(found):
    r = found["ingest"]
    assert r["results_vs_one_device"] == 0 and r["results_vs_reference"] == 0
    assert r["upserts"] > 0 and r["cross_device_rows"] > 0
    assert r["rows_vs_one_device"] == 0 and r["rows_vs_reference"] == 0
    assert r["found_vs_reference"] == 0


def test_evict_matches_one_device_store(found):
    r = found["evict"]
    assert r["results_vs_one_device"] == 0 and r["removed"] > 0
    assert r["found_after_vs_one_device"] == 0
    assert r["found_after_vs_expected"] == 0


def test_mesh_spans_nest_inside_the_entry_points(found):
    assert found["spans"] == {
        "read.search_mesh": "store.get_batch",
        "store.gather_rows": "store.get_batch",
        "write.apply_ops_mesh": ["store.evict", "store.ingest"]}


def test_routed_lane_counters_add_up_only_under_a_trace(found):
    r = found["counters"]
    assert r["untraced"] == dict.fromkeys(r["expected"], 0)
    assert r["traced"] == r["expected"]
    assert r["after_untraced_call"] == r["expected"]
    # every device searches bucket fill beside its own lanes
    assert 0 < r["expected"]["lane_steps"] < r["expected"]["lane_slots"]


def test_range_scan_is_refused_on_a_mesh_store(found):
    assert found["range_scan"] == "NotImplementedError"


# ---------------------------------------------------------------------------
# The child: four host devices
# ---------------------------------------------------------------------------

def _mismatch(a, b) -> int:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return int(np.sum(np.any(a != b, axis=tuple(range(1, a.ndim)))
                      if a.ndim > 1 else a != b))


def counters() -> dict:
    from repro import obs
    return {**obs.snapshot(), **obs.snapshot(obs.ROUTED)}


def expected_counts(mx, batches) -> dict:
    """The counters of one mesh read of each batch, from each device's
    ``search_sharded_counted`` run alone on the lanes routed to it and on
    key 0, which each bucket's fill searches."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import sharded as shd
    local = [jax.tree.map(lambda a, d=d: a[d], mx.local)
             for d in range(DEVICES)]
    db = np.asarray(mx.device_boundaries)
    c = dict.fromkeys(("keys", "lane_steps", "lane_slots", "gathers",
                       "routed_lanes", "routed_max"), 0)
    for x in batches:
        slots = DEVICES * -(-x.size // DEVICES)
        dev = np.searchsorted(db, x, side="right") - 1
        lanes = np.bincount(dev, minlength=DEVICES)
        for d in range(DEVICES):
            probe = np.append(x[dev == d], 0).astype(np.int32)
            steps = np.asarray(
                shd.search_sharded_counted(local[d], jnp.asarray(probe))[2])
            trips = max(steps[:-1].max(initial=0),
                        steps[-1] if lanes[d] < slots else 0)
            c["lane_steps"] += int(steps[:-1].sum())
            c["lane_slots"] += int(trips) * slots
        c["keys"] += x.size
        c["routed_lanes"] += x.size
        c["routed_max"] += int(lanes.max()) * DEVICES
    c["gathers"] = c["lane_steps"]          # one a step with foresight
    return c


def _child() -> dict:
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import host_trace
    from bench import trace as btrace
    from bench.reference import SortedIndex
    from repro import obs
    from repro.core import mesh_index as mi
    from repro.data.store import IndexedSampleStore, StoreConfig

    out: dict = {"devices": len(jax.devices())}
    rng = np.random.default_rng(7)
    keys = np.sort(rng.choice(2**31 - 2, N, replace=False)).astype(np.int32)
    rows = rng.integers(-2**31, 2**31 - 1, (N, WIDTH)).astype(np.int32)

    def cfg(**kw):
        return StoreConfig(n_samples=N, seq_len=WIDTH - 1, index_levels=16,
                           seed=3, **kw)
    one = IndexedSampleStore(cfg(), rows=rows, keys=keys)
    mesh = IndexedSampleStore(cfg(mesh_devices=DEVICES), rows=rows,
                              keys=keys)
    ref = SortedIndex(keys, np.arange(N))
    out["row_blocks"] = [list(s.data.shape)
                         for s in mesh.rows.addressable_shards]

    def expect_rows(q):
        f, r = ref.lookup(q)
        return f, rows[np.where(f, r, 0)]

    def compare_reads(q):
        r1, f1 = one.get_batch(jnp.asarray(q))
        r2, f2 = mesh.get_batch(jnp.asarray(q))
        want_f, want_r = expect_rows(q)
        return {"hits": int(np.sum(want_f)), "missing": int(np.sum(~want_f)),
                "rows_vs_one_device": _mismatch(r2, r1),
                "found_vs_one_device": _mismatch(f2, f1),
                "rows_vs_reference": _mismatch(r2, want_r),
                "found_vs_reference": _mismatch(f2, want_f)}

    def probes(n_hit, n_miss):
        return np.concatenate([rng.choice(keys, n_hit),
                               rng.integers(0, 2**31 - 2, n_miss)]
                              ).astype(np.int32)
    out["get_batch"] = {"b1001": compare_reads(probes(700, 301)),
                        "b4096": compare_reads(probes(4000, 96)),
                        "b3": compare_reads(probes(2, 1))}

    q = probes(900, 123)
    f1, v1 = one.lookup(jnp.asarray(q))
    f2, v2 = mesh.lookup(jnp.asarray(q))
    want_f, want_v = ref.lookup(q)
    out["lookup"] = {"found_vs_one_device": _mismatch(f2, f1),
                     "vals_vs_one_device": _mismatch(v2, v1),
                     "found_vs_reference": _mismatch(f2, want_f),
                     "vals_vs_reference": _mismatch(v2, want_v)}

    # new keys, and a few present ones (upserts), each naming a row on
    # another device than its key's: YCSB's item mod records does so
    new = rng.integers(0, 2**31 - 2, 60).astype(np.int32)
    ins = np.concatenate([new, rng.choice(keys, 10)]).astype(np.int32)
    m = -(-N // DEVICES)
    db = np.asarray(mesh.index.device_boundaries)
    key_dev = np.searchsorted(db, ins, side="right") - 1
    rid = (((key_dev + 1 + np.arange(ins.size) % (DEVICES - 1)) % DEVICES)
           * m + rng.integers(0, m, ins.size))
    rid = np.minimum(rid, N - 1).astype(np.int32)
    a = one.ingest(jnp.asarray(ins), jnp.asarray(rid))
    b = mesh.ingest(jnp.asarray(ins), jnp.asarray(rid))
    want = ref.insert(ins, rid)
    r1, _ = one.get_batch(jnp.asarray(ins))
    r2, f2 = mesh.get_batch(jnp.asarray(ins))
    want_f, want_r = expect_rows(ins)
    out["ingest"] = {"results_vs_one_device": _mismatch(b, a),
                     "results_vs_reference": _mismatch(b, want),
                     "upserts": int(np.sum(want == 0)),
                     "cross_device_rows": int(np.sum(rid // m != key_dev)),
                     "rows_vs_one_device": _mismatch(r2, r1),
                     "rows_vs_reference": _mismatch(r2, want_r),
                     "found_vs_reference": _mismatch(f2, want_f)}

    gone = np.concatenate([new[:20], probes(0, 5)]).astype(np.int32)
    e1 = one.evict(jnp.asarray(gone))
    e2 = mesh.evict(jnp.asarray(gone))
    f_one = one.lookup(jnp.asarray(ins))[0]
    f_mesh = mesh.lookup(jnp.asarray(ins))[0]
    out["evict"] = {"results_vs_one_device": _mismatch(e2, e1),
                    "removed": int(np.sum(np.asarray(e2))),
                    "found_after_vs_one_device": _mismatch(f_mesh, f_one),
                    "found_after_vs_expected": _mismatch(
                        f_mesh, ~np.isin(ins, gone))}

    # spans and counters: one traced stretch of reads and writes
    q = probes(500, 12)
    obs.reset()
    mesh.get_batch(jnp.asarray(q))
    untraced = counters()
    expected = expected_counts(mesh.index, (q, q[:64]))
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    mesh.get_batch(jnp.asarray(q))
    mesh.lookup(jnp.asarray(q[:64]))
    mesh.ingest(jnp.asarray(new[20:30]), jnp.asarray(rid[20:30]))
    mesh.evict(jnp.asarray(new[20:25]))
    jax.block_until_ready(mesh.index)
    jax.profiler.stop_trace()
    traced = counters()
    mesh.get_batch(jnp.asarray(q))
    out["counters"] = {"untraced": untraced, "traced": traced,
                       "expected": expected,
                       "after_untraced_call": counters()}

    host = host_trace.load(btrace.find(tdir))["host"]
    spans = [h for h in host if h[0].startswith("repro.")]
    parents: dict = {}
    for name, s, e, thread in spans:
        if name in ("repro.read.search_mesh", "repro.store.gather_rows",
                    "repro.write.apply_ops_mesh"):
            outer = {p[0][len("repro."):] for p in spans
                     if p[3] == thread and p[0].startswith("repro.store.")
                     and p[0] != name and p[1] <= s and e <= p[2]}
            parents.setdefault(name[len("repro."):], set()).update(outer)
    out["spans"] = {k: sorted(v) if len(v) > 1 else next(iter(v))
                    for k, v in parents.items()}

    try:
        mesh.range_scan(0, 100, 8)
        out["range_scan"] = "served"
    except NotImplementedError:
        out["range_scan"] = "NotImplementedError"
    assert isinstance(mesh.index, mi.MeshShardedIndex)
    return out


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    print(json.dumps(_child()))
