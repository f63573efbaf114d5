"""Mesh-distributed key-space index: equivalence, routing, fuzz.

The contract under test: ``core.mesh_index`` / ``kernels.mesh_launch``
are BIT-IDENTICAL to the single-device ``ShardedSkipList`` engine on the
same key/op stream — the device partition, ``all_to_all`` exchange and
inverse permutation are pure data movement and must never change a
result flag, a found mask, or a value.

Runs at every device count available in the process: 1 (always), plus 2
and the full count when the CI mesh lane forces
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  The flag must be
set before jax initializes, so under a single-process tier-1 run the
multi-device cases self-skip rather than re-initialize the backend.
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import mesh_index as mi
from repro.core import sharded as shd
from repro.core import skiplist as sl
from repro.core.oracle import DictOracle
from repro.kernels import mesh_launch as ml
from repro.kernels import ops as kops
from repro.launch import mesh as lmesh

SPAN = 1 << 16
N_AVAIL = len(jax.devices())
DEVICE_COUNTS = sorted({d for d in (1, 2, N_AVAIL) if d <= N_AVAIL})
_MESHES = {}


def _mesh(d):
    """One mesh per device count — keeps the lru_cached jits warm."""
    if d not in _MESHES:
        _MESHES[d] = lmesh.make_index_mesh(d)
    return _MESHES[d]


def _pair(n=192, n_shards=4, levels=8, seed=0, n_devices=1, span=SPAN):
    """(mesh index, equivalent single-device index, keys, rng)."""
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(span, n, replace=False)).astype(np.int32)
    vals = (keys * 3).astype(np.int32)
    # capacity sized for the WHOLE key set per device: skewed op batches
    # route to one device, and a per-device capacity fail would (validly)
    # diverge from the big single-device reference — the same headroom
    # rule the mesh page table applies
    cap = shd.shard_capacity_for(n, n_shards)
    mx = mi.build_mesh_index(jnp.asarray(keys), jnp.asarray(vals),
                             mesh=_mesh(n_devices), n_devices=n_devices, n_shards=n_shards,
                             capacity=cap, levels=levels, seed=seed)
    ref = shd.build_sharded(jnp.asarray(keys), jnp.asarray(vals),
                            n_shards=n_shards, levels=levels, seed=seed)
    return mx, ref, keys, rng


def _probes(keys, rng, n_miss=64):
    return np.concatenate([keys, rng.integers(0, SPAN, n_miss)
                           ]).astype(np.int32)


# ---------------------------------------------------------------------------
# Build + invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", DEVICE_COUNTS)
def test_build_and_invariant(d):
    mx, ref, keys, rng = _pair(n_devices=d)
    assert mx.n_devices == d
    assert bool(mi.check_mesh_invariant(mx, expect_n=len(keys)))
    assert int(mi.total_n_mesh(mx)) == len(keys)
    assert int(jnp.sum(mi.device_live(mx))) == len(keys)


@pytest.mark.parametrize("d", DEVICE_COUNTS)
def test_search_equivalence_uniform(d):
    mx, ref, keys, rng = _pair(n_devices=d)
    q = jnp.asarray(_probes(keys, rng))
    f, v = mi.search_mesh(mx, q, mesh=_mesh(d))
    ef, ev = shd.search_sharded(ref, q)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(ef))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(ev))


@pytest.mark.parametrize("d", DEVICE_COUNTS)
def test_search_equivalence_zipf(d):
    mx, ref, keys, rng = _pair(n_devices=d, seed=3)
    hot = int(rng.integers(0, SPAN - 4096))
    q = jnp.asarray((hot + (rng.zipf(1.2, 160) - 1) % 4096).astype(np.int32))
    f, v = mi.search_mesh(mx, q, mesh=_mesh(d))
    ef, ev = shd.search_sharded(ref, q)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(ef))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(ev))


@pytest.mark.parametrize("d", DEVICE_COUNTS)
def test_kernel_search_equivalence(d):
    mx, ref, keys, rng = _pair(n_devices=d, seed=5)
    q = jnp.asarray(_probes(keys, rng))
    r = ml.search_kernel_mesh(mx, q, mesh=_mesh(d))
    er = kops.search_kernel_sharded(ref, q)
    np.testing.assert_array_equal(np.asarray(r.found), np.asarray(er.found))
    np.testing.assert_array_equal(np.asarray(r.vals), np.asarray(er.vals))
    # unified dispatch front door takes the same path
    r2 = kops.search_kernel(mx, q, mesh=_mesh(d))
    np.testing.assert_array_equal(np.asarray(r2.vals), np.asarray(er.vals))


def test_kernel_search_mesh_requires_mesh():
    mx, _, _, _ = _pair()
    with pytest.raises(ValueError, match="mesh"):
        kops.search_kernel(mx, jnp.zeros(4, jnp.int32))


# ---------------------------------------------------------------------------
# Mixed-op apply equivalence + linearization
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", DEVICE_COUNTS)
def test_apply_equivalence_mixed_ops(d):
    mx, ref, keys, rng = _pair(n_devices=d, seed=7)
    batch = 96
    kk = rng.integers(0, SPAN, batch).astype(np.int32)
    kk[: len(keys) // 4] = rng.choice(keys, len(keys) // 4, replace=False)
    ops = rng.integers(0, 3, batch).astype(np.int32)
    vv = (kk * 7 + 1).astype(np.int32)
    mx2, res, stats = mi.apply_ops_mesh(
        mx, jnp.asarray(ops), jnp.asarray(kk), jnp.asarray(vv),
        mesh=_mesh(d), rebalance=True)
    ref2, eres = shd.apply_ops_sharded(ref, jnp.asarray(ops),
                                       jnp.asarray(kk), jnp.asarray(vv),
                                       rebalance=True)
    np.testing.assert_array_equal(np.asarray(res), np.asarray(eres))
    # post-apply searches stay bit-identical and invariants hold
    probe = jnp.asarray(_probes(np.unique(kk), rng))
    f, v = mi.search_mesh(mx2, probe, mesh=_mesh(d))
    ef, ev = shd.search_sharded(ref2, probe)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(ef))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(ev))
    n_live = int(shd.total_n(ref2))
    assert bool(mi.check_mesh_invariant(mx2, expect_n=n_live))
    # load counters: every real lane was routed exactly once
    assert int(jnp.sum(stats.routed)) == batch
    assert int(jnp.sum(stats.live)) == n_live


# ---------------------------------------------------------------------------
# Boundary keys, empty lanes, exchange round trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", DEVICE_COUNTS)
def test_boundary_key_routing_roundtrip(d):
    """Keys EQUAL to device-slice boundaries route to the owning device
    and round-trip bit-identically (the off-by-one hot spot)."""
    mx, ref, keys, rng = _pair(n_devices=d, seed=11)
    db = np.asarray(mx.device_boundaries)
    edge = []
    for i, b in enumerate(db):
        if int(b) != int(sl.KEY_MIN):
            edge += [int(b), int(b) - 1, int(b) + 1]
    if not edge:               # d == 1: the only boundary is KEY_MIN
        edge = [int(keys[0]), int(keys[-1])]
    q = jnp.asarray(np.array(edge, np.int32))
    did = np.asarray(mi.route_devices(mx, q))
    for b, dev in zip(edge, did):
        lo = int(db[dev])
        hi = int(db[dev + 1]) if dev + 1 < d else int(sl.KEY_MAX)
        assert lo <= b < hi, f"key {b} routed to device {dev} [{lo},{hi})"
    f, v = mi.search_mesh(mx, q, mesh=_mesh(d))
    ef, ev = shd.search_sharded(ref, q)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(ef))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(ev))
    # inserting AT every boundary lands on the owner, invariants intact
    ops = jnp.full((len(edge),), sl.OP_INSERT, jnp.int32)
    vv = jnp.asarray(np.arange(len(edge), dtype=np.int32) + 1000)
    mx2, res, _ = mi.apply_ops_mesh(mx, ops, q, vv, mesh=_mesh(d))
    ref2, eres = shd.apply_ops_sharded(ref, ops, q, vv)
    np.testing.assert_array_equal(np.asarray(res), np.asarray(eres))
    assert bool(mi.check_mesh_invariant(mx2,
                                        expect_n=int(shd.total_n(ref2))))


@pytest.mark.parametrize("d", DEVICE_COUNTS)
def test_empty_lanes_after_all_to_all(d):
    """A batch routed entirely to ONE device leaves every other device's
    received lanes pure bucket fill — results must be unaffected."""
    mx, ref, keys, rng = _pair(n_devices=d, seed=13)
    db = np.asarray(mx.device_boundaries).astype(np.int64)
    # everything >= the last boundary routes to device d-1 (clamped off
    # the KEY_MIN sentinel for d == 1, where the only boundary IS it)
    lo = max(int(db[-1]), 0)
    q = jnp.asarray(np.clip(np.arange(40) + lo, None,
                            int(sl.KEY_MAX) - 1).astype(np.int32))
    f, v = mi.search_mesh(mx, q, mesh=_mesh(d))
    ef, ev = shd.search_sharded(ref, q)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(ef))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(ev))
    # same skew through the apply path: all other devices run no-op fill
    ops = jnp.full((40,), sl.OP_INSERT, jnp.int32)
    mx2, res, stats = mi.apply_ops_mesh(mx, ops, q, q, mesh=_mesh(d))
    ref2, eres = shd.apply_ops_sharded(ref, ops, q, q)
    np.testing.assert_array_equal(np.asarray(res), np.asarray(eres))
    routed = np.asarray(stats.routed)
    assert routed.sum() == 40 and (routed[:-1] == 0).all()


@pytest.mark.parametrize("d", DEVICE_COUNTS)
def test_exchange_roundtrip_identity_under_jit(d):
    """out-exchange then back-exchange is the identity on lane order —
    the inverse-permute contract, under jit, boundary keys included."""
    mesh = _mesh(d)
    C = 24
    rng = np.random.default_rng(17)
    db = np.sort(rng.choice(SPAN, d, replace=False)).astype(np.int32)
    db[0] = sl.KEY_MIN
    q_host = rng.integers(0, SPAN, d * C).astype(np.int32)
    q_host[:d] = db            # every boundary value rides the exchange

    def body(dbv, q):
        did = mi.route(dbv, q)
        (rq,), _, perm, starts, did_s = mi._exchange_out(
            did, (q,), (jnp.int32(0),), d)
        return mi._exchange_back(rq, perm, starts, did_s, d)

    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=(P(), P(lmesh.INDEX_AXIS)),
                               out_specs=P(lmesh.INDEX_AXIS),
                               check_vma=False))
    out = fn(jnp.asarray(db), jnp.asarray(q_host))
    np.testing.assert_array_equal(np.asarray(out), q_host)


# ---------------------------------------------------------------------------
# Validation errors (the mesh-assumption bugfix surface)
# ---------------------------------------------------------------------------

def test_mesh_index_validate_mismatch():
    mx, _, _, _ = _pair(n_devices=1)
    if N_AVAIL >= 2:
        with pytest.raises(ValueError, match="partitioned for"):
            mi.search_mesh(mx, jnp.zeros(4, jnp.int32), mesh=_mesh(2))
    dp = lmesh.make_host_mesh()      # ("data","model") axes: no "index"
    with pytest.raises(ValueError, match="lack"):
        mi.search_mesh(mx, jnp.zeros(4, jnp.int32), mesh=dp)


def test_make_index_mesh_rejects_oversubscription():
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        lmesh.make_index_mesh(N_AVAIL + 1)
    with pytest.raises(ValueError):
        lmesh.make_index_mesh(-3)


def test_production_mesh_fallback_warns():
    if N_AVAIL >= 256:
        pytest.skip("real production topology present")
    with pytest.warns(lmesh.MeshFallbackWarning):
        m = lmesh.make_production_mesh()
    assert m.devices.size == N_AVAIL


def test_validate_index_partition_divisibility():
    m = _mesh(max(DEVICE_COUNTS))
    d = max(DEVICE_COUNTS)
    assert lmesh.validate_index_partition(m, 4 * d) == 4
    if d > 1:
        with pytest.raises(ValueError, match="divide"):
            lmesh.validate_index_partition(m, 4 * d + 1)
    dp = lmesh.make_host_mesh()
    with pytest.raises(ValueError):
        lmesh.validate_index_partition(dp, 8)


# ---------------------------------------------------------------------------
# Differential fuzz vs the DictOracle (uniform + Zipf)
# ---------------------------------------------------------------------------

def _replay_mesh(seed, *, d, rounds=3, batch=48, zipf=False):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(SPAN, 48, replace=False)).astype(np.int32)
    # headroom for the worst case: every op of every round lands on one
    # device (a Zipf hot span fits inside a single device slice)
    cap = shd.shard_capacity_for(48 + rounds * batch, 4)
    mx = mi.build_mesh_index(jnp.asarray(keys), jnp.asarray(keys * 3),
                             mesh=_mesh(d), n_devices=d, n_shards=4, capacity=cap,
                             levels=8, seed=seed)
    oracle = DictOracle()
    for k in keys:
        oracle.insert(int(k), int(k) * 3)
    mesh = _mesh(d)
    for r in range(rounds):
        if zipf:
            hot = int(rng.integers(0, SPAN - 4096))
            kk = (hot + (rng.zipf(1.2, batch) - 1) % 4096).astype(np.int32)
        else:
            kk = rng.integers(0, SPAN, batch).astype(np.int32)
        ops = rng.integers(0, 3, batch).astype(np.int32)
        vv = (kk * 7 + r).astype(np.int32)
        expected = []
        for o, k, v in zip(ops, kk, vv):
            if o == sl.OP_INSERT:
                expected.append(int(oracle.insert(int(k), int(v))))
            elif o == sl.OP_DELETE:
                expected.append(int(oracle.delete(int(k))))
            else:
                expected.append(int(oracle.search(int(k))[0]))
        mx, res, _ = mi.apply_ops_mesh(mx, jnp.asarray(ops),
                                       jnp.asarray(kk), jnp.asarray(vv),
                                       mesh=mesh, rebalance=True)
        assert np.asarray(res).tolist() == expected
        assert bool(mi.check_mesh_invariant(mx, expect_n=len(oracle.d)))
        live = np.fromiter(oracle.d, np.int32, len(oracle.d))
        probe = np.concatenate([live, rng.integers(0, SPAN, 32)
                                ]).astype(np.int32)
        f, v = mi.search_mesh(mx, jnp.asarray(probe), mesh=mesh)
        exp_f = np.array([k in oracle.d for k in probe])
        exp_v = np.array([oracle.d.get(int(k), int(sl.NULL_VAL))
                          for k in probe], np.int32)
        np.testing.assert_array_equal(np.asarray(f), exp_f)
        np.testing.assert_array_equal(np.asarray(v), exp_v)


@pytest.mark.parametrize("d", DEVICE_COUNTS)
def test_fuzz_differential_dict_oracle(d):
    _replay_mesh(0, d=d)
    _replay_mesh(1, d=d, zipf=True)


# ---------------------------------------------------------------------------
# Serving-plane opt-in: the mesh page table is the same page table
# ---------------------------------------------------------------------------

@pytest.mark.skipif(N_AVAIL < 2, reason="needs >= 2 devices")
def test_kvcache_mesh_table_equivalent():
    from repro.serving.kvcache import PagedCacheConfig, PageTable

    def drive(pt, seed):
        rng = np.random.default_rng(seed)
        out = []
        for step in range(4):
            seqs = rng.integers(0, 40, 24).astype(np.int64)
            blks = rng.integers(0, 64, 24).astype(np.int64)
            ok, pages = pt.try_alloc(seqs, blks)
            out.append(ok.tolist())
            f, v = pt.lookup(seqs, blks)
            out.append(np.asarray(f).tolist())
            out.append(np.asarray(v).tolist())
            if step % 2:
                out.append(pt.release(int(seqs[0]), 64))
        out.append(pt.n_live)
        return out

    base = drive(PageTable(PagedCacheConfig(n_pages=512, n_shards=4,
                                            levels=8)), 9)
    pt = PageTable(PagedCacheConfig(n_pages=512, n_shards=4, levels=8,
                                   mesh_devices=2))
    assert pt.mesh is not None
    assert drive(pt, 9) == base
    assert bool(mi.check_mesh_invariant(pt.index, expect_n=pt.n_live))
