"""Fat-node (B-wide) layout: differential equivalence to the scalar seed.

The contract under test: ``node_width > 1`` is a LAYOUT change only.
Every observable — search found/vals, insert/delete result flags, range
scans, kernel outputs, mesh outputs — must be bit-identical to the
``node_width = 1`` scalar layout (and to the pure-python ``DictOracle``)
on the same key/op stream, across the monolithic, sharded, clustered,
and D-device mesh paths.  Node ids are exempt: they are layout-local
addresses (element-flat with stride ``capacity * node_width`` under fat).

Runs the seeded harness always and a hypothesis property sweep behind
``importorskip`` (uniform + Zipf(1.2) streams), mirroring the
``test_rebalance`` fuzz structure.
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mesh_index as mi
from repro.core import sharded as shd
from repro.core import skiplist as sl
from repro.core.oracle import DictOracle
from repro.kernels import ops as kops
from repro.kernels.foresight_traverse import QBLK

SPAN = 1 << 16
WIDTHS = [8, 128]
N_AVAIL = len(jax.devices())


def _keys(n, seed=0, span=SPAN):
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(span, n, replace=False)).astype(np.int32), rng


def _probe(keys, rng, extra=64):
    """Live keys + their neighbours + uniform misses, QBLK-padded."""
    probe = np.concatenate([
        keys, keys + 1, rng.integers(0, SPAN, extra)]).astype(np.int32)
    pad = (-len(probe)) % QBLK
    return np.concatenate([probe, probe[:1].repeat(pad)]).astype(np.int32)


# ---------------------------------------------------------------------------
# Monolithic core: search / search_fast / updates / range scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nw", WIDTHS)
def test_core_search_matches_scalar(nw):
    keys, rng = _keys(500)
    ref = sl.build(jnp.asarray(keys), jnp.asarray(keys * 3),
                   capacity=2048, levels=8)
    cap = sl.node_slots_for(1000, nw) + 8
    fat = sl.build(jnp.asarray(keys), jnp.asarray(keys * 3),
                   capacity=cap, levels=8, node_width=nw)
    q = jnp.asarray(_probe(keys, rng))
    r0, r1 = sl.search(ref, q), sl.search(fat, q)
    np.testing.assert_array_equal(np.asarray(r0.found), np.asarray(r1.found))
    np.testing.assert_array_equal(np.asarray(r0.vals), np.asarray(r1.vals))
    f0, v0 = sl.search_fast(ref, q)
    f1, v1 = sl.search_fast(fat, q)
    np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    # fat gathers tiles: strictly fewer dependent gathers than scalar
    assert int(r1.gathers) < int(r0.gathers)


@pytest.mark.parametrize("nw", WIDTHS)
def test_core_update_stream_matches_oracle(nw):
    keys, rng = _keys(200, seed=3)
    oracle = DictOracle()
    for k in keys:
        oracle.insert(int(k), int(k) * 3)
    cap = sl.node_slots_for(2048, nw) + 8
    st = sl.build(jnp.asarray(keys), jnp.asarray(keys * 3),
                  capacity=cap, levels=8, node_width=nw)
    for r in range(4):
        kk = rng.integers(0, SPAN, 64).astype(np.int32)
        ops = rng.integers(0, 3, 64).astype(np.int32)
        vv = (kk * 7 + r).astype(np.int32)
        expected = []
        for o, k, v in zip(ops, kk, vv):
            if o == sl.OP_INSERT:
                expected.append(int(oracle.insert(int(k), int(v))))
            elif o == sl.OP_DELETE:
                expected.append(int(oracle.delete(int(k))))
            else:
                expected.append(int(oracle.search(int(k))[0]))
        st, res = sl.apply_ops(st, jnp.asarray(ops), jnp.asarray(kk),
                               jnp.asarray(vv))
        assert np.asarray(res).tolist() == expected
        assert int(st.n) == len(oracle.d)
        assert bool(sl.check_fat_invariant(st))
    live = np.fromiter(oracle.d, np.int32, len(oracle.d))
    f, v = sl.search_fast(st, jnp.asarray(np.sort(live)))
    assert bool(jnp.all(f))
    lo, hi = int(SPAN * 0.2), int(SPAN * 0.8)
    ks, vs, cnt = sl.range_scan(st, jnp.int32(lo), jnp.int32(hi), 256)
    expect = [k for k in oracle.sorted_keys() if lo <= k < hi][:256]
    assert np.asarray(ks)[:int(cnt)].tolist() == expect


# ---------------------------------------------------------------------------
# Sharded: replay streams (uniform + Zipf), S = 9 straddle, rebalance on
# ---------------------------------------------------------------------------

def _replay_sharded(seed, nw, *, rounds=3, batch=36, zipf=False, n_init=24,
                    n_shards=4, levels=8):
    keys, rng = _keys(n_init, seed=seed)
    oracle = DictOracle()
    for k in keys:
        oracle.insert(int(k), int(k) * 3)
    shl = shd.build_sharded(jnp.asarray(keys), jnp.asarray(keys * 3),
                            n_shards=n_shards, levels=levels, seed=seed,
                            node_width=nw)
    ref = shd.build_sharded(jnp.asarray(keys), jnp.asarray(keys * 3),
                            n_shards=n_shards, levels=levels, seed=seed)
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
        args = (jnp.asarray(ops), jnp.asarray(kk), jnp.asarray(vv))
        shl, res = shd.apply_ops_sharded(shl, *args, rebalance=True)
        ref, res_ref = shd.apply_ops_sharded(ref, *args, rebalance=True)
        assert np.asarray(res).tolist() == expected
        assert np.asarray(res_ref).tolist() == expected
        probe = _probe(kk, rng)
        f1, v1 = shd.search_sharded(shl, jnp.asarray(probe))
        f0, v0 = shd.search_sharded(ref, jnp.asarray(probe))
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f0))
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v0))
        lo = int(rng.integers(0, SPAN // 2))
        hi = lo + int(rng.integers(1, SPAN // 2))
        k1, vv1, c1 = shd.range_scan_sharded(shl, jnp.int32(lo),
                                             jnp.int32(hi), 96)
        expect = [k for k in oracle.sorted_keys() if lo <= k < hi][:96]
        assert np.asarray(k1)[:int(c1)].tolist() == expect
    return shl


@pytest.mark.parametrize("nw", WIDTHS)
def test_sharded_streams_match_scalar_and_oracle(nw):
    _replay_sharded(0, nw)
    _replay_sharded(1, nw, zipf=True)


def test_shard_boundary_keys_exact():
    """Keys ON and adjacent to every shard boundary: the fat owner rule
    (predecessor node vs foreseen successor) must pick the right run."""
    keys, rng = _keys(800, seed=7)
    shl = shd.build_sharded(jnp.asarray(keys), jnp.asarray(keys * 3),
                            n_shards=8, levels=8, node_width=8)
    b = np.asarray(shl.boundaries).astype(np.int64)[1:]
    probe = np.concatenate([b - 1, b, b + 1]).astype(np.int32)
    pad = (-len(probe)) % QBLK
    probe = np.concatenate([probe, probe[:1].repeat(pad)]).astype(np.int32)
    f, v = shd.search_sharded(shl, jnp.asarray(probe))
    in_set = np.isin(probe, keys)
    np.testing.assert_array_equal(np.asarray(f), in_set)
    np.testing.assert_array_equal(
        np.asarray(v)[in_set], probe[in_set].astype(np.int64) * 3)


def test_straddle_stream_s9_fat():
    """Post-split S = 9 (not a power of two) with one block straddling all
    shards — the K-degeneration regression, now on the fat layout."""
    keys, rng = _keys(1200, seed=11)
    shl = shd.build_sharded(jnp.asarray(keys), jnp.asarray(keys * 3),
                            n_shards=8, levels=10, node_width=8)
    shl = shd.split_shard(shl, 3)              # S = 9
    assert shl.n_shards == 9
    S = shl.n_shards
    sids = np.asarray(shd.route(shl.boundaries, jnp.asarray(keys)))
    picks = np.array([keys[sids == s][0] for s in range(S)], np.int32)
    block = np.sort(np.concatenate(
        [picks, keys[:QBLK - S]])).astype(np.int32)
    res = kops.search_kernel_sharded(shl, jnp.asarray(block))
    assert bool(jnp.all(res.found))
    np.testing.assert_array_equal(np.asarray(res.vals),
                                  block.astype(np.int64) * 3)


# ---------------------------------------------------------------------------
# Kernels: monolithic + sharded dense/clustered launches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nw", WIDTHS)
@pytest.mark.parametrize("foresight", [False, True])
def test_kernel_monolithic_matches_scalar(nw, foresight):
    keys, rng = _keys(700, seed=5)
    ref = sl.build(jnp.asarray(keys), jnp.asarray(keys * 3),
                   capacity=2048, levels=8, foresight=foresight)
    cap = sl.node_slots_for(1400, nw) + 8
    fat = sl.build(jnp.asarray(keys), jnp.asarray(keys * 3),
                   capacity=cap, levels=8, foresight=foresight,
                   node_width=nw)
    q = jnp.asarray(_probe(keys, rng))
    r0 = kops.search_kernel(ref, q)
    r1 = kops.search_kernel(fat, q)
    np.testing.assert_array_equal(np.asarray(r0.found), np.asarray(r1.found))
    np.testing.assert_array_equal(np.asarray(r0.vals), np.asarray(r1.vals))


@pytest.mark.parametrize("nw", WIDTHS)
@pytest.mark.parametrize("cluster", [False, True])
def test_kernel_sharded_matches_scalar(nw, cluster):
    keys, rng = _keys(900, seed=6)
    fat = shd.build_sharded(jnp.asarray(keys), jnp.asarray(keys * 3),
                            n_shards=4, levels=8, node_width=nw)
    ref = shd.build_sharded(jnp.asarray(keys), jnp.asarray(keys * 3),
                            n_shards=4, levels=8)
    q = jnp.asarray(_probe(keys, rng))
    r1 = kops.search_kernel_sharded(fat, q, cluster=cluster)
    r0 = kops.search_kernel_sharded(ref, q, cluster=cluster)
    np.testing.assert_array_equal(np.asarray(r0.found), np.asarray(r1.found))
    np.testing.assert_array_equal(np.asarray(r0.vals), np.asarray(r1.vals))
    # element-flat fat node ids dereference to the probed key's value
    node = np.asarray(r1.node)
    served = node >= 0
    flat_v = np.asarray(fat.shards.fat_vals).reshape(-1)
    hit = served & np.asarray(r1.found)
    np.testing.assert_array_equal(flat_v[node[hit]], np.asarray(r1.vals)[hit])


# ---------------------------------------------------------------------------
# Mesh: D-device paths (self-skip when the backend has fewer devices)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("D", [1, 2, 8])
def test_mesh_matches_scalar(D):
    if D > N_AVAIL:
        pytest.skip(f"needs {D} devices, have {N_AVAIL}")
    from repro.launch import mesh as lmesh
    mesh = lmesh.make_index_mesh(D)
    keys, rng = _keys(600, seed=9)
    fat = mi.build_mesh_index(jnp.asarray(keys), jnp.asarray(keys * 3),
                              mesh=mesh, n_devices=D, n_shards=4, levels=8,
                              node_width=8)
    ref = mi.build_mesh_index(jnp.asarray(keys), jnp.asarray(keys * 3),
                              mesh=mesh, n_devices=D, n_shards=4, levels=8)
    assert fat.node_width == 8
    q = jnp.asarray(_probe(keys, rng))
    f1, v1 = mi.search_mesh(fat, q, mesh=mesh)
    f0, v0 = mi.search_mesh(ref, q, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f0))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v0))


# ---------------------------------------------------------------------------
# Hypothesis sweep (skips when hypothesis is absent)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fat_differential_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @settings(max_examples=8, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(seed=st.integers(0, 2**31 - 1), zipf=st.booleans(),
           nw=st.sampled_from(WIDTHS), batch=st.integers(8, 48))
    def check(seed, zipf, nw, batch):
        _replay_sharded(seed, nw, rounds=2, batch=batch, zipf=zipf)

    check()
