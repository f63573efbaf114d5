"""Shard split/merge rebalancing: units, watermarks, differential fuzz.

The contract under test: rebalancing moves *boundaries*, never *contents*.
After any split / merge / repack / watermark pass — including the ones
``apply_ops_sharded(..., rebalance=True)`` interleaves with op batches —
the sharded index must stay bit-identical to the pure-python ``DictOracle``
(and to the monolithic skiplist) on every search, insert/delete result
flag, and range scan, while ``check_sharded_invariant`` holds with the
live count conserved.

The fuzz harness replays random op streams (uniform + Zipf keys) against
the oracle.  It runs twice: a hand-rolled seeded-random version that works
without hypothesis (this container has none), and a hypothesis property
sweep behind ``importorskip``.  ``REBALANCE_EXAMPLES`` scales both — the
CI ``rebalance-stress`` job sets it high.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sharded as shd
from repro.core import skiplist as sl
from repro.core.oracle import DictOracle
from repro.kernels import ops as kops
from repro.kernels.foresight_traverse import QBLK

SPAN = 1 << 16
EXAMPLES = int(os.environ.get("REBALANCE_EXAMPLES", "0"))


def _build(n=60, n_shards=4, levels=8, capacity=0, seed=0, span=SPAN):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(span, n, replace=False)).astype(np.int32)
    shl = shd.build_sharded(jnp.asarray(keys), jnp.asarray(keys * 3),
                            n_shards=n_shards, levels=levels,
                            capacity=capacity, seed=seed)
    oracle = DictOracle()
    for k in keys:
        oracle.insert(int(k), int(k) * 3)
    return shl, oracle, keys, rng


def _assert_matches_oracle(shl, oracle, rng, n_probe=48):
    """Search + range-scan differential against the DictOracle."""
    live = np.fromiter(oracle.d, np.int32, len(oracle.d)) if oracle.d \
        else np.zeros(0, np.int32)
    probe = np.concatenate([live,
                            rng.integers(0, SPAN, n_probe)]).astype(np.int32)
    f, v = shd.search_sharded(shl, jnp.asarray(probe))
    exp_f = np.array([k in oracle.d for k in probe])
    exp_v = np.array([oracle.d.get(int(k), int(sl.NULL_VAL))
                      for k in probe], np.int32)
    np.testing.assert_array_equal(np.asarray(f), exp_f)
    np.testing.assert_array_equal(np.asarray(v), exp_v)
    lo = int(rng.integers(0, SPAN))
    hi = lo + int(rng.integers(1, SPAN // 2))
    ks, vs, count = shd.range_scan_sharded(shl, jnp.int32(lo), jnp.int32(hi),
                                           96)
    expect = [k for k in oracle.sorted_keys() if lo <= k < hi][:96]
    assert np.asarray(ks)[:int(count)].tolist() == expect
    np.testing.assert_array_equal(
        np.asarray(vs)[:int(count)],
        np.array([oracle.d[k] for k in expect], np.int32))


# ---------------------------------------------------------------------------
# Structural units: split / merge / repack preserve contents + invariants
# ---------------------------------------------------------------------------

def test_split_at_median_preserves_contents():
    shl, oracle, keys, rng = _build()
    n0 = int(shd.total_n(shl))
    shl2 = shd.split_shard(shl, 1)
    assert shl2.n_shards == shl.n_shards + 1
    assert bool(shd.check_sharded_invariant(shl2, expect_n=n0))
    b = np.asarray(shl2.boundaries).astype(np.int64)  # diff overflows int32
    assert np.all(np.diff(b) >= 0)                 # flat sorted routing array
    _assert_matches_oracle(shl2, oracle, rng)


def test_split_at_explicit_key_and_range_guard():
    shl, oracle, keys, rng = _build()
    b = np.asarray(shl.boundaries)
    at = int(b[1]) + 1                             # just inside shard 1
    shl2 = shd.split_shard(shl, 1, at_key=at)
    assert int(np.asarray(shl2.boundaries)[2]) == at
    assert bool(shd.check_sharded_invariant(shl2, expect_n=len(oracle.d)))
    _assert_matches_oracle(shl2, oracle, rng)
    with pytest.raises(ValueError, match="outside"):
        shd.split_shard(shl, 1, at_key=int(b[1]))   # == own boundary
    with pytest.raises(ValueError, match="outside"):
        shd.split_shard(shl, 1, at_key=int(b[2]))   # == next boundary


def test_merge_preserves_contents_and_rejects_overflow():
    shl, oracle, keys, rng = _build()
    shl2 = shd.merge_shards(shl, 2)
    assert shl2.n_shards == shl.n_shards - 1
    assert bool(shd.check_sharded_invariant(shl2, expect_n=len(oracle.d)))
    _assert_matches_oracle(shl2, oracle, rng)
    # merging two genuinely full shards must raise, not truncate
    full, _, _, _ = _build(n=100, n_shards=2, capacity=64)  # 50 live each
    with pytest.raises(ValueError, match="exceeds"):
        shd.merge_shards(full, 0)                  # 50 + 50 + 2 > 64
    # repack refuses a shard count the capacity cannot hold either
    with pytest.raises(ValueError, match="capacity"):
        shd.repack(full, 1)                        # 100 + 2 > 64


def test_repack_equalizes_occupancy():
    shl, oracle, keys, rng = _build(n=60, n_shards=4)
    shl2 = shd.split_shard(shl, 0)                 # skew the partition
    shl2 = shd.split_shard(shl2, 0)
    ns_before = np.asarray(shl2.shards.n)
    shl3 = shd.repack(shl2)                        # keeps S, levels ns
    ns = np.asarray(shl3.shards.n)
    assert shl3.n_shards == shl2.n_shards
    assert ns.max() - ns.min() <= 1                # even to within one key
    assert ns.max() < ns_before.max() or ns_before.max() - ns_before.min() <= 1
    assert bool(shd.check_sharded_invariant(shl3, expect_n=len(oracle.d)))
    _assert_matches_oracle(shl3, oracle, rng)
    # changing the shard count on the way through
    shl4 = shd.repack(shl2, n_shards=2)
    assert shl4.n_shards == 2
    assert bool(shd.check_sharded_invariant(shl4, expect_n=len(oracle.d)))
    _assert_matches_oracle(shl4, oracle, rng)


def test_rebalance_driver_watermarks():
    # capacity 64 -> usable 62; 100 keys over 2 shards = 50 each, above the
    # 0.75 high-water mark (46.5) -> the driver must split both
    shl, oracle, keys, rng = _build(n=100, n_shards=2, capacity=64)
    assert np.asarray(shl.shards.n).max() > 0.75 * 62
    shl2, stats = shd.rebalance(shl)
    assert stats.splits >= 1
    ns = np.asarray(shl2.shards.n)
    assert np.all(ns <= 0.75 * 62)                 # no shard above high water
    assert bool(shd.check_sharded_invariant(shl2, expect_n=len(oracle.d)))
    _assert_matches_oracle(shl2, oracle, rng)
    # now delete most keys: underfull neighbours must merge back
    drop = keys[::2]
    ops = jnp.full((drop.size,), sl.OP_DELETE, jnp.int32)
    shl3, res = shd.apply_ops_sharded(shl2, ops, jnp.asarray(drop),
                                      jnp.zeros(drop.size, jnp.int32))
    for k in drop:
        oracle.delete(int(k))
    assert bool(jnp.all(res == 1))
    shl4, stats2 = shd.rebalance(shl3)
    assert stats2.merges >= 1
    assert shl4.n_shards < shl3.n_shards
    assert bool(shd.check_sharded_invariant(shl4, expect_n=len(oracle.d)))
    _assert_matches_oracle(shl4, oracle, rng)


def test_apply_ops_rebalance_under_jit_stays_active():
    """rebalance=True inside a traced computation no longer degrades to
    fixed boundaries: it dispatches to core.rebalance_traced and splits in
    place at the state's static shard ceiling.  A burst that would exhaust
    one shard of the padded state must complete with every insert accepted
    and results identical to the eager (host-loop) rebalance."""
    from repro.core import rebalance_traced as rbt
    shl, oracle, keys, rng = _build(n=40, n_shards=4, capacity=16)
    padded = rbt.pad_shards(shl, 16)
    # hammer shard 0's key range hard enough to need guard splits
    hot = int(np.asarray(shl.boundaries)[1])
    kk = np.setdiff1d(np.unique(rng.integers(0, hot, 24).astype(np.int32)),
                      keys)                        # all genuinely new
    ops = jnp.full((kk.size,), sl.OP_INSERT, jnp.int32)

    @jax.jit
    def step(state, o, k, v):
        return shd.apply_ops_sharded(state, o, k, v, rebalance=True)

    shl_j, res_j = step(padded, ops, jnp.asarray(kk), jnp.asarray(kk * 2))
    shl_e, res_e = shd.apply_ops_sharded(shl, ops, jnp.asarray(kk),
                                         jnp.asarray(kk * 2),
                                         rebalance=True)
    assert bool(jnp.all(res_j == 1))               # no capacity failures
    np.testing.assert_array_equal(np.asarray(res_j), np.asarray(res_e))
    assert shl_j.n_shards == padded.n_shards       # static shape: ceiling
    assert int(rbt.live_shard_count(shl_j)) > int(rbt.live_shard_count(padded))
    for k in kk:
        oracle.insert(int(k), int(k) * 2)
    assert bool(shd.check_sharded_invariant(shl_j, expect_n=len(oracle.d)))
    _assert_matches_oracle(shl_j, oracle, rng)
    f_j, v_j = shd.search_sharded(shl_j, jnp.asarray(kk))
    f_e, v_e = shd.search_sharded(shl_e, jnp.asarray(kk))
    np.testing.assert_array_equal(np.asarray(f_j), np.asarray(f_e))
    np.testing.assert_array_equal(np.asarray(v_j), np.asarray(v_e))


def test_empty_sharded_grows_under_rebalance():
    shl = shd.empty_sharded(n_shards=1, capacity=16, levels=6)
    kk = jnp.asarray(np.arange(1, 100, 3, dtype=np.int32))
    ops = jnp.full(kk.shape, sl.OP_INSERT, jnp.int32)
    shl2, res = shd.apply_ops_sharded(shl, ops, kk, kk * 2, rebalance=True)
    assert bool(jnp.all(res == 1))                 # no capacity failure
    assert shl2.n_shards > 1                       # guard split ahead
    assert bool(shd.check_sharded_invariant(shl2, expect_n=int(kk.size)))
    f, v = shd.search_sharded(shl2, kk)
    assert bool(jnp.all(f))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(kk) * 2)


# ---------------------------------------------------------------------------
# Acceptance: Zipf(1.2) insert stream — fixed boundaries exhaust, rebalanced
# boundaries complete, results bit-identical to the monolithic oracle
# ---------------------------------------------------------------------------

def _zipf_stream(rng, n_batches=4, batch=32, hot_lo=0, hot_span=4096):
    """Zipf(1.2)-ranked keys folded into one hot key range (one shard)."""
    for _ in range(n_batches):
        kk = (hot_lo + (rng.zipf(1.2, batch) - 1) % hot_span).astype(np.int32)
        yield kk


def test_zipf_exhaustion_fixed_fails_rebalanced_completes():
    # 48 initial keys over 4 shards at capacity 16 (usable 14): every shard
    # starts at 12/14, and the Zipf stream hammers shard 0's key range.
    shl0, oracle0, keys, rng = _build(n=48, n_shards=4, capacity=16)
    hot_lo = int(keys[2])                          # inside shard 0
    batches = list(_zipf_stream(np.random.default_rng(7), hot_lo=hot_lo))

    # --- fixed boundaries: some NEW insert must come back 0 ----------------
    shl = shl0
    oracle = DictOracle()
    oracle.d.update(oracle0.d)
    failed = 0
    for kk in batches:
        ops = jnp.full((kk.size,), sl.OP_INSERT, jnp.int32)
        shl, res = shd.apply_ops_sharded(shl, ops, jnp.asarray(kk),
                                         jnp.asarray(kk * 2))
        res = np.asarray(res)
        for i, k in enumerate(kk):
            expect_new = int(oracle.insert(int(k), int(k) * 2))
            if expect_new and not res[i]:
                failed += 1                        # capacity-failed insert
            else:
                assert res[i] == expect_new
    assert failed > 0, "stream too small to exhaust the fixed shard"

    # --- rebalance on: every result matches the monolithic oracle ----------
    shl = shl0
    oracle = DictOracle()
    oracle.d.update(oracle0.d)
    mono = sl.build(jnp.asarray(keys), jnp.asarray(keys * 3),
                    capacity=512, levels=8, seed=0)
    for kk in batches:
        ops = jnp.full((kk.size,), sl.OP_INSERT, jnp.int32)
        shl, res = shd.apply_ops_sharded(shl, ops, jnp.asarray(kk),
                                         jnp.asarray(kk * 2), rebalance=True)
        mono, res_m = sl.apply_ops(mono, ops, jnp.asarray(kk),
                                   jnp.asarray(kk * 2))
        np.testing.assert_array_equal(np.asarray(res), np.asarray(res_m))
        for k in kk:
            oracle.insert(int(k), int(k) * 2)
        assert bool(shd.check_sharded_invariant(shl, expect_n=len(oracle.d)))
    assert shl.n_shards > shl0.n_shards            # splits actually happened
    # search + range results bit-identical to the monolithic index
    probe = jnp.asarray(np.concatenate(
        [keys, np.unique(np.concatenate(batches)),
         rng.integers(0, SPAN, 64)]).astype(np.int32))
    f_m, v_m = sl.search_fast(mono, probe)
    f_s, v_s = shd.search_sharded(shl, probe)
    np.testing.assert_array_equal(np.asarray(f_s), np.asarray(f_m))
    np.testing.assert_array_equal(np.asarray(v_s), np.asarray(v_m))
    _assert_matches_oracle(shl, oracle, rng)


# ---------------------------------------------------------------------------
# Differential fuzz harness (seeded fallback + hypothesis sweep)
# ---------------------------------------------------------------------------

def _replay_stream(seed, *, rounds=3, batch=36, zipf=False, n_init=24,
                   n_shards=4, capacity=16, levels=8, repack_every=2):
    """Replay a random op stream against the DictOracle, rebalancing on.

    Asserts, after EVERY batch and every amortized repack: result flags
    equal the oracle's, the extended sharded invariant holds with the live
    count conserved, and searches + range scans are bit-identical.
    """
    shl, oracle, keys, rng = _build(n=n_init, n_shards=n_shards,
                                    capacity=capacity, levels=levels,
                                    seed=seed)
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
        shl, res = shd.apply_ops_sharded(shl, jnp.asarray(ops),
                                         jnp.asarray(kk), jnp.asarray(vv),
                                         rebalance=True)
        assert np.asarray(res).tolist() == expected
        assert bool(shd.check_sharded_invariant(shl, expect_n=len(oracle.d)))
        _assert_matches_oracle(shl, oracle, rng)
        if repack_every and (r + 1) % repack_every == 0:
            shl = shd.repack(shl)
            assert bool(shd.check_sharded_invariant(shl,
                                                    expect_n=len(oracle.d)))
            _assert_matches_oracle(shl, oracle, rng)
    return shl


def test_fuzz_differential_seeded():
    """Deterministic stand-in for the hypothesis sweep (runs sans deps)."""
    _replay_stream(0)
    _replay_stream(1, zipf=True)


@pytest.mark.slow
def test_fuzz_differential_seeded_stress():
    """Larger-budget sweep for the CI rebalance-stress job
    (REBALANCE_EXAMPLES seeds; alternates uniform / Zipf streams)."""
    for seed in range(max(4, EXAMPLES)):
        _replay_stream(seed, zipf=bool(seed % 2), rounds=4, batch=48)


@pytest.mark.slow
def test_fuzz_differential_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @settings(max_examples=max(8, EXAMPLES), deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(seed=st.integers(0, 2**31 - 1), zipf=st.booleans(),
           batch=st.integers(8, 48))
    def check(seed, zipf, batch):
        _replay_stream(seed, rounds=2, batch=batch, zipf=zipf,
                       repack_every=1)

    check()


# ---------------------------------------------------------------------------
# ROADMAP K-degeneration regression: a sorted block straddling all S shards
# ---------------------------------------------------------------------------

def test_sorted_block_straddling_all_shards_cluster_plan():
    """One QBLK block holding >= 1 key of EVERY shard (the sparse-Zipf-tail
    degeneration, ROADMAP): the plan must widen K to exactly S — including
    a post-split S that is not a power of two — and stay bit-identical."""
    shl, oracle, keys, rng = _build(n=1200, n_shards=8, levels=10,
                                    capacity=512)
    shl = shd.split_shard(shl, 3)                  # S = 9, not a power of two
    S = shl.n_shards
    b = np.asarray(shl.boundaries).astype(np.int64)
    sids = np.asarray(shd.route(shl.boundaries, jnp.asarray(keys)))
    picks = np.array([keys[sids == s][0] for s in range(S)], np.int32)
    assert np.unique(np.asarray(
        shd.route(shl.boundaries, jnp.asarray(picks)))).size == S
    q = jnp.asarray(np.sort(picks))                # one sorted block
    qp, _ = kops._pad(q)
    plan = kops.cluster_queries(shl.boundaries, qp)
    assert plan.block_sids.shape == (1, S)         # K degenerates to S
    assert int(plan.ndist[0]) == S
    rc = kops.search_kernel_sharded(shl, q, cluster=True)
    rd = kops.search_kernel_sharded(shl, q, cluster=False)
    for a, c in zip(rc, rd):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    assert bool(jnp.all(rc.found))
    np.testing.assert_array_equal(np.asarray(rc.vals), np.sort(picks) * 3)


def test_cluster_plan_k_clamps_to_rebalanced_shard_count():
    """K never exceeds the CURRENT S, and a plan built for a larger S is
    statically rejected by the clustered wrappers (stale-plan guard)."""
    shl, _, keys, rng = _build(n=400, n_shards=4, levels=8, capacity=256)
    qp, _ = kops._pad(jnp.asarray(rng.choice(keys, 64).astype(np.int32)))
    plan_old = kops.cluster_queries(shl.boundaries, qp, k_shards=4)
    merged = shd.merge_shards(shd.merge_shards(shl, 0), 1)   # S = 2
    with pytest.raises(AssertionError, match="stale"):
        from repro.kernels.foresight_traverse import foresight_traverse_clustered
        foresight_traverse_clustered(merged.shards.fused, plan_old.block_sids,
                                     plan_old.ndist, plan_old.sid_sorted,
                                     plan_old.q_sorted, interpret=True)
    # replanning against the merged boundaries is the supported path
    f, v = shd.search_sharded(merged, qp)
    rc = kops.search_kernel_sharded(merged, qp)
    np.testing.assert_array_equal(np.asarray(rc.found), np.asarray(f))
    np.testing.assert_array_equal(np.asarray(rc.vals), np.asarray(v))
