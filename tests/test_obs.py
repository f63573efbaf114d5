"""The served read path's counters and spans (``repro.obs``): the counted
traversals against ``search``'s own counter, the accumulator, and no new
host sync on the served read calls."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import array as jax_array

from repro import obs
from repro.core import sharded as shd
from repro.core import skiplist as sl
from repro.data import store as store_mod
from repro.data.store import IndexedSampleStore, StoreConfig
from repro.serving import kvcache
from repro.serving.kvcache import PagedCacheConfig, PageTable


def _keys(n, seed=0, span=1 << 22):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(span, n, replace=False)).astype(np.int32)
    probe = np.concatenate([rng.choice(keys, n // 2),
                            rng.integers(1, span, n // 2)]).astype(np.int32)
    return keys, jnp.asarray(probe)


def _slack(state, lanes: int) -> int:
    """The gathers ``search`` spends above the effective top level."""
    g = 1 if state.foresight else 2
    top = int(sl.effective_top_level(state))
    return lanes * g * (state.levels - 1 - top)


@pytest.mark.parametrize("node_width", [1, 128])
@pytest.mark.parametrize("foresight", [True, False])
def test_search_fast_counts_match_search(foresight, node_width):
    keys, q = _keys(1500)
    cap = (sl.node_slots_for(3000, node_width) + 8 if node_width > 1
           else 4096)
    st = sl.build(jnp.asarray(keys), jnp.asarray(keys * 3), capacity=cap,
                  levels=14, foresight=foresight, node_width=node_width)
    ref = sl.search(st, q)
    found, vals, steps = sl.search_fast_counted(st, q)
    f0, v0 = sl.search_fast(st, q)
    np.testing.assert_array_equal(np.asarray(found), np.asarray(f0))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(v0))
    np.testing.assert_array_equal(np.asarray(found), np.asarray(ref.found))
    g = 1 if foresight else 2
    skipped = st.levels - 1 - int(sl.effective_top_level(st))
    steps = np.asarray(steps)
    assert g * int(steps.sum()) == int(ref.gathers) - _slack(st, q.shape[0])
    assert int(steps.max()) == int(ref.steps) - skipped
    assert steps.min() >= 1


@pytest.mark.parametrize("foresight", [True, False])
def test_search_sharded_counts_match_per_shard_search(foresight):
    keys, q = _keys(2000, seed=3)
    shl = shd.build_sharded(jnp.asarray(keys), jnp.asarray(keys * 3),
                            n_shards=4, levels=12, foresight=foresight)
    found, vals, steps = shd.search_sharded_counted(shl, q)
    f0, v0 = shd.search_sharded(shl, q)
    np.testing.assert_array_equal(np.asarray(found), np.asarray(f0))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(v0))
    sid = np.asarray(shd.route(shl.boundaries, q))
    want_gathers, want_trips = 0, 0
    for s in np.unique(sid):
        shard = jax.tree.map(lambda a: a[s], shl.shards)
        qs = q[np.flatnonzero(sid == s)]
        ref = sl.search(shard, qs)
        want_gathers += int(ref.gathers) - _slack(shard, qs.shape[0])
        skipped = shard.levels - 1 - int(sl.effective_top_level(shard))
        want_trips = max(want_trips, int(ref.steps) - skipped)
    steps = np.asarray(steps)
    g = 1 if foresight else 2
    assert g * int(steps.sum()) == want_gathers
    assert int(steps.max()) == want_trips


def test_accumulator_sums_calls_and_carries_past_32_bits(tmp_path):
    obs.reset()
    big = (1 << 31) - 1
    jax.profiler.start_trace(str(tmp_path))
    try:
        obs.count_search(jnp.asarray([3, 10, 2, 0], jnp.int32), per_step=2)
        obs.count_search(jnp.asarray([5, 5], jnp.int32), per_step=1)
        assert obs.snapshot() == {"keys": 6, "lane_steps": 25,
                                  "lane_slots": 50, "gathers": 40}
        for _ in range(3):
            obs.count_search(jnp.asarray([big], jnp.int32), per_step=1)
    finally:
        jax.profiler.stop_trace()
    assert obs.snapshot()["gathers"] == 40 + 3 * big
    assert obs.snapshot()["lane_slots"] == 50 + 3 * big
    obs.reset()
    assert obs.snapshot() == dict.fromkeys(obs.COUNTERS, 0)


def test_traced_counts_take_only_calls_under_a_trace(tmp_path):
    keys, q = _keys(1000)
    store = IndexedSampleStore(StoreConfig(n_samples=1000, seq_len=3),
                               keys=keys)
    obs.reset()
    store.get_batch(q)
    assert obs.snapshot() == dict.fromkeys(obs.COUNTERS, 0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        store.get_batch(q)
        store.get_batch(q[:64])
    finally:
        jax.profiler.stop_trace()
    store.get_batch(q)
    traced = obs.snapshot()
    steps = np.asarray(sl.search_fast_counted(store.index, q)[2])
    steps64 = steps[:64]
    assert traced["keys"] == q.shape[0] + 64
    assert traced["gathers"] == int(steps.sum()) + int(steps64.sum())
    assert traced["lane_slots"] == (int(steps.max()) * q.shape[0]
                                    + int(steps64.max()) * 64)


def test_served_reads_make_no_host_sync(monkeypatch, tmp_path):
    keys, q = _keys(1000)
    store = IndexedSampleStore(StoreConfig(n_samples=1000, seq_len=3),
                               keys=keys)
    pt = PageTable(PagedCacheConfig(n_pages=256, levels=8, max_shards=4))
    seqs = np.repeat(np.arange(8), 4)
    blocks = np.tile(np.arange(4), 8)
    pt.alloc(seqs, blocks)
    store.get_batch(q)                      # compiled outside the guard
    pt.lookup(seqs, blocks)
    obs.reset()
    # The CPU backend lets the guard pass (its arrays live on the host),
    # so every copy of a device array to the host is counted as well:
    # NumPy's conversions, and ``int``/``bool``/``item`` through ``_value``.
    copies = []
    value = jax_array.ArrayImpl._value
    monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(
        lambda self: copies.append(self.shape) or value.fget(self)))
    for name in ("asarray", "array"):
        def spy(x, *a, _to_numpy=getattr(np, name), **k):
            if isinstance(x, jax.Array):
                copies.append(x.shape)
            return _to_numpy(x, *a, **k)
        monkeypatch.setattr(np, name, spy)
    # under a trace, so that the counters' add runs too
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            rows, found = store.get_batch(q)
            pfound, pages = pt.lookup(seqs, blocks)
    finally:
        jax.profiler.stop_trace()
    assert copies == []
    assert bool(np.asarray(found).any()) and bool(np.asarray(pfound).all())
    assert copies, "the spy sees a host copy"
    assert obs.snapshot()["keys"] == q.shape[0] + len(seqs)


def test_untraced_reads_run_the_uncounted_traversal(monkeypatch):
    """With no trace the read path answers from the plain programs, the
    loop without the count's carry: the counted twins may be built beside
    them, but are not called."""
    keys, q = _keys(1000)
    store = IndexedSampleStore(StoreConfig(n_samples=1000, seq_len=3),
                               keys=keys)
    pt = PageTable(PagedCacheConfig(n_pages=256, levels=8, max_shards=4))
    seqs = np.repeat(np.arange(8), 4)
    blocks = np.tile(np.arange(4), 8)
    pt.alloc(seqs, blocks)

    class Unrun:
        def __init__(self, program):
            self.lower = program.lower      # building is allowed

        def __call__(self, *a):
            raise AssertionError("counted traversal outside a trace")
    reads = [r for rs in store_mod._READS.values()
             for r in (rs.lookup, rs.read_rows)] + [kvcache._SEARCH]
    for read in reads:
        monkeypatch.setattr(read, "counted", Unrun(read.counted))
    rows, found = store.get_batch(q)
    found_l, _ = store.lookup(q)
    pfound, pages = pt.lookup(seqs, blocks)
    assert bool(np.asarray(pfound).all())
    f0, _ = sl.search_fast(store.index, q)
    np.testing.assert_array_equal(np.asarray(found), np.asarray(f0))
    np.testing.assert_array_equal(np.asarray(found_l), np.asarray(f0))
