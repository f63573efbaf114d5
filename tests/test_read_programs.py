"""The one-device read path as one jitted program per index structure and
batch shape: the store's ``lookup`` and ``get_batch`` (search and row
gather in one launch) and the page table's pow2-padded lookups give the
eager traversal's answers, build one program per shape (and its counted
twin beside it, unrun), and count under a profiler trace what the
counted traversal returns."""
import collections
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import sharded as shd
from repro.core import skiplist as sl
from repro.data import store as store_mod
from repro.data.store import IndexedSampleStore, StoreConfig
from repro.serving import kvcache
from repro.serving.kvcache import PagedCacheConfig, PageTable, page_key

N = 1500            # keys in the store: no other test builds this size
SIZES = (96, 160)   # batch sizes no other test reads at


@contextlib.contextmanager
def _lowered():
    """The names of the programs JAX lowers inside the block."""
    names = []

    def listen(event, secs, *, fun_name="", **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            names.append(fun_name)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield names
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _store(foresight: bool, node_width: int, n_shards: int):
    rng = np.random.default_rng(7)
    keys = np.sort(rng.choice(1 << 22, N, replace=False)).astype(np.int32)
    store = IndexedSampleStore(StoreConfig(n_samples=N, seq_len=5,
                                           index_levels=12,
                                           foresight=foresight,
                                           n_shards=n_shards), keys=keys)
    if node_width > 1:      # the store builds scalar nodes: a fat index
        row_ids = jnp.arange(N, dtype=jnp.int32)   # in its place
        k = jnp.asarray(keys)
        store.index = (
            shd.build_sharded(k, row_ids, n_shards=n_shards, levels=12,
                              foresight=foresight, node_width=node_width)
            if n_shards > 1 else
            sl.build(k, row_ids, capacity=sl.node_slots_for(2 * N,
                                                            node_width) + 8,
                     levels=12, foresight=foresight, node_width=node_width))
    probe = np.concatenate([rng.choice(keys, 128),
                            rng.integers(1, 1 << 22, 128)]).astype(np.int32)
    return store, probe


@pytest.mark.parametrize("n_shards", [1, 4], ids=["monolithic", "sharded"])
@pytest.mark.parametrize("node_width", [1, 128], ids=["scalar", "fat"])
@pytest.mark.parametrize("foresight", [True, False],
                         ids=["foresight", "base"])
def test_store_reads_are_one_program_per_shape(tmp_path, foresight,
                                               node_width, n_shards):
    store, probe = _store(foresight, node_width, n_shards)
    assert store.sharded == (n_shards > 1)
    search, counted = ((shd.search_sharded, shd.search_sharded_counted)
                       if store.sharded else
                       (sl.search_fast, sl.search_fast_counted))
    reads = store_mod._READS[store.sharded]
    programs = (reads.lookup.plain, reads.read_rows.plain)
    built = [p._cache_size() for p in programs]

    # the eager traversal and row gather on the same state
    want = {}
    for size in SIZES:
        q = jnp.asarray(probe[:size])
        f0, v0 = search(store.index, q)
        want[size] = q, f0, v0, store.rows[jnp.where(f0, v0, 0)]
    with _lowered() as names:
        for size in SIZES:
            q, f0, v0, rows0 = want[size]
            for _ in range(3):
                found, vals = store.lookup(q)
                np.testing.assert_array_equal(np.asarray(found),
                                              np.asarray(f0))
                np.testing.assert_array_equal(np.asarray(vals),
                                              np.asarray(v0))
                rows, found = store.get_batch(q)
                np.testing.assert_array_equal(np.asarray(found),
                                              np.asarray(f0))
                np.testing.assert_array_equal(np.asarray(rows),
                                              np.asarray(rows0))
    # untraced: one build per batch shape of each program, and of its
    # counted twin beside it
    assert [p._cache_size() - b for p, b in zip(programs, built)] == [
        len(SIZES)] * 2
    read_programs = {f"jit({search.__name__})": len(SIZES),
                     f"jit({counted.__name__})": len(SIZES),
                     "jit(read_rows)": 2 * len(SIZES)}
    assert collections.Counter(names) == read_programs

    q = jnp.asarray(probe[:SIZES[0]])
    obs.reset()
    with jax.profiler.trace(str(tmp_path)), _lowered() as names:
        rows, found = store.get_batch(q)
        found_l, vals_l = store.lookup(q)
    got = obs.snapshot()
    obs.reset()
    f0, v0 = search(store.index, q)
    np.testing.assert_array_equal(np.asarray(found), np.asarray(f0))
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(
        store.rows[jnp.where(f0, v0, 0)]))
    np.testing.assert_array_equal(np.asarray(found_l), np.asarray(f0))
    np.testing.assert_array_equal(np.asarray(vals_l), np.asarray(v0))
    steps = np.asarray(counted(store.index, q)[2]).astype(np.int64)
    g = 1 if foresight else 2
    assert got == {"keys": 2 * q.shape[0],
                   "lane_steps": 2 * int(steps.sum()),
                   "lane_slots": 2 * int(steps.max()) * q.shape[0],
                   "gathers": 2 * g * int(steps.sum())}
    # traced at a warm shape: the twins were built already
    assert not set(names) & set(read_programs), names


def _page_table():
    pt = PageTable(PagedCacheConfig(n_pages=384, levels=9, max_shards=4))
    seqs = np.repeat(np.arange(24), 12)
    blocks = np.tile(np.arange(12), 24)
    pt.alloc(seqs, blocks)
    return pt


def _eager(pt, seqs, blocks):
    keys = page_key(np.asarray(seqs, np.int64),
                    np.asarray(blocks, np.int64)).astype(np.int32)
    found, pages = shd.search_sharded(pt.index, jnp.asarray(keys))
    return np.asarray(found), np.asarray(pages)


def test_page_table_reads_are_one_program_per_size_class(tmp_path):
    pt = _page_table()
    plain = kvcache._SEARCH.plain
    # jit's cache is the function's, shared by every table: count builds
    built = plain._cache_size()
    rng = np.random.default_rng(3)
    calls = []
    for n in (5, 7, 8, 3, 9, 16, 1):
        seqs = rng.integers(0, 30, n)       # sessions 24-29 hold no pages
        blocks = rng.integers(0, 12, n)
        calls.append((seqs, blocks, *_eager(pt, seqs, blocks)))
    classes = []
    searches = ("jit(search_sharded)", "jit(search_sharded_counted)")
    for seqs, blocks, want_f, want_p in calls:
        with _lowered() as names:
            found, pages = pt.lookup(seqs, blocks)
        n = len(seqs)
        assert found.shape == (n,) and pages.shape == (n,)
        np.testing.assert_array_equal(np.asarray(found), want_f)
        np.testing.assert_array_equal(np.asarray(pages), want_p)
        new = (1 << (n - 1).bit_length()) not in classes
        classes.append(1 << (n - 1).bit_length())
        assert plain._cache_size() - built == len(set(classes))
        # a new size class builds the search and its twin, once each
        assert sorted(x for x in names if x in searches) == (
            sorted(searches) if new else [])

    # traced, padded: the counts are the real keys' alone
    seqs, blocks = np.arange(5), np.arange(5)
    keys = page_key(seqs, blocks).astype(np.int32)
    obs.reset()
    with jax.profiler.trace(str(tmp_path)), _lowered() as names:
        found, _ = pt.lookup(seqs, blocks)
    got = obs.snapshot()
    obs.reset()
    assert np.asarray(found).all()
    steps = np.asarray(shd.search_sharded_counted(
        pt.index, jnp.asarray(keys))[2]).astype(np.int64)
    assert got == {"keys": 5, "lane_steps": int(steps.sum()),
                   "lane_slots": 5 * int(steps.max()),
                   "gathers": int(steps.sum())}
    # size class 8 is warm: the traced call built no search
    assert not set(names) & set(searches), names


@pytest.mark.parametrize("blocks", [[0, 1, 2], [3, 5, 6, 7, 11],
                                    list(range(8))], ids=["3", "5", "8"])
def test_page_table_release_frees_what_the_eager_search_finds(blocks):
    pt = _page_table()
    seq = 11
    blocks = np.asarray(blocks, np.int64)
    want_f, want_p = _eager(pt, np.full(len(blocks), seq), blocks)
    assert want_f.all()
    free_before = list(pt.free)
    assert pt.release_blocks(seq, blocks) == int(want_f.sum())
    assert sorted(pt.free[len(free_before):]) == sorted(want_p.tolist())
    # released: a second release finds nothing and frees nothing
    assert pt.release_blocks(seq, blocks) == 0
    assert len(pt.free) == len(free_before) + len(blocks)
    found, _ = pt.lookup(np.full(len(blocks), seq), blocks)
    assert not np.asarray(found).any()
