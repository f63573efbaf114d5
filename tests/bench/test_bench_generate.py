"""The traffic generators and the plain references."""
from __future__ import annotations

import numpy as np
import pytest

from bench import generate as gen
from bench.reference import PageMap, SortedIndex


def test_key_stream_is_seeded_distinct_and_chunk_free():
    a = gen.KeyStream(2**31 + 9)
    keys = a.extend(50_000)
    assert len(np.unique(keys)) == keys.size == 50_000
    assert keys.min() >= 0 and keys.max() < gen.KEY_LIMIT
    b = gen.KeyStream(2**31 + 9)
    chunks = np.concatenate([b.extend(n) for n in (1, 999, 30_000, 19_000)])
    np.testing.assert_array_equal(chunks, keys)
    assert not np.array_equal(gen.KeyStream(1).extend(1000), keys[:1000])
    np.testing.assert_array_equal(a.sorted_keys, np.sort(keys))


def test_fnv64_matches_the_byte_loop():
    def fnv(v):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h ^= v & 0xFF
            h = (h * 1099511628211) % 2**64
            v >>= 8
        return h
    vals = np.array([0, 1, 255, 2**40 + 7], np.int64)
    assert gen.fnv64(vals).tolist() == [fnv(int(v)) for v in vals]


def test_zipfian_ranks_follow_ycsb():
    u = np.random.default_rng(0).random(400_000)
    z = gen.Zipfian(1000)
    r = z.draw(u)
    assert r.min() == 0 and r.max() < 1000
    # rank 0 is drawn with probability 1 / zeta(n)
    assert np.mean(r == 0) == pytest.approx(1 / z.zetan, rel=0.03)
    grown = gen.Zipfian(1000)
    grown.grow(1500)
    assert grown.zetan == pytest.approx(gen.zeta(1500, 0.99))


def test_scrambled_zipfian_spreads_the_hot_set():
    items = 100_000
    u = np.random.default_rng(1).random(200_000)
    it = gen.ScrambledZipfian(items).draw(u)
    assert it.min() >= 0 and it.max() < items
    hot = np.argsort(np.bincount(it, minlength=items))[-20:]
    # hot records are all over the table, not the first ranks
    assert hot.max() - hot.min() > items // 4
    assert np.bincount(it).max() / it.size > 0.02


def test_latest_favours_the_newest_records():
    u = np.random.default_rng(2).random(100_000)
    it = gen.Latest(10_000).draw(u, 10_500)
    assert it.max() == 10_499 and it.min() >= 0
    assert np.mean(it >= 10_400) > 0.5


def test_lengths_are_clipped_lognormal():
    spec = {"dist": "lognormal", "median": 1024, "sigma": 1.0, "min": 16,
            "max": 16384}
    x = gen.quantiles(spec, 50_000)
    assert x.min() >= 16 and x.max() <= 16384
    assert np.all(np.diff(x) >= 0)
    assert np.median(x) == pytest.approx(1024, rel=0.05)


def test_rows_on_the_device_match_the_reference_hash():
    import jax.numpy as jnp

    from bench.systems.store import make_rows
    words = gen.row_words(2**31 + 3)
    dev = np.asarray(make_rows(64, 250, *(jnp.uint32(w) for w in words)))
    np.testing.assert_array_equal(dev, gen.row_hash(np.arange(64), 250,
                                                    words))
    assert len({r.tobytes() for r in dev}) == 64


def test_sorted_index_is_a_map_with_upserts():
    rng = np.random.default_rng(4)
    keys = np.unique(rng.integers(0, 1000, 300))
    ref = SortedIndex(keys, np.arange(keys.size))
    d = dict(zip(keys.tolist(), range(keys.size)))
    for _ in range(20):
        k = rng.integers(0, 1200, 7)
        v = rng.integers(0, 50, 7)
        want = []
        for kk, vv in zip(k.tolist(), v.tolist()):
            want.append(int(kk not in d))
            d[kk] = vv
        assert ref.insert(k, v).tolist() == want
    q = np.arange(-5, 1300)
    found, rows = ref.lookup(q)
    assert found.tolist() == [int(x) in d for x in q]
    assert rows[found].tolist() == [d[int(x)] for x in q[found]]


def test_page_map_counts_double_mappings_and_releases():
    ref = PageMap(8)
    assert ref.alloc([1, 1], [0, 1], [3, 4]) == 0
    assert ref.alloc([2], [0], [3]) == 1          # page 3 is live
    assert ref.alloc([2], [1], [9]) == 1          # out of the pool
    found, pages = ref.lookup([1, 2, 5], [1, 0, 0])
    assert found.tolist() == [True, True, False]
    assert pages.tolist() == [4, 3, -1]
    assert ref.release(1, [0, 1, 2]) == 2         # block 2 was never mapped
    assert len(ref) == 2


def test_binary_parts_are_powers_of_two_up_to_the_cap():
    from bench.systems.page_table import MAX_CALL, binary_parts
    assert binary_parts(2500) == [1024, 1024, 256, 128, 64, 4]
    assert binary_parts(0) == []
    for n in (1, 7, 1023, 1024, 5000):
        parts = binary_parts(n)
        assert sum(parts) == n
        assert all(p & (p - 1) == 0 and p <= MAX_CALL for p in parts)


def test_sessions_are_one_set_for_every_seed():
    prompt = {"dist": "lognormal", "median": 1020, "sigma": 1.0, "min": 16,
              "max": 16384}
    output = {"dist": "lognormal", "median": 129, "sigma": 1.0, "min": 8,
              "max": 4096}
    s = gen.sessions(prompt, output, 64)
    np.testing.assert_array_equal(s, gen.sessions(prompt, output, 64))
    np.testing.assert_array_equal(np.sort(s[:, 0]), gen.quantiles(prompt, 64))
    np.testing.assert_array_equal(np.sort(s[:, 1]), gen.quantiles(output, 64))
    # prompts are not sorted along with outputs
    assert not np.array_equal(np.argsort(s[:, 0], kind="stable"),
                              np.argsort(s[:, 1], kind="stable"))


def test_decode_loop_starts_every_seed_from_the_same_live_state():
    import json
    import pathlib

    from bench.control import ReducedKeyPageTable
    from bench.systems import page_table
    root = pathlib.Path(__file__).resolve().parents[2]
    config = json.loads((root / "bench/configs/kv_pages_64k.json")
                        .read_text())
    mix = json.loads((root / "bench/traffic/decode_churn.json").read_text())
    states = []
    for seed in (2**31 + 1, 2**32 + 7):
        cell = page_table.Cell(config, mix, seed,
                               make_system=ReducedKeyPageTable)
        cell.unit = lambda: 0                    # the sessions, no steps
        cell.setup(lambda *a: None)
        states.append((sorted(zip(cell.tokens.tolist(),
                                  cell.left.tolist())),
                       cell.tokens.tolist()))
    assert states[0][0] == states[1][0]
    assert states[0][1] != states[1][1]          # in another order
