"""Traffic generation from a seed: keys, request distributions, lengths.

One general generator for every mix.  What a mix asks for (shares of
operations, the request distribution, batch sizes, length distributions)
is data in ``bench/traffic/<name>.json``; this module only knows how to
draw from the distributions those files name.

The YCSB pieces follow YCSB's ``CoreWorkload`` (github.com/brianfrankcooper/YCSB,
``core/src/main/java/site/ycsb``): records are inserted in the order of
their sequence numbers and keyed by a 64-bit FNV-1a hash of that number
(``Utils.fnvhash64``); ``ZipfianGenerator`` draws ranks by Gray et al.'s
method with the constant 0.99; ``ScrambledZipfianGenerator`` draws over
10**10 items and hashes the rank into the record count, so the hot records
are spread over the key space; ``SkewedLatestGenerator`` ("latest")
favours the records inserted last.  Keys here are 31-bit ints (the
index's key domain is the open interval (-2**31, 2**31 - 1)), so a hash
that repeats an earlier key is skipped, and the key hash is salted by the
seed so that every seed draws another table.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

FNV_OFFSET_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)
KEY_LIMIT = 2**31 - 1            # the index's tail sentinel; never a key

ZIPFIAN_CONSTANT = 0.99
SCRAMBLED_ITEMS = 10_000_000_000                 # YCSB ITEM_COUNT
SCRAMBLED_ZETAN = 26.46902820178302              # YCSB ZETAN for the above


def fnv64(values: np.ndarray, basis: np.uint64 = FNV_OFFSET_64) -> np.ndarray:
    """FNV-1a over the 8 little-endian bytes of each int64, as uint64."""
    v = values.astype(np.uint64)
    h = np.full(v.shape, basis, np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= FNV_PRIME_64
        v >>= np.uint64(8)
    return h


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` uint32 words derived from any non-negative integer seed."""
    ss = np.random.SeedSequence(int(seed))
    return ss.generate_state(n, np.uint32)


def key_basis(seed: int) -> np.uint64:
    w = seed_words(seed, 2).astype(np.uint64)
    return FNV_OFFSET_64 ^ ((w[0] << np.uint64(32)) | w[1])


class KeyStream:
    """Record keys in insertion order: item ``i`` is the ``i``-th distinct
    31-bit hash of the sequence numbers 0, 1, 2, ...

    ``sorted_keys`` holds every key issued so far, so new hashes that
    repeat one are skipped (YCSB's 64-bit keys never collide; 31-bit ones
    do, about once per 500 keys at four million).
    """

    def __init__(self, seed: int):
        self.basis = key_basis(seed)
        self.next_seq = 0
        self.keys = np.empty(0, np.int64)          # by item number
        self.sorted_keys = np.empty(0, np.int64)

    def _hash31(self, seqs: np.ndarray) -> np.ndarray:
        return (fnv64(seqs, self.basis) >> np.uint64(33)).astype(np.int64)

    def extend(self, n: int) -> np.ndarray:
        """Issue ``n`` more keys; returns them in insertion order."""
        out = np.empty(0, np.int64)
        while out.size < n:
            want = n - out.size
            seqs = np.arange(self.next_seq, self.next_seq + want + want // 64
                             + 16, dtype=np.int64)
            h = self._hash31(seqs)
            # first occurrence of each hash, in sequence order
            _, first = np.unique(h, return_index=True)
            keep = np.zeros(h.size, bool)
            keep[first] = True
            keep &= (h != KEY_LIMIT) & ~_member(h, self.sorted_keys)
            keep &= ~_member(h, np.sort(out))
            idx = np.flatnonzero(keep)[:want]
            # sequence numbers past the last one kept are drawn again later
            self.next_seq = int(seqs[idx[-1]] if idx.size == want
                                else seqs[-1]) + 1
            out = np.concatenate([out, h[idx]])
        self.keys = np.concatenate([self.keys, out])
        self.sorted_keys = np.union1d(self.sorted_keys, out)
        return out


def _member(x: np.ndarray, sorted_set: np.ndarray) -> np.ndarray:
    """``x`` in ``sorted_set``, by binary search (no sort of the set)."""
    if sorted_set.size == 0:
        return np.zeros(x.shape, bool)
    i = np.minimum(np.searchsorted(sorted_set, x), sorted_set.size - 1)
    return sorted_set[i] == x


def zeta(n: int, theta: float, start: int = 0, initial: float = 0.0) -> float:
    """sum_{i=start+1..n} i**-theta, added to ``initial`` (YCSB zetastatic)."""
    if n <= start:
        return initial
    i = np.arange(start + 1, n + 1, dtype=np.float64)
    return initial + float(np.sum(i ** -theta))


class Zipfian:
    """YCSB ``ZipfianGenerator`` over ranks [0, items), vectorized.

    ``grow`` raises the item count the way YCSB does for "latest": zeta is
    extended incrementally, eta recomputed.
    """

    def __init__(self, items: int, theta: float = ZIPFIAN_CONSTANT,
                 zetan: float | None = None):
        self.theta = theta
        self.alpha = 1.0 / (1.0 - theta)
        self.zeta2 = zeta(2, theta)
        self.items = items
        self.zetan = zeta(items, theta) if zetan is None else zetan
        self._eta()

    def _eta(self) -> None:
        self.eta = ((1 - (2.0 / self.items) ** (1 - self.theta))
                    / (1 - self.zeta2 / self.zetan))

    def grow(self, items: int) -> None:
        if items > self.items:
            self.zetan = zeta(items, self.theta, self.items, self.zetan)
            self.items = items
            self._eta()

    def draw(self, u: np.ndarray) -> np.ndarray:
        uz = u * self.zetan
        r = (self.items * np.power(self.eta * u - self.eta + 1, self.alpha)
             ).astype(np.int64)
        r = np.where(uz < 1.0 + 0.5 ** self.theta, 1, r)
        r = np.where(uz < 1.0, 0, r)
        return np.minimum(r, self.items - 1)


class ScrambledZipfian:
    """YCSB ``ScrambledZipfianGenerator``: a rank over 10**10 items, hashed
    into [0, items)."""

    def __init__(self, items: int, theta: float = ZIPFIAN_CONSTANT):
        self.items = items
        self.z = Zipfian(SCRAMBLED_ITEMS, theta, SCRAMBLED_ZETAN)

    def draw(self, u: np.ndarray, items: int | None = None) -> np.ndarray:
        """``items`` is ignored: the hot set stays over the loaded records."""
        r = self.z.draw(u)
        return (fnv64(r) % np.uint64(self.items)).astype(np.int64)


class Latest:
    """YCSB ``SkewedLatestGenerator``: item = last inserted - zipfian rank."""

    def __init__(self, items: int, theta: float = ZIPFIAN_CONSTANT):
        self.z = Zipfian(items, theta)

    def draw(self, u: np.ndarray, items: int) -> np.ndarray:
        self.z.grow(items)
        return (items - 1) - self.z.draw(u)


class Uniform:
    def __init__(self, items: int):
        self.items = items

    def draw(self, u: np.ndarray, items: int | None = None) -> np.ndarray:
        n = self.items if items is None else items
        return np.minimum((u * n).astype(np.int64), n - 1)


def request_distribution(name: str, items: int, theta: float):
    """The read-key chooser a mix names: ``zipfian`` (scrambled, as YCSB's
    default), ``latest`` or ``uniform``."""
    if name == "zipfian":
        return ScrambledZipfian(items, theta)
    if name == "latest":
        return Latest(items, theta)
    if name == "uniform":
        return Uniform(items)
    raise ValueError(f"unknown request distribution {name!r}")


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` whole lengths from a mix's length spec, ascending: the
    quantiles (i + 1/2) / n of a lognormal with the given median and
    sigma, clipped to [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    normal = NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def sessions(prompt: dict, output: dict, n: int) -> np.ndarray:
    """A set of ``n`` sessions, [n, 2] (prompt, output) tokens, the same
    for every seed: each length is a quantile of its spec, and prompts are
    paired with outputs by a fixed shuffle, so the two are independent."""
    pair = np.random.default_rng(n).permutation(n)
    return np.stack([quantiles(prompt, n), quantiles(output, n)[pair]],
                    axis=1)


def row_words(seed: int) -> tuple[int, int]:
    w = seed_words(int(seed) ^ 0x5EED_0F_20, 2)
    return int(w[0]), int(w[1])


def row_hash(row_ids: np.ndarray, width: int, words: tuple[int, int]
             ) -> np.ndarray:
    """The bytes of table rows, as int32 [len(row_ids), width].

    Word ``c`` of row ``r`` is a 32-bit mix of ``r * width + c`` and the
    seed.  The store's table is made on the device by the same formula
    (``bench.systems.store.make_rows``); this is the reference's copy.
    """
    s0, s1 = (np.uint32(w) for w in words)
    x = (row_ids.astype(np.uint32)[:, None] * np.uint32(width)
         + np.arange(width, dtype=np.uint32)[None, :])
    with np.errstate(over="ignore"):
        h = x * np.uint32(0x9E3779B1) + s0
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
        h ^= s1
    return h.view(np.int32)
