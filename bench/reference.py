"""Plain references: the semantics each configuration promises, written
without the skiplist and without anything the program made.

``SortedIndex`` is an ordered map from int key to row id held as two
sorted NumPy arrays (a ``searchsorted`` per lookup).  ``PageMap`` is a
dict from (session, block) to physical page with the pool's invariants.
Both replay the operation log a run recorded and count the answers that
differ.
"""
from __future__ import annotations

import numpy as np


class SortedIndex:
    """Ordered map key -> row id; insert is an upsert (result 1 when the
    key was absent, 0 when it replaced a row id), as the store documents."""

    def __init__(self, sorted_keys: np.ndarray, row_ids: np.ndarray):
        self.keys = np.asarray(sorted_keys, np.int64)
        self.rows = np.asarray(row_ids, np.int64)

    def __len__(self) -> int:
        return int(self.keys.size)

    def lookup(self, q: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        q = np.asarray(q, np.int64)
        if self.keys.size == 0:
            return np.zeros(q.shape, bool), np.full(q.shape, -1, np.int64)
        i = np.minimum(np.searchsorted(self.keys, q), self.keys.size - 1)
        found = self.keys[i] == q
        return found, np.where(found, self.rows[i], -1)

    def insert(self, keys: np.ndarray, row_ids: np.ndarray) -> np.ndarray:
        """Linearized batch insert, in the order given."""
        res = np.zeros(len(keys), np.int32)
        new_r: dict = {}                  # keys absent before the batch
        for j, (k, r) in enumerate(zip(np.asarray(keys, np.int64).tolist(),
                                       np.asarray(row_ids).tolist())):
            if self.lookup(np.array([k]))[0][0]:
                self.rows[np.searchsorted(self.keys, k)] = r
            else:
                res[j] = k not in new_r
                new_r[k] = r
        if new_r:
            k = np.fromiter(new_r, np.int64, len(new_r))
            r = np.fromiter(new_r.values(), np.int64, len(new_r))
            pos = np.searchsorted(self.keys, k)
            self.keys = np.insert(self.keys, pos, k)
            self.rows = np.insert(self.rows, pos, r)
            order = np.argsort(self.keys, kind="stable")
            self.keys, self.rows = self.keys[order], self.rows[order]
        return res


class PageMap:
    """(session, block) -> physical page, over a pool of ``n_pages``.

    ``alloc`` counts pages that are out of the pool or already mapped
    (a page mapped twice); ``release`` returns how many of the given
    blocks were mapped.
    """

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self.map: dict[tuple[int, int], int] = {}
        self.owner: dict[int, tuple[int, int]] = {}

    def __len__(self) -> int:
        return len(self.map)

    def alloc(self, seqs, blocks, pages) -> int:
        bad = 0
        for s, b, p in zip(np.asarray(seqs).tolist(),
                           np.asarray(blocks).tolist(),
                           np.asarray(pages).tolist()):
            if not 0 <= p < self.n_pages or p in self.owner:
                bad += 1
            old = self.map.get((s, b))
            if old is not None:
                self.owner.pop(old, None)
            self.map[(s, b)] = p
            self.owner[p] = (s, b)
        return bad

    def lookup(self, seqs, blocks) -> tuple[np.ndarray, np.ndarray]:
        got = [self.map.get(k) for k in zip(np.asarray(seqs).tolist(),
                                            np.asarray(blocks).tolist())]
        found = np.array([g is not None for g in got], bool)
        pages = np.array([-1 if g is None else g for g in got], np.int64)
        return found, pages

    def release(self, seq: int, blocks) -> int:
        n = 0
        for b in np.asarray(blocks).tolist():
            p = self.map.pop((int(seq), b), None)
            if p is not None:
                self.owner.pop(p, None)
                n += 1
        return n
