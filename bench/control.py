"""The controls: the plain reference put in the program's place with one
guarantee broken, driven by the same harness, so the comparison that
decides ``correct`` is shown to fail.

    python -m bench.control --workload <name> --seed <n> --seconds <s>

The configurations state no precision; each control compares keys at a
lower precision than the configuration states, the shortcut a lossy
index would take:

* store: keys compared on their top ``bit_length(records)`` of 31 bits,
  about as many key prefixes as the table has records, so a read of a
  key returns the row of the first key that shares its prefix;
* page table: page keys compared without their lowest 4 block bits, so
  sixteen blocks of a session share one mapping.

The benchmark's own runs never run these.
"""
from __future__ import annotations

import sys

import numpy as np

PAGE_DROP_BITS = 4


class ReducedKeyStore:
    """``get_batch``/``ingest`` of the store over a sorted array of key
    prefixes."""

    index = None        # no device table to wait for

    def __init__(self, config, rows, sorted_keys, seed):
        self.drop = 31 - int(config["records"]).bit_length()
        self.rows = rows
        self.prefix = np.asarray(sorted_keys, np.int64) >> self.drop
        self.row_ids = np.arange(len(sorted_keys))

    def _lookup(self, q):
        p = np.asarray(q, np.int64) >> self.drop
        i = np.minimum(np.searchsorted(self.prefix, p), self.prefix.size - 1)
        found = self.prefix[i] == p
        return found, np.where(found, self.row_ids[i], 0)

    def get_batch(self, keys):
        import jax.numpy as jnp
        found, rid = self._lookup(keys)
        return self.rows[jnp.asarray(rid, jnp.int32)], jnp.asarray(found)

    def ingest(self, keys, row_ids):
        import jax.numpy as jnp
        found, _ = self._lookup(keys)
        p = np.asarray(keys, np.int64) >> self.drop
        pos = np.searchsorted(self.prefix, p)
        self.prefix = np.insert(self.prefix, pos, p)
        self.row_ids = np.insert(self.row_ids, pos, np.asarray(row_ids))
        return jnp.asarray((~found).astype(np.int32))


class ReducedKeyPageTable:
    """``alloc``/``lookup``/``release_blocks`` over a dict keyed by the page
    key without its low ``PAGE_DROP_BITS`` bits, with a plain free list."""

    index = None

    def __init__(self, config):
        self.free = list(range(config["n_pages"] - 1, -1, -1))
        self.map: dict = {}

    @staticmethod
    def _keys(seqs, blocks):
        return ((np.asarray(seqs, np.int64) << 12)
                | np.asarray(blocks, np.int64)) >> PAGE_DROP_BITS

    def alloc(self, seqs, blocks):
        pages = np.array([self.free.pop() for _ in range(len(seqs))],
                         np.int32)
        for k, p in zip(self._keys(seqs, blocks).tolist(), pages.tolist()):
            old = self.map.get(k)
            if old is not None:
                self.free.append(old)
            self.map[k] = p
        return pages

    def lookup(self, seqs, blocks):
        import jax.numpy as jnp
        got = [self.map.get(k) for k in self._keys(seqs, blocks).tolist()]
        return (jnp.asarray([g is not None for g in got]),
                jnp.asarray([-1 if g is None else g for g in got], jnp.int32))

    def release_blocks(self, seq, blocks):
        freed = 0
        for k in self._keys(np.full(len(blocks), seq), blocks).tolist():
            p = self.map.pop(k, None)
            if p is not None:
                self.free.append(p)
                freed += 1
        return freed

    @property
    def n_live(self) -> int:
        return len(self.map)


CONTROLS = {"store": ReducedKeyStore, "page_table": ReducedKeyPageTable}


def main(argv=None) -> int:
    from bench import run, spec
    args = run.parse(argv)
    _, _, config, _ = spec.resolve(run.ROOT, args.workload)
    return run.main(argv, make_system=CONTROLS[config["system"]])


if __name__ == "__main__":
    sys.exit(main())
