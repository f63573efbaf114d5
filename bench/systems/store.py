"""YCSB traffic on the sample store (``repro.data.store.IndexedSampleStore``).

A unit of the window is one client batch: ``ingest`` of the batch's new
records, then ``get_batch`` of its reads, each run to completion.  Rows
are made on the device from the seed (``make_rows``); keys follow YCSB's
hashed insertion order (``bench.generate.KeyStream``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import generate as gen
from bench.reference import SortedIndex
from bench.timing import span

RESERVOIR = 16          # read batches whose rows are kept for the check
READ_CHUNK = 64         # read batches drawn at a time
INSERT_CHUNK = 4096     # new keys issued at a time


@functools.partial(jax.jit, static_argnums=(0, 1))
def make_rows(n: int, width: int, s0, s1):
    """The table, [n, width] int32, in one program on the device: word ``c``
    of row ``r`` is ``generate.row_hash``'s mix of ``r * width + c``."""
    r = lax.broadcasted_iota(jnp.uint32, (n, width), 0)
    c = lax.broadcasted_iota(jnp.uint32, (n, width), 1)
    h = (r * jnp.uint32(width) + c) * jnp.uint32(0x9E3779B1) + s0
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return lax.bitcast_convert_type(h ^ s1, jnp.int32)


def program_store(config: dict, rows, sorted_keys: np.ndarray, seed: int):
    from repro.data.store import IndexedSampleStore, StoreConfig
    cfg = StoreConfig(n_samples=config["records"],
                      seq_len=rows.shape[1] - 1,
                      index_levels=config["index_levels"],
                      foresight=config["foresight"],
                      use_kernel=config["use_kernel"],
                      seed=seed % 2**31)
    store = IndexedSampleStore(cfg, rows=rows,
                               keys=sorted_keys.astype(np.int32))
    jax.block_until_ready(store.index)
    return store


class Cell:
    """One configuration of the store under one YCSB mix."""

    def __init__(self, config: dict, mix: dict, seed: int,
                 make_system=program_store):
        self.config, self.mix, self.seed = config, mix, seed
        self.make_system = make_system
        self.records = config["records"]
        self.width = config["fieldcount"] * config["fieldlength"] // 4
        self.n_ins = round(mix["batch"] * mix["insert"])
        self.n_read = mix["batch"] - self.n_ins
        self.log: list = []
        self.kept: list = []          # reservoir of (log index, rows)
        self.reads_done = 0
        self.failed = 0

    # -- set-up --------------------------------------------------------------

    def setup(self, log) -> None:
        w = gen.seed_words(self.seed, 4)
        self.rng = np.random.default_rng(w)
        self.keys = gen.KeyStream(self.seed)
        self.keys.extend(self.records)
        self.initial = self.keys.sorted_keys
        self.inserted = 0
        self.words = gen.row_words(self.seed)
        rows = make_rows(self.records, self.width,
                         *(jnp.uint32(x) for x in self.words))
        self.system = self.make_system(self.config, rows, self.initial,
                                       self.seed)
        del rows
        self.chooser = gen.request_distribution(
            self.mix["request_distribution"], self.records,
            self.mix.get("zipfian_constant", gen.ZIPFIAN_CONSTANT))
        self.read_u = np.empty((0, self.n_read))
        log(f"store: records={self.records} row_bytes={self.width * 4} "
            f"levels={self.config['index_levels']} batch={self.mix['batch']}"
            f" (reads {self.n_read}, inserts {self.n_ins})")
        # warm every shape the window uses: the inserts are real records
        # of the stream, the reads are checked like any other
        for _ in range(2):
            self.unit()

    # -- the window's unit ---------------------------------------------------

    def live(self) -> int:
        return self.records + self.inserted

    def _next_inserts(self) -> tuple[np.ndarray, np.ndarray]:
        while self.keys.keys.size < self.records + self.inserted + self.n_ins:
            with span("generate", "host"):
                self.keys.extend(INSERT_CHUNK)
        items = np.arange(self.records + self.inserted,
                          self.records + self.inserted + self.n_ins)
        return self.keys.keys[items], items % self.records

    def _next_reads(self) -> np.ndarray:
        if not len(self.read_u):
            with span("generate", "host"):
                self.read_u = self.rng.random((READ_CHUNK, self.n_read))
        u, self.read_u = self.read_u[0], self.read_u[1:]
        items = self.chooser.draw(u, self.records + self.inserted)
        return self.keys.keys[items]

    def unit(self) -> int:
        if self.n_ins:
            k, r = self._next_inserts()
            with span("ingest", "write", self.n_ins):
                res = self.system.ingest(jnp.asarray(k, jnp.int32),
                                         jnp.asarray(r, jnp.int32))
                jax.block_until_ready((res, self.system.index))
            self.inserted += self.n_ins
            self.log.append(("ingest", k, r, res))
        if self.n_read:
            q = self._next_reads()
            with span("get_batch", "read", self.n_read):
                rows, found = self.system.get_batch(jnp.asarray(q, jnp.int32))
                jax.block_until_ready((rows, found))
            self.log.append(("get_batch", q, found))
            self._keep(len(self.log) - 1, rows)
        return self.n_ins + self.n_read

    def _keep(self, at: int, rows) -> None:
        """Reservoir sample, drawn from the seed, of the read batches whose
        rows the check compares; the rest are dropped on the device."""
        k = self.reads_done
        self.reads_done += 1
        if k < RESERVOIR:
            self.kept.append((at, rows))
        else:
            j = int(self.rng.integers(0, k + 1))
            if j < RESERVOIR:
                self.kept[j] = (at, rows)

    # -- the check -------------------------------------------------------------

    def fetch(self) -> None:
        """Copy every answer to the host; the device state can then go."""
        self.log = [ev[:-1] + (np.asarray(ev[-1]),) for ev in self.log]
        self.kept = {at: np.asarray(rows) for at, rows in self.kept}
        self.system = None

    def check(self, log) -> dict:
        ref = SortedIndex(self.initial, np.arange(self.records))
        n = {"found_mismatch": 0, "row_mismatch": 0, "insert_mismatch": 0}
        compared = {"reads": 0, "rows": 0, "inserts": 0}
        for at, ev in enumerate(self.log):
            if ev[0] == "ingest":
                _, k, r, res = ev
                want = ref.insert(k, r)
                n["insert_mismatch"] += int(np.sum(res != want))
                self.failed += int(np.sum(res == 0))
                compared["inserts"] += len(k)
                continue
            _, q, found = ev
            want_f, want_r = ref.lookup(q)
            n["found_mismatch"] += int(np.sum(found != want_f))
            compared["reads"] += len(q)
            if at in self.kept:
                want_rows = gen.row_hash(np.where(want_f, want_r, 0),
                                         self.width, self.words)
                n["row_mismatch"] += int(np.sum(np.any(
                    self.kept[at] != want_rows, axis=1)))
                compared["rows"] += len(q)
        log(f"check: compared {compared} against bench.reference.SortedIndex")
        return {name: (v, 0) for name, v in n.items()}
