"""Skiplist-indexed in-memory sample store — Foresight in the data plane.

This is the framework-level deployment of the paper's technique (DESIGN.md
§3): training samples live in a flat token array; an ordered index maps
sample *keys* (stable 31-bit ids, e.g. shard/document hashes) to storage
rows.  The data pipeline looks samples up by key — a batched foresight
traversal — and can range-scan for shard assignment.  The index variant
(base / foresight / foresight+kernel) is selectable so the macro benchmarks
can compare them end-to-end, mirroring the paper's DBx1000 experiment where
Fraser's skiplist indexes table rows.

When the index outgrows one VMEM tile, the store partitions the key space
into ``n_shards`` contiguous range shards (``core.sharded``): ``n_shards=0``
auto-selects — monolithic unless the kernel path is in use AND the table
exceeds ``VMEM_BUDGET_BYTES`` (the budget only binds kernels), in which
case the smallest power-of-two shard count whose per-shard tile fits.
All lookups, scans, and updates route host-free through the flat boundary
array; callers never see the partitioning.  With ``rebalance`` on (the
default) a key-skewed ingest stream can no longer fill one shard early:
``apply_ops_sharded`` splits ahead of any shard a batch would exhaust and
re-levels watermarks after (``core.sharded``), and every ``repack_every``
update batches the store amortizes an occupancy-equalizing ``repack``.
With it off, the fixed-capacity caveat applies (failed inserts report 0 in
the result flags).  ``max_shards`` caps rebalancing growth — and doubles
as the static ceiling a jit-driven caller pads the index to
(``core.rebalance_traced.pad_shards``) so traced in-place splits keep
working inside one compiled trace.

Past one device's memory, ``mesh_devices`` >= 2 partitions the key space
over a ``D``-device mesh (``core.mesh_index``): each device holds one key
slice's index (``n_shards`` range shards, auto = 1) and the block of rows
of the same stride (``mesh_index.place_rows``), so no device holds the
whole table.  Lookups, reads and updates route lanes to their owning
device by ``all_to_all``; a read gathers each row on the device that holds
it, since an insert may name a row in any block.  Each device's shards
keep their fixed capacity (no rebalancing across or within devices), and
``range_scan`` is not served.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import mesh_index as mi
from repro.core import sharded as shd
from repro.core import skiplist as sl
from repro.kernels import ops as kops
from repro.launch.mesh import make_index_mesh


@dataclasses.dataclass
class StoreConfig:
    n_samples: int = 4096
    seq_len: int = 128
    vocab: int = 256
    index_levels: int = 16
    foresight: bool = True
    use_kernel: bool = False
    n_shards: int = 0        # 0 = auto (shard only past the VMEM budget)
    clustered: bool = True   # shard-sort query batches -> DMA only routed
                             # tiles (kernels/ops.cluster_queries); False
                             # keeps the dense (B//QBLK, S) launch
    rebalance: bool = True   # sharded only: split/merge around skewed ingest
    max_shards: int = 0      # shard-count ceiling for rebalancing growth
                             # (0 = library default, core.sharded.MAX_SHARDS).
                             # Eagerly this caps host-side split growth; a
                             # caller driving updates under jit should pad
                             # the index to this ceiling first
                             # (core.rebalance_traced.pad_shards) so the
                             # traced in-place splits have slots to spend
                             # and the apply traces ONCE at the ceiling.
    repack_every: int = 0    # update batches between amortized repacks
                             # (0 = never; sharded + rebalance only)
    seed: int = 0
    mesh_devices: int = 1    # 1 = one device; >= 2 = index and rows
                             # partitioned by key range over a D-device
                             # mesh (XLA engine; n_shards is then per
                             # device)


_apply_donated = jax.jit(sl.apply_ops, donate_argnums=(0,))


def _read_rows(search):
    """One program: ``search``'s traversal, then the row gather of the
    keys it found (row 0 for a missing key): (rows, found[, steps])."""
    def read_rows(index, rows, keys):
        found, row_ids, *steps = search(index, keys)
        return (rows[jnp.where(found, row_ids, 0)], found, *steps)
    return read_rows


class _Reads(NamedTuple):
    """The one-device XLA read programs of one index kind, each an
    ``obs.Read``: a plain program and its counted twin."""

    span: str
    lookup: obs.Read       # -> (found, row_ids)
    read_rows: obs.Read    # -> (rows, found)


def _reads(span: str, search, counted) -> _Reads:
    return _Reads(span, obs.Read(search, counted),
                  obs.Read(_read_rows(search), _read_rows(counted)))


# keyed by ``IndexedSampleStore.sharded``: the monolithic index (its
# preds-free read path), then the sharded one
_READS = {False: _reads("read.search_fast", sl.search_fast,
                        sl.search_fast_counted),
          True: _reads("read.search_sharded", shd.search_sharded,
                       shd.search_sharded_counted)}


class IndexedSampleStore:
    """rows: [N, seq_len+1] tokens; index: key -> row (Foresight skiplist)."""

    index: Union[sl.SkipListState, shd.ShardedSkipList, mi.MeshShardedIndex]

    def __init__(self, cfg: StoreConfig, rows=None,
                 keys: Optional[np.ndarray] = None):
        """``rows``: [n_samples, seq_len+1] int32, or on a mesh store also
        rows already placed as ``mesh_index.place_rows`` places them."""
        self.cfg = cfg
        rng = np.random.default_rng(cfg.seed)
        if rows is None:
            rows = _markov_corpus(rng, cfg.n_samples, cfg.seq_len + 1,
                                  cfg.vocab)
        if keys is None:
            keys = np.sort(rng.choice(2**30, cfg.n_samples, replace=False))
        self.keys_np = keys.astype(np.int64)
        self.mesh = None
        if cfg.mesh_devices >= 2:
            self._build_mesh(rows, keys)
            return
        self.rows = jnp.asarray(rows, jnp.int32)
        cap = int(2 ** np.ceil(np.log2(cfg.n_samples * 2 + 4)))
        self.n_shards = cfg.n_shards
        if self.n_shards == 0:
            # The VMEM budget only binds the kernel path; the pure-JAX path
            # has no tile constraint, so auto keeps it monolithic (sharding
            # there would just cost S-times apply_ops work for nothing).
            mono_tile = kops.tile_bytes(cfg.index_levels, cap, cfg.foresight)
            needs_shards = cfg.use_kernel and \
                mono_tile > kops.VMEM_BUDGET_BYTES
            self.n_shards = kops.auto_shards(
                cfg.n_samples, cfg.index_levels,
                cfg.foresight) if needs_shards else 1
        self._updates_since_repack = 0
        row_ids = jnp.arange(cfg.n_samples, dtype=jnp.int32)  # value = row id
        if self.n_shards > 1:
            self.index = shd.build_sharded(
                jnp.asarray(keys, jnp.int32), row_ids,
                n_shards=self.n_shards, levels=cfg.index_levels,
                foresight=cfg.foresight, seed=cfg.seed)
        else:
            self.index = sl.build(
                jnp.asarray(keys, jnp.int32), row_ids,
                capacity=cap, levels=cfg.index_levels,
                foresight=cfg.foresight, seed=cfg.seed)

    def _build_mesh(self, rows, keys: np.ndarray) -> None:
        cfg = self.cfg
        if cfg.use_kernel:
            raise ValueError("a mesh store (mesh_devices >= 2) runs the XLA "
                             "engine: use_kernel must be False")
        self.mesh = make_index_mesh(cfg.mesh_devices)
        self.n_shards = cfg.n_shards or 1
        self.rows = mi.place_rows(rows, self.mesh, cfg.n_samples)
        self.index = mi.build_mesh_index(
            jnp.asarray(keys, jnp.int32),
            jnp.arange(cfg.n_samples, dtype=jnp.int32),  # value = row id
            mesh=self.mesh, n_devices=cfg.mesh_devices,
            n_shards=self.n_shards, levels=cfg.index_levels,
            foresight=cfg.foresight, seed=cfg.seed)

    @property
    def sharded(self) -> bool:
        return isinstance(self.index, shd.ShardedSkipList)

    # -- lookups ------------------------------------------------------------

    def lookup(self, keys: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Batched key lookup -> (found [B], row_ids [B]).

        Under a profiler trace the XLA traversals add their loop counts to
        ``obs``'s counters."""
        n = keys.shape[0]
        if self.mesh is not None:
            with obs.span("read.search_mesh", ops=n):
                found, vals, counts = mi.search_mesh_counted(
                    self.index, keys, mesh=self.mesh)
            self._count_mesh(counts)
            return found, vals
        if self.cfg.use_kernel:
            r = kops.search_kernel(self.index, keys,   # auto-dispatches
                                   cluster=self.cfg.clustered)
            return r.found, r.vals
        reads = _READS[self.sharded]
        with obs.span(reads.span, ops=n):
            return self._launch(reads.lookup, keys)

    def get_batch(self, keys: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Fetch token rows for keys (missing keys fall back to row 0)."""
        n = keys.shape[0]
        with obs.span("store.get_batch", ops=n):
            if self.mesh is not None:
                return self._mesh_get_batch(keys)
            if not self.cfg.use_kernel:
                # one launch: the search and the row gather run in one
                # program, so both spans hold it
                reads = _READS[self.sharded]
                with obs.span(reads.span, ops=n), \
                        obs.span("store.gather_rows", ops=n):
                    return self._launch(reads.read_rows, self.rows, keys)
            found, row_ids = self.lookup(keys)
            with obs.span("store.gather_rows", ops=n):
                safe = jnp.where(found, row_ids, 0)
                return self.rows[safe], found

    def _launch(self, read: obs.Read, *args) -> tuple:
        """Run one of ``_Reads`` on this index; under a profiler trace
        ``obs`` adds the counted twin's steps."""
        out, steps = read(self.index, *args)
        if steps is not None:
            obs.count_search(steps, per_step=1 if self.cfg.foresight else 2)
        return out

    def _mesh_get_batch(self, keys: jax.Array
                        ) -> Tuple[jax.Array, jax.Array]:
        """One launch: the search and the row gather run in one program,
        so both spans hold it."""
        n = keys.shape[0]
        with obs.span("read.search_mesh", ops=n), \
                obs.span("store.gather_rows", ops=n):
            rows, found, counts = mi.read_rows_mesh(
                self.index, self.rows, keys, mesh=self.mesh)
        self._count_mesh(counts)
        return rows, found

    def _count_mesh(self, counts: mi.MeshReadCounts) -> None:
        """The mesh read's program counts on every call (one program,
        traced or not); ``obs`` adds them only under a trace."""
        obs.count_mesh_read(counts.steps, counts.live,
                            per_step=1 if self.cfg.foresight else 2)

    def range_scan(self, lo, hi, max_out: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Ordered (key, row_id) scan of [lo, hi); crosses shard boundaries."""
        if self.mesh is not None:
            raise NotImplementedError(
                "range_scan is not served on a mesh store (mesh_devices >= 2)")
        lo = jnp.asarray(lo, jnp.int32)
        hi = jnp.asarray(hi, jnp.int32)
        if self.sharded:
            return shd.range_scan_sharded(self.index, lo, hi, max_out)
        return sl.range_scan(self.index, lo, hi, max_out)

    # -- updates (streaming ingestion) ---------------------------------------

    def _apply(self, ops: jax.Array, keys: jax.Array, vals: jax.Array
               ) -> jax.Array:
        n = ops.shape[0]
        if self.mesh is not None:
            with obs.span("write.apply_ops_mesh", ops=n):
                self.index, results, _ = mi.apply_ops_mesh(
                    self.index, ops, keys, vals, mesh=self.mesh,
                    seed=self.cfg.seed)
        elif self.sharded:
            with obs.span("write.apply_ops_sharded", ops=n):
                self.index, results = shd.apply_ops_sharded(
                    self.index, ops, keys, vals,
                    rebalance=self.cfg.rebalance,
                    max_shards=self.cfg.max_shards or shd.MAX_SHARDS,
                    seed=self.cfg.seed)
            self._updates_since_repack += 1
            if (self.cfg.rebalance and self.cfg.repack_every and
                    self._updates_since_repack >= self.cfg.repack_every):
                self.index = shd.repack(self.index, seed=self.cfg.seed)
                self._updates_since_repack = 0
        else:
            # donated: the old table's buffers become the new one's, so a
            # table near the device's size is never held twice
            with obs.span("write.apply_ops", ops=n):
                self.index, results = _apply_donated(self.index, ops, keys,
                                                     vals)
        return results

    def ingest(self, keys: jax.Array, row_ids: jax.Array) -> jax.Array:
        """Insert new key->row mappings (linearized batch)."""
        with obs.span("store.ingest", ops=keys.shape[0]):
            ops = jnp.full(keys.shape, sl.OP_INSERT, jnp.int32)
            return self._apply(ops, keys, row_ids)

    def evict(self, keys: jax.Array) -> jax.Array:
        with obs.span("store.evict", ops=keys.shape[0]):
            ops = jnp.full(keys.shape, sl.OP_DELETE, jnp.int32)
            return self._apply(ops, keys, jnp.zeros_like(keys))


def _markov_corpus(rng: np.random.Generator, n: int, width: int,
                   vocab: int) -> np.ndarray:
    """Order-1 Markov token rows — learnable structure for train examples."""
    trans = rng.dirichlet(np.full(vocab, 0.05), size=vocab)
    cum = np.cumsum(trans, axis=1)
    out = np.empty((n, width), np.int32)
    state = rng.integers(0, vocab, size=n)
    out[:, 0] = state
    for t in range(1, width):
        u = rng.random(n)
        state = (cum[state] < u[:, None]).sum(axis=1)
        state = np.minimum(state, vocab - 1)
        out[:, t] = state
    return out
