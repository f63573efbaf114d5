"""Paged KV-cache with a Foresight-skiplist page table.

The serving-plane deployment of the paper (DESIGN.md §3): logical KV blocks
of live sequences are mapped to physical pages of a fixed pool.  The page
table is an ordered index over the composite key ``seq_id << 12 | block_id``
— the lookup pattern of every decode step (find the pages of a sequence) and
of eviction (range-delete a sequence's pages) is exactly the skiplist
read/update workload the paper accelerates.  Lookups are batched foresight
traversals; the variant (base / foresight / kernel) is selectable so the
macrobenchmark can compare them under a realistic serving key distribution.

The table is a ``core.sharded.ShardedSkipList`` held directly and, with
``rebalance`` on (the default), built at a static ``max_shards`` ceiling
(``empty_sharded`` at the ceiling — spare shards are dead ``KEY_MAX``-
boundary slots).  The update path is ``jax.jit``-compiled: splits and
merges run as the traced in-place edits of ``core.rebalance_traced``, so a
seq-id-skewed allocation burst can no longer exhaust one shard's fixed
capacity while its neighbours sit empty, and the compiled apply is traced
ONCE at the ceiling no matter how many shards come and go (batch sizes are
pow2-padded with no-op reads to bound shape variants).  The old eager-only
caveat is gone: this is the production serving loop shape — rebalancing
lives inside the jitted region.

Composite keys must stay inside int31: ``alloc`` / ``lookup`` / ``release``
validate ``seq_id < MAX_SEQS`` and ``block_id < 2**BLOCK_BITS`` and raise
``ValueError`` on violation — out-of-range ids would wrap ``page_key``
negative in int32 and collide with the ``KEY_MIN``/sentinel key space.

Mesh opt-in: past a size threshold (or forced via ``mesh_devices``) the
table is held as a ``core.mesh_index.MeshShardedIndex`` instead — the key
space is range-partitioned across the devices of a 1-D ``("index",)``
mesh and every apply/lookup goes through the ``shard_map`` +
``all_to_all`` data path, which is bit-identical to the single-device
table on the same op stream.  The composite page-key space is dense in
``[0, MAX_SEQS << BLOCK_BITS)``, so the uniform static device partition
of ``empty_mesh_index`` balances devices by construction.  Per-device
shard capacity is sized for the FULL pool, so a seq-id-skewed workload
can never lose a mapping to the partition (it costs headroom, not
correctness); cross-device skew is surfaced through ``load_stats``.

Robustness (ROBUSTNESS.md): ``try_alloc`` is the soft-fail allocation
path — it returns a per-block success mask instead of raising, granting a
*prefix* of the requested blocks when the pool or a shard runs out, so the
serving plane can shed/preempt/retry instead of dying.  ``alloc`` is the
strict wrapper (raises on any failed grant) kept for callers that treat
exhaustion as a bug.  Pool watermarks (``fill_fraction`` vs the configured
``high_water``/``low_water``) give the engine a preemption trigger *before*
hard exhaustion — the page-pool mirror of the PR 4/5 shard watermark
drivers.  The ``chaos`` hook threads a ``runtime.chaos.FaultInjector``
into the ``kvcache.alloc`` injection site (forced pool exhaustion and
forced capacity failure).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import mesh_index as mshi
from repro.core import sharded as shd
from repro.core import skiplist as sl
from repro.kernels import ops as kops
from repro.launch import mesh as lmesh
from repro.runtime import chaos as rchaos

BLOCK_BITS = 12                  # up to 4096 blocks per sequence
MAX_SEQS = 1 << 18


def page_key(seq_id, block_id):
    return (seq_id << BLOCK_BITS) | block_id


@dataclasses.dataclass
class PagedCacheConfig:
    n_pages: int = 4096
    page_tokens: int = 16
    levels: int = 16
    foresight: bool = True
    use_kernel: bool = False
    n_shards: int = 1            # minimum shard count (kernel path may raise)
    rebalance: bool = True       # split/merge shards as the table evolves
    max_shards: int = 0          # static ceiling for traced rebalancing
                                 # (0 = auto: max(8, n_shards, kernel tiling))
    seed: int = 0
    high_water: float = 0.85     # pool fill fraction: preempt above this
    low_water: float = 0.60     # ... down to this (hysteresis band)
    mesh_devices: int = 1        # 1 = single-device table; >=2 = force a
                                 # D-device mesh table; 0 = auto (mesh on
                                 # all devices once n_pages crosses
                                 # mesh_min_pages AND >1 device exists)
    mesh_min_pages: int = 1 << 16  # auto-mode size threshold
    node_width: int = 1          # >1 = fat-node table layout (B keys per
                                 # node, one gather serves a lane tile);
                                 # bit-identical to the scalar layout


class PageTable:
    """Ordered (seq, block) -> physical page index, sharded-skiplist-backed."""

    index: shd.ShardedSkipList

    def __init__(self, cfg: PagedCacheConfig,
                 chaos: "rchaos.FaultInjector | None" = None):
        self.cfg = cfg
        self.chaos = chaos
        shd.validate_watermarks(cfg.high_water, cfg.low_water)
        n_shards = cfg.n_shards
        if cfg.use_kernel:
            # the kernel path pins one shard tile in VMEM per grid step;
            # size the partition so a full table ships fitting tiles
            n_shards = max(n_shards, kops.auto_shards(
                cfg.n_pages, cfg.levels, cfg.foresight,
                node_width=cfg.node_width))
        if cfg.rebalance:
            # build AT the ceiling: spare shards are the dead slots the
            # traced splits spend, and the jitted apply traces once there
            n_shards = max(n_shards, cfg.max_shards or 8)
        if n_shards > 1:
            cap = shd.shard_capacity_for(cfg.n_pages, n_shards,
                                         cfg.node_width)
        else:
            cap = shd.shard_capacity_for(cfg.n_pages, 1, cfg.node_width)
        n_dev = cfg.mesh_devices
        if n_dev == 0:       # auto: mesh once the table outgrows a device
            n_dev = len(jax.devices()) if cfg.n_pages >= cfg.mesh_min_pages \
                else 1
        self.mesh = None
        self.load_stats = None   # last apply's DeviceLoadStats (mesh only)
        if n_dev > 1:
            # make_index_mesh validates n_dev against jax.devices() and
            # raises (never silently shrinks) when the topology is short
            self.mesh = lmesh.make_index_mesh(n_dev)
            # capacity sized for the FULL pool on every device: a seq-id
            # skewed stream may land everything on one device slice, and
            # losing mappings to the static partition would turn load
            # into corruption.  Costs headroom, never correctness.
            self.index = mshi.empty_mesh_index(
                n_devices=n_dev, n_shards=n_shards, capacity=cap,
                levels=cfg.levels, foresight=cfg.foresight, seed=cfg.seed,
                key_span=MAX_SEQS << BLOCK_BITS,
                node_width=cfg.node_width, mesh=self.mesh)
        else:
            self.index = shd.empty_sharded(
                n_shards=n_shards, capacity=cap, levels=cfg.levels,
                foresight=cfg.foresight, seed=cfg.seed,
                node_width=cfg.node_width)
        self.free = list(range(cfg.n_pages - 1, -1, -1))
        # one compiled apply at the shard ceiling; rebalance/seed are
        # baked in statically, batch shapes pow2-padded by _apply.  The
        # input index state is donated — _apply unconditionally replaces
        # self.index with the result, so the old buffers (a full table at
        # the ceiling) can be reused instead of held alive alongside it.
        # (The mesh path jits inside apply_ops_mesh, cached per mesh.)
        # A named function, so that its program and device ops are named
        # ``jit_apply_ops_sharded`` in traces and compile logs.
        def apply_ops_sharded(index, ops, keys, vals):
            return shd.apply_ops_sharded(index, ops, keys, vals,
                                         rebalance=cfg.rebalance,
                                         seed=cfg.seed)
        self._jit_apply = None if self.mesh is not None else jax.jit(
            apply_ops_sharded, donate_argnums=(0,))

    def _apply(self, ops: jax.Array, keys: jax.Array, vals: jax.Array
               ) -> jax.Array:
        n = ops.shape[0]
        pad = (1 if n == 0 else 1 << int(n - 1).bit_length()) - n
        with obs.span("page_table.pad", ops=n, padded=pad):
            if pad:  # no-op reads of key 0: no state, RNG, or routing effect
                ops = jnp.concatenate([ops, jnp.full((pad,), sl.OP_READ,
                                                     jnp.int32)])
                keys = jnp.concatenate([keys, jnp.zeros((pad,), jnp.int32)])
                vals = jnp.concatenate([vals, jnp.zeros((pad,), jnp.int32)])
        if self.mesh is not None:
            self.index, results, self.load_stats = mshi.apply_ops_mesh(
                self.index, ops, keys, vals, mesh=self.mesh,
                rebalance=self.cfg.rebalance, seed=self.cfg.seed)
        else:
            with obs.span("write.apply_ops_sharded", ops=n):
                self.index, results = self._jit_apply(self.index, ops, keys,
                                                      vals)
        return results[:n]

    def _search(self, keys: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Traversal-loop lookup on whichever table variant is live; under
        a profiler trace the single-device loop adds its counts to
        ``obs``'s counters."""
        if self.mesh is not None:
            return mshi.search_mesh(self.index, keys, mesh=self.mesh)
        n = keys.shape[0]
        with obs.span("read.search_sharded", ops=n):
            if not obs.counting():
                return shd.search_sharded(self.index, keys)
            found, vals, steps = shd.search_sharded_counted(
                self.index, keys)
        obs.count_search(steps, per_step=1 if self.cfg.foresight else 2)
        return found, vals

    def _validate_ids(self, seq_ids, block_ids) -> None:
        seq = np.atleast_1d(np.asarray(seq_ids, np.int64))
        blk = np.atleast_1d(np.asarray(block_ids, np.int64))
        if seq.size and (seq.min() < 0 or seq.max() >= MAX_SEQS):
            raise ValueError(
                f"seq_id out of range [0, {MAX_SEQS}): got "
                f"[{seq.min()}, {seq.max()}] — page_key would wrap negative "
                "in int32 and collide with the sentinel key space")
        if blk.size and (blk.min() < 0 or blk.max() >= (1 << BLOCK_BITS)):
            raise ValueError(
                f"block_id out of range [0, {1 << BLOCK_BITS}): got "
                f"[{blk.min()}, {blk.max()}] — blocks past 2**BLOCK_BITS "
                "alias the next sequence's key range")

    # -- allocation -----------------------------------------------------------

    def _insert_pages(self, keys: np.ndarray, pages: np.ndarray
                      ) -> np.ndarray:
        """Insert key->page mappings; returns the LOST mask.

        A result of 0 is either an upsert of an already-mapped block
        (mapping updated in place; pre-existing contract — counts as a
        success) or a capacity-failed insert (mapping LOST).  Lost pages
        are reclaimed to the free list here, so callers only decide how
        loudly to report them (``alloc`` raises, ``try_alloc`` masks).
        """
        n = len(keys)
        ops = jnp.full((n,), sl.OP_INSERT, jnp.int32)
        res = self._apply(ops, jnp.asarray(keys), jnp.asarray(pages))
        with obs.span("page_table.host_sync", ops=n):
            res = np.asarray(res)  # trace-ok: single batched sync; result gates host-side reclaim
        lost = np.zeros(n, bool)
        if not res.all():
            failed = res == 0
            still_absent = ~np.asarray(
                self._search(jnp.asarray(keys[failed]))[0])
            if still_absent.any():
                lost[np.flatnonzero(failed)[still_absent]] = True
                for p in pages[lost]:
                    self.free.append(int(p))
        return lost

    def alloc(self, seq_ids: np.ndarray, block_ids: np.ndarray
              ) -> np.ndarray:
        """Allocate physical pages for (seq, block) pairs; returns pages.

        Strict path: raises on pool exhaustion or a capacity-failed insert
        (lost pages reclaimed first) — exhaustion is a caller bug here.
        The serving plane uses ``try_alloc`` instead and degrades.
        """
        n = len(seq_ids)
        with obs.span("page_table.alloc", ops=n):
            self._validate_ids(seq_ids, block_ids)
            if n > len(self.free):
                raise RuntimeError("KV page pool exhausted")
            with obs.span("page_table.free_list", ops=n):
                pages = np.array([self.free.pop() for _ in range(n)],
                                 np.int32)
            keys = page_key(seq_ids.astype(np.int64),
                            block_ids.astype(np.int64)).astype(np.int32)
            lost = self._insert_pages(keys, pages)
        if lost.any():
            raise RuntimeError(
                f"page-table insert failed for {int(lost.sum())} block(s): "
                "shard capacity exhausted (rebalance off or shards "
                "indivisible); their pages were returned to the pool")
        return pages

    def try_alloc(self, seq_ids: np.ndarray, block_ids: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """Soft-fail allocation: ``(ok_mask, pages)``, never raises on
        exhaustion.

        Grants a *prefix* of the request while pages last (``ok`` is
        monotone until the first pool miss); a capacity-failed insert
        inside the grant flips just that block's ``ok`` off (its page is
        reclaimed).  ``pages`` holds -1 where ``ok`` is False.  Id-range
        violations still raise ``ValueError`` — those are caller bugs,
        not load.  This is the ``kvcache.alloc`` chaos injection site:
        a due ``pool_exhausted`` fault forces a zero grant, a due
        ``capacity_fail`` fault forces the whole grant to fail (pages
        reclaimed), exactly the footprint of the real failures.
        """
        self._validate_ids(seq_ids, block_ids)
        n = len(seq_ids)
        ok = np.zeros(n, bool)
        pages = np.full(n, -1, np.int32)
        kinds = self.chaos.poll("kvcache.alloc") if self.chaos is not None \
            else ()
        grant = 0 if rchaos.POOL_EXHAUSTED in kinds else min(n,
                                                             len(self.free))
        if grant == 0:
            return ok, pages
        got = np.array([self.free.pop() for _ in range(grant)], np.int32)
        if rchaos.CAPACITY_FAIL in kinds:
            # forced capacity failure: mappings lost, pages reclaimed —
            # the same observable footprint as a real shard-full insert
            self.free.extend(int(p) for p in got)
            return ok, pages
        keys = page_key(seq_ids[:grant].astype(np.int64),
                        block_ids[:grant].astype(np.int64)).astype(np.int32)
        granted_ok = ~self._insert_pages(keys, got)
        ok[:grant] = granted_ok
        pages[:grant][granted_ok] = got[granted_ok]
        return ok, pages

    def lookup(self, seq_ids: np.ndarray, block_ids: np.ndarray
               ) -> Tuple[jax.Array, jax.Array]:
        """Batched page lookup -> (found, physical_pages).

        Returns DEVICE arrays: no host sync happens here, so a decode loop
        can chain lookups into downstream device work (attention gathers)
        without a per-call round trip.  Callers that need host values
        convert once per batch at their own boundary (as ``release``
        does), never per element.
        """
        n = len(seq_ids)
        with obs.span("page_table.lookup", ops=n):
            with obs.span("page_table.validate", ops=n):
                self._validate_ids(seq_ids, block_ids)
                keys = jnp.asarray(page_key(seq_ids.astype(np.int64),
                                            block_ids.astype(np.int64))
                                   .astype(np.int32))
            if self.cfg.use_kernel:
                r = kops.search_kernel(self.index, keys, mesh=self.mesh)
                return r.found, r.vals
            return self._search(keys)

    def release(self, seq_id: int, n_blocks: int) -> int:
        """Free all pages of a finished sequence (ordered range delete)."""
        if n_blocks > (1 << BLOCK_BITS):
            raise ValueError(
                f"n_blocks={n_blocks} exceeds the {1 << BLOCK_BITS}-block "
                "per-sequence ceiling (2**BLOCK_BITS)")
        return self.release_blocks(seq_id, np.arange(n_blocks,
                                                     dtype=np.int64))

    def release_blocks(self, seq_id: int, block_ids: np.ndarray) -> int:
        """Free specific blocks of a sequence (the non-prefix counterpart
        of ``release``, for returning a partial ``try_alloc`` grant)."""
        blocks = np.atleast_1d(np.asarray(block_ids, np.int64))
        n_blocks = blocks.size
        if n_blocks == 0:
            return 0
        with obs.span("page_table.release", ops=n_blocks):
            self._validate_ids(seq_id, blocks)
            keys = page_key(np.int64(seq_id), blocks).astype(np.int32)
            found, pages = self.lookup(np.full(n_blocks, seq_id), blocks)
            ops = jnp.full((n_blocks,), sl.OP_DELETE, jnp.int32)
            self._apply(ops, jnp.asarray(keys),
                        jnp.zeros(n_blocks, jnp.int32))
            # ONE batched device->host sync at the eager API boundary (the
            # free list is host state); the old per-element loop synced
            # implicitly through python iteration over device arrays
            with obs.span("page_table.host_sync", ops=n_blocks):
                fnp = np.asarray(found, bool)  # trace-ok: single batched sync at eager API boundary
                pnp = np.asarray(pages)        # trace-ok: single batched sync at eager API boundary
            with obs.span("page_table.free_list", ops=n_blocks):
                live = pnp[fnp]
                self.free.extend(int(p) for p in live.tolist())
            return int(fnp.sum())

    # -- pool pressure ---------------------------------------------------------

    @property
    def n_free(self) -> int:
        return len(self.free)

    @property
    def fill_fraction(self) -> float:
        return 1.0 - len(self.free) / self.cfg.n_pages

    @property
    def above_high_water(self) -> bool:
        """Pool pressure past the preemption trigger (ROBUSTNESS.md)."""
        return self.fill_fraction > self.cfg.high_water

    @property
    def below_low_water(self) -> bool:
        return self.fill_fraction <= self.cfg.low_water

    @property
    def n_live(self) -> int:
        if self.mesh is not None:
            return int(mshi.total_n_mesh(self.index))
        return int(shd.total_n(self.index))
