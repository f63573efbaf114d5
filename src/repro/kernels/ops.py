"""jit'd public wrappers over the Pallas traversal kernels.

Adds the ergonomics the raw kernels don't have: query padding to the lane
block, found/value resolution, float-key encoding, and a VMEM-budget check
that decides between the single-tile kernel and the sharded-key-space path.

VMEM-budget math
----------------
Each kernel asks Mosaic for ``VMEM_LIMIT_BYTES`` (32 MiB) of scoped VMEM;
we budget ``VMEM_BUDGET_BYTES`` (12 MiB) for the index tile, which the
sharded grids double-buffer, leaving headroom for query/output blocks and
the row-gather scratch.  The single-tile kernels pin the whole table per
grid step:

* foresight: ``levels * capacity * 2 * 4`` bytes (fused (ptr, key) pairs),
* base:      ``levels * capacity * 4 + capacity * 4`` bytes (nxt + keys),

so e.g. ``levels=16, capacity=2**18`` fused is 32 MiB — past the budget.
Past it, callers hold a ``ShardedSkipList``: the key space is partitioned
into ``S`` contiguous range shards (smallest power of two whose per-shard
tile fits, see ``auto_shards``; ``shard_state`` converts a monolithic
state once), queries are routed host-free via ``jnp.searchsorted`` on the
shard boundaries, and one ``pallas_call`` streams the per-shard tiles
through VMEM (``core.sharded`` holds the data structure — including the
split/merge rebalancing that moves boundaries at runtime — and the
sharded kernels live in ``foresight_traverse.py``).  ``search_kernel`` on
an over-budget *monolithic* state raises: the old transparent auto-
reshard cached conversions by state identity, which both rebuilt per
updated state and went stale the moment a rebalance moved boundaries.

Query clustering (the scalar-prefetch launch)
---------------------------------------------
The dense sharded grid ``(B // QBLK, S)`` DMAs every shard tile for every
query block — ``pl.when`` skips the compute of unrouted tiles but not the
copy.  ``cluster_queries`` removes that waste: a stable argsort on the
routed shard ids yields contiguous per-shard query segments (plus the
inverse permutation to unsort results bit-identically), so each QBLK block
of sorted lanes straddles only a short run of shards.  The launch becomes
grid ``(B // QBLK, K)`` on ``pltpu.PrefetchScalarGridSpec`` with K = the
max distinct shards any block touches (rounded up to a power of two to
bound recompiles, clamped to S): the prefetched ``block_sids [nblk, K]``
array drives the table-tile ``index_map``, so ONLY the owning tiles are
DMA'd and padding slots coalesce onto the resident tile for free.

DMA cost model: dense moves ``nblk * S * tile_bytes``; clustered moves
``dma_model_tile_loads(block_sids) * tile_bytes`` — the number of
index-map transitions in visit order.  Clustering wins whenever queries
exhibit shard locality (skewed/Zipf routing, sorted key batches): loads
collapses toward S (or 1) independent of batch size.  K must grow toward S
only when a single 128-lane block straddles many shards — uniform routing
with tiny batches — where the clustered grid degenerates to the dense one
and the only overhead left is the argsort.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sharded as shd
from repro.core.skiplist import NULL_VAL, SkipListState
from repro.core.sharded import ShardedSkipList
from repro.kernels.foresight_traverse import (QBLK, VMEM_BUDGET_BYTES,
                                              base_traverse,
                                              base_traverse_clustered,
                                              base_traverse_sharded,
                                              foresight_traverse,
                                              foresight_traverse_clustered,
                                              foresight_traverse_sharded,
                                              interpret_mode, tile_bytes)
from repro.kernels.ref import encode_float_keys

MAX_SHARDS = shd.MAX_SHARDS            # one ceiling, shared with core.sharded


class KernelSearchResult(NamedTuple):
    found: jax.Array   # [B] bool
    vals: jax.Array    # [B] int32
    node: jax.Array    # [B] int32 — shard-local id composed as sid*cap + node
                       #             on the sharded path (shard-global); fat
                       #             layouts use element-flat ids with stride
                       #             cap * node_width


def _pad(q: jax.Array) -> Tuple[jax.Array, int]:
    B = q.shape[0]
    pad = (-B) % QBLK
    if pad:
        q = jnp.concatenate([q, jnp.zeros((pad,), q.dtype)])
    return q, B


def vmem_footprint(state: Union[SkipListState, ShardedSkipList]) -> int:
    """Bytes the (per-shard) index tile occupies in VMEM."""
    if isinstance(state, ShardedSkipList):
        return shard_vmem_footprint(state.levels, state.shard_capacity,
                                    state.foresight, state.node_width)
    return shard_vmem_footprint(state.levels, state.capacity,
                                state.foresight, state.node_width)


def fits_vmem(state: Union[SkipListState, ShardedSkipList]) -> bool:
    return vmem_footprint(state) <= VMEM_BUDGET_BYTES


def shard_vmem_footprint(levels: int, capacity: int, foresight: bool,
                         node_width: int = 1) -> int:
    return tile_bytes(levels, capacity, foresight, node_width)


def auto_shards(n: int, levels: int, foresight: bool = True,
                node_width: int = 1) -> int:
    """Smallest power-of-two shard count whose per-shard tile fits VMEM."""
    s = 1
    while s <= MAX_SHARDS:
        cap = shd.shard_capacity_for(n, s, node_width)
        if shard_vmem_footprint(levels, cap, foresight,
                                node_width) <= VMEM_BUDGET_BYTES:
            return s
        s *= 2
    raise ValueError(f"index with n={n}, levels={levels} cannot be sharded "
                     f"into <= {MAX_SHARDS} VMEM-sized tiles")


@functools.partial(jax.jit, static_argnames=("n_shards",))
def shard_state(state: SkipListState, n_shards: int) -> ShardedSkipList:
    """Convert a monolithic skiplist into ``n_shards`` key-range shards.

    Live keys are recovered in sorted order from the SoA key array (unused
    and deleted slots hold KEY_MAX, the head KEY_MIN, so one argsort + a
    prefix mask of length ``state.n`` suffices) and re-bulk-built.  Node ids
    are NOT preserved — found/vals are; callers that key on node ids must
    stay on the single-tile path.  This is a full rebuild: callers serving
    a big index repeatedly should build a ``ShardedSkipList`` once (e.g.
    ``IndexedSampleStore(n_shards=...)``) instead of converting per call.
    """
    from repro.core.skiplist import sorted_live_kv
    if state.node_width > 1:
        # fat layout: element-sorted keys come from the run arrays (the
        # routing keys in state.keys are only per-node minima)
        keys_sorted, vals_sorted = sorted_live_kv(state)
        valid = jnp.arange(keys_sorted.shape[0]) < state.n
        return shd.build_sharded(keys_sorted, vals_sorted,
                                 n_shards=n_shards, levels=state.levels,
                                 foresight=state.foresight, valid=valid,
                                 node_width=state.node_width)
    cap = state.capacity
    m_total = cap - 2                              # static live-count bound
    order = jnp.argsort(state.keys)                # [cap]; head first
    keys_sorted = state.keys[order][1:m_total + 1]
    vals_sorted = state.vals[order][1:m_total + 1]
    valid = jnp.arange(m_total) < state.n
    return shd.build_sharded(keys_sorted, vals_sorted, n_shards=n_shards,
                             levels=state.levels,
                             foresight=state.foresight, valid=valid)


# ---------------------------------------------------------------------------
# Query clustering: shard-sort the batch so each block touches 1-2 tiles
# ---------------------------------------------------------------------------

class ClusterPlan(NamedTuple):
    """Shard-sorted launch plan for the scalar-prefetch clustered kernels."""

    q_sorted: jax.Array     # [Bp] queries in shard-sorted order
    sid_sorted: jax.Array   # [Bp] matching shard ids (non-decreasing)
    inv: jax.Array          # [Bp] inverse permutation: sorted -> original
    block_sids: jax.Array   # [nblk, K] k-th distinct shard of each block
    ndist: jax.Array        # [nblk] distinct-shard count per block


def cluster_queries(boundaries: jax.Array, q_padded: jax.Array, *,
                    k_shards: int = 0) -> ClusterPlan:
    """Build the clustered launch plan for a padded query batch.

    A stable argsort on the routed shard id makes per-shard query segments
    contiguous, so QBLK-lane blocks straddle only adjacent shards; the
    inverse permutation restores the original order bit-identically.
    ``block_sids[j, k]`` names block j's k-th distinct shard; slots past
    ``ndist[j]`` repeat the block's last shard so the kernel's table-tile
    index_map re-selects the resident tile (a coalesced, DMA-free step).

    ``k_shards=0`` auto-sizes K to the max distinct-shard count of any
    block, rounded up to a power of two (bounds jit recompiles to log2
    variants) and clamped to S.  Auto-sizing concretizes that count, so
    call this OUTSIDE jit (as ``search_kernel_sharded`` does) or pass an
    explicit ``k_shards``.
    """
    S = boundaries.shape[0]
    Bp = q_padded.shape[0]
    assert Bp % QBLK == 0, "pad queries to a multiple of QBLK first"
    nblk = Bp // QBLK
    sid = shd.route(boundaries, q_padded)
    perm = jnp.argsort(sid, stable=True)
    q_sorted = q_padded[perm]
    sid_sorted = sid[perm]
    inv = jnp.argsort(perm)

    sid_blk = sid_sorted.reshape(nblk, QBLK)
    # first lane of each within-block run of equal shard ids
    first = jnp.concatenate(
        [jnp.ones((nblk, 1), jnp.bool_), sid_blk[:, 1:] != sid_blk[:, :-1]],
        axis=1)
    slot = jnp.cumsum(first, axis=1) - 1             # distinct-run index
    ndist = (slot[:, -1] + 1).astype(jnp.int32)
    if k_shards == 0:
        kmax = int(jnp.max(ndist))  # trace-ok: eager auto-K only; traced callers pass k_shards
        K = 1 << (kmax - 1).bit_length() if kmax > 1 else 1
        K = min(K, S)
    else:
        K = k_shards
        try:   # an undersized explicit K would silently drop lanes
            widest = int(jnp.max(ndist))  # trace-ok: eager-only width check, guarded below
        except jax.errors.ConcretizationTypeError:  # trace-ok: documented dual-mode — traced caller vouches for K
            widest = None                # traced: caller vouches for K
        if widest is not None and K < widest:
            # explicit raise (not assert): must survive python -O
            raise ValueError(f"k_shards={K} < widest block's {widest} "
                             "shards — lanes would be dropped")
    if K < 1:
        raise ValueError(f"k_shards={K} must be >= 1")
    rows = jnp.broadcast_to(jnp.arange(nblk)[:, None], (nblk, QBLK))
    block_sids = jnp.zeros((nblk, K), jnp.int32)
    block_sids = block_sids.at[rows, jnp.minimum(slot, K - 1)].set(sid_blk)
    # padding slots repeat the last distinct shard -> coalesced re-select
    block_sids = jnp.where(jnp.arange(K)[None, :] < ndist[:, None],
                           block_sids, sid_blk[:, -1:])
    return ClusterPlan(q_sorted, sid_sorted, inv, block_sids, ndist)


def plan_degeneration_split(ndist, n_shards: int):  # trace-ok: eager auto-K planning only — the caller guards on isinstance(ndist, Tracer)
    """Split a clustered plan's blocks into a small-K set and stragglers.

    The auto-sized K is the max distinct-shard count over ALL blocks, so
    ONE straggler block (a sparse Zipf tail straddling every shard) snaps
    the whole grid back to the dense ``(nblk, S)`` size — the clustered
    launch degenerates even though every other block touches 1-2 shards.
    This planner picks the power-of-two ``k < K`` minimizing the grid-step
    cost ``n_keep * k + n_straggler * S`` (a straggler block runs through
    the dense grid, whose per-block cost is ``S``); when no ``k`` beats
    the single clustered launch it returns ``None``.

    Returns ``None`` or ``(k_small, keep_rows, straggler_rows)`` with the
    row index arrays concrete (host) — eager auto-K planning only, which
    is exactly where the degeneration bites (an explicit static
    ``k_shards`` already caps the grid by contract).
    """
    nd = np.asarray(ndist)
    nblk = int(nd.size)
    if nblk == 0:
        return None
    kmax = int(nd.max())
    k_full = min(1 << (kmax - 1).bit_length() if kmax > 1 else 1, n_shards)
    best_cost = nblk * k_full
    best = None
    k = 1
    while k < k_full:
        strag = nd > k
        n_s = int(strag.sum())
        cost = (nblk - n_s) * k + n_s * n_shards
        if cost < best_cost:
            best_cost = cost
            best = (k, np.flatnonzero(~strag), np.flatnonzero(strag))
        k <<= 1
    return best


def dma_model_tile_loads(block_sids: jax.Array) -> int:
    """Tiles DMA'd by the clustered launch under revisited-tile coalescing.

    The grid visits ``block_sids`` row-major (K minor); a step whose tile
    index equals the previous step's reuses the resident tile.  Loads =
    transitions + 1.  The dense grid's analogue is ``nblk * S``.
    """
    seq = np.asarray(block_sids).reshape(-1)
    if seq.size == 0:
        return 0
    return 1 + int(np.sum(seq[1:] != seq[:-1]))


def dma_model_bytes(shl: ShardedSkipList, n_queries: int,
                    block_sids=None) -> int:
    """Modeled HBM->VMEM index-tile traffic for one sharded search call.

    ``block_sids=None`` models the dense ``(nblk, S)`` grid (every tile per
    block); passing a plan's ``block_sids`` models the clustered grid.
    """
    Bp = n_queries + (-n_queries) % QBLK
    nblk = Bp // QBLK
    tile = shard_vmem_footprint(shl.levels, shl.shard_capacity,
                                shl.foresight)
    if block_sids is None:
        return nblk * shl.n_shards * tile
    return dma_model_tile_loads(block_sids) * tile


def _degenerate_launch(shl: ShardedSkipList, plan: ClusterPlan, split, *,
                       max_steps: int, interpret: bool
                       ) -> Tuple[jax.Array, jax.Array]:
    """Dual launch for a degeneration-split plan: clustered small-K grid
    for the keep blocks, dense mini-grid for the straggler blocks.

    Both sub-launches run existing kernels unchanged; results are
    scattered back by block row, so the sorted-order output is
    bit-identical to one full-K clustered launch.  Truncating
    ``block_sids`` to ``k_small`` columns is sound for keep blocks: their
    distinct count fits, and padding slots only repeat the last shard.
    """
    k_small, keep, strag = split
    nblk = plan.block_sids.shape[0]
    qs = plan.q_sorted.reshape(nblk, QBLK)
    ss = plan.sid_sorted.reshape(nblk, QBLK)
    keep_j = jnp.asarray(keep, jnp.int32)
    strag_j = jnp.asarray(strag, jnp.int32)
    node_s = jnp.zeros((nblk, QBLK), jnp.int32)
    ckey_s = jnp.zeros((nblk, QBLK), jnp.int32)

    fatk = shl.shards.fat_keys          # None on the scalar layout
    bs = plan.block_sids[keep_j][:, :k_small]
    nd = plan.ndist[keep_j]
    qk, sk = qs[keep_j].reshape(-1), ss[keep_j].reshape(-1)
    if shl.foresight:
        nk, ck = foresight_traverse_clustered(
            shl.shards.fused, bs, nd, sk, qk, fatk, max_steps=max_steps,
            interpret=interpret)
    else:
        nk, ck = base_traverse_clustered(
            shl.shards.nxt, shl.shards.keys, bs, nd, sk, qk, fatk,
            max_steps=max_steps, interpret=interpret)
    node_s = node_s.at[keep_j].set(nk.reshape(-1, QBLK))
    ckey_s = ckey_s.at[keep_j].set(ck.reshape(-1, QBLK))

    qd, sd = qs[strag_j].reshape(-1), ss[strag_j].reshape(-1)
    if shl.foresight:
        nn, cn = foresight_traverse_sharded(
            shl.shards.fused, sd, qd, fatk, max_steps=max_steps,
            interpret=interpret)
    else:
        nn, cn = base_traverse_sharded(
            shl.shards.nxt, shl.shards.keys, sd, qd, fatk,
            max_steps=max_steps, interpret=interpret)
    node_s = node_s.at[strag_j].set(nn.reshape(-1, QBLK))
    ckey_s = ckey_s.at[strag_j].set(cn.reshape(-1, QBLK))
    return node_s.reshape(-1), ckey_s.reshape(-1)


def search_kernel_sharded(shl: ShardedSkipList, queries: jax.Array, *,
                          max_steps: int = 0, cluster: bool = True,
                          k_shards: int = 0) -> KernelSearchResult:
    """Kernel-backed search over a partitioned index.

    ``cluster=True`` (default) launches the scalar-prefetch clustered grid
    ``(B//QBLK, K)`` — only routed tiles are DMA'd; results are unsorted
    back so the output is bit-identical to ``cluster=False`` (the dense
    ``(B//QBLK, S)`` grid, kept for comparison benchmarks).  Under ``jit``
    the auto-sized K cannot concretize; pass a static ``k_shards`` (an
    upper bound on the distinct shards any 128-lane block straddles —
    ``min(QBLK, S)`` is always safe) to keep the clustered launch inside a
    trace, else the call falls back to the dense launch — correct, just
    without the DMA saving.

    Rebalance-aware: grid, K and the traversal bound are re-derived from
    THIS state's static shapes on every call.  A padded fixed-ceiling
    state (``core.rebalance_traced.pad_shards``) launches with the ceiling
    as its static S; dead shards are never routed to, so the clustered
    path's ``block_sids`` never name them (no DMA) and the dense grid
    skips their compute via ``pl.when`` (their tile copy is the price of
    the dense reference path).

    An UNDERSIZED ``k_shards`` (a block straddles more shards than K)
    raises eagerly (``cluster_queries``'s guard); under tracing that guard
    cannot run, so lanes whose shard was dropped from ``block_sids`` are
    clamped to a signalled MISS (``found=False``, ``NULL_VAL``, node -1)
    — a conservative, detectable outcome, never a fabricated hit against
    the wrong shard tile.  ``min(QBLK, S)`` is always a sufficient K.
    """
    if not fits_vmem(shl):
        raise ValueError(
            "search_kernel_sharded: per-shard tile exceeds the VMEM budget "
            f"({vmem_footprint(shl)} > {VMEM_BUDGET_BYTES} bytes); build "
            "with more shards (auto_shards picks the smallest fitting "
            "count) or repack(shl, n_shards=...) the existing index")
    interpret = interpret_mode()
    q, B = _pad(queries.astype(jnp.int32))
    if cluster:
        try:
            plan = cluster_queries(shl.boundaries, q,
                                   k_shards=min(k_shards, shl.n_shards))
        except jax.errors.ConcretizationTypeError:  # trace-ok: documented dual-mode dispatch, dense grid is bit-identical
            cluster = False              # traced batch, no static K: dense
    if cluster:
        split = None
        if k_shards == 0 and not isinstance(plan.ndist, jax.core.Tracer):
            # eager auto-K: one straggler block must not snap K (and the
            # grid) back to the dense size for every other block
            split = plan_degeneration_split(plan.ndist, shl.n_shards)
        if split is not None:
            node, ckey = _degenerate_launch(shl, plan, split,
                                            max_steps=max_steps,
                                            interpret=interpret)
        elif shl.foresight:
            node, ckey = foresight_traverse_clustered(
                shl.shards.fused, plan.block_sids, plan.ndist,
                plan.sid_sorted, plan.q_sorted, shl.shards.fat_keys,
                max_steps=max_steps, interpret=interpret)
        else:
            node, ckey = base_traverse_clustered(
                shl.shards.nxt, shl.shards.keys, plan.block_sids,
                plan.ndist, plan.sid_sorted, plan.q_sorted,
                shl.shards.fat_keys, max_steps=max_steps,
                interpret=interpret)
        node, ckey = node[plan.inv], ckey[plan.inv]   # unsort: bit-identical
        sid = plan.sid_sorted[plan.inv]
        if isinstance(plan.ndist, jax.core.Tracer):
            # traced explicit-K launch: cluster_queries' sufficiency guard
            # could not run, so an undersized K silently drops shards from
            # block_sids and those lanes' outputs are the k==0 init
            # garbage.  A lane is served iff its shard made a slot; clamp
            # the rest to a signalled miss.  (Eager plans skip this: the
            # guard already proved every lane served.)
            nblk, K = plan.block_sids.shape
            sid_blk = plan.sid_sorted.reshape(nblk, QBLK)
            served = jnp.any(
                sid_blk[:, :, None] == plan.block_sids[:, None, :],
                axis=-1).reshape(-1)[plan.inv]
        else:
            served = jnp.ones_like(q, jnp.bool_)
    else:
        sid = shd.route(shl.boundaries, q)
        served = jnp.ones_like(q, jnp.bool_)
        if shl.foresight:
            node, ckey = foresight_traverse_sharded(
                shl.shards.fused, sid, q, shl.shards.fat_keys,
                max_steps=max_steps, interpret=interpret)
        else:
            node, ckey = base_traverse_sharded(
                shl.shards.nxt, shl.shards.keys, sid, q,
                shl.shards.fat_keys, max_steps=max_steps,
                interpret=interpret)
    node, ckey, sid = node[:B], ckey[:B], sid[:B]
    served = served[:B]
    found = (ckey == queries.astype(jnp.int32)) & served
    nw = shl.node_width
    if nw > 1:
        # fat: kernels return ELEMENT-flat ids (owner * nw + pos), so the
        # shard-global stride is the element capacity cap * nw
        flat_vals = shl.shards.fat_vals.reshape(-1)
        gnode = jnp.where(served, sid * (shl.shard_capacity * nw) + node, -1)
    else:
        flat_vals = shl.shards.vals.reshape(-1)
        gnode = jnp.where(served, sid * shl.shard_capacity + node, -1)
    vals = jnp.where(found, jnp.take(flat_vals, jnp.maximum(gnode, 0)),
                     NULL_VAL)
    return KernelSearchResult(found, vals, gnode)


def search_kernel(state: Union[SkipListState, ShardedSkipList],
                  queries: jax.Array, *, max_steps: int = 0,
                  cluster: bool = True, k_shards: int = 0,
                  mesh=None) -> KernelSearchResult:
    """Kernel-backed batched search on any variant; resolves found/vals.

    Auto-dispatch: a ``MeshShardedIndex`` takes the mesh-distributed path
    (``mesh`` required — the 1-D index mesh the state was partitioned
    for); a ``ShardedSkipList`` takes the sharded key-space path; a
    monolithic state takes the single-tile kernel and must fit the VMEM
    budget.  The historical oversized-monolith auto-reshard (an identity-
    keyed conversion cache plus a ``DeprecationWarning``) is gone: it
    rebuilt the whole partition on every new state object, and rebalancing
    now changes boundaries underneath any such cache — callers hold a
    ``ShardedSkipList`` directly instead (``shard_state`` converts once;
    ``core.sharded.build_sharded`` builds one from scratch).

    Kernels compile on a TPU and run interpreted on the CPU backend
    (``interpret_mode``); any other backend raises.
    """
    from repro.core.mesh_index import MeshShardedIndex
    if isinstance(state, MeshShardedIndex):
        if mesh is None:
            raise ValueError("search_kernel on a MeshShardedIndex needs "
                             "mesh= (see launch.mesh.make_index_mesh)")
        from repro.kernels.mesh_launch import search_kernel_mesh
        return search_kernel_mesh(state, queries, max_steps=max_steps,
                                  k_shards=k_shards, mesh=mesh)
    if isinstance(state, ShardedSkipList):
        return search_kernel_sharded(state, queries, max_steps=max_steps,
                                     cluster=cluster, k_shards=k_shards)
    if not fits_vmem(state):
        raise ValueError(
            "search_kernel: monolithic table exceeds the VMEM budget "
            f"({vmem_footprint(state)} > {VMEM_BUDGET_BYTES} bytes); hold a "
            "ShardedSkipList instead (kernels.ops.shard_state converts a "
            "monolithic state once; core.sharded.build_sharded builds one)")
    interpret = interpret_mode()
    q, B = _pad(queries.astype(jnp.int32))
    if state.foresight:
        node, ckey = foresight_traverse(state.fused, q, state.fat_keys,
                                        max_steps=max_steps,
                                        interpret=interpret)
    else:
        node, ckey = base_traverse(state.nxt, state.keys, q, state.fat_keys,
                                   max_steps=max_steps, interpret=interpret)
    node, ckey = node[:B], ckey[:B]
    found = ckey == queries.astype(jnp.int32)
    if state.node_width > 1:   # fat: node is element-flat into the runs
        vals = jnp.where(found, jnp.take(state.fat_vals.reshape(-1), node),
                         NULL_VAL)
    else:
        vals = jnp.where(found, jnp.take(state.vals, node), NULL_VAL)
    return KernelSearchResult(found, vals, node)


def search_kernel_float(state: Union[SkipListState, ShardedSkipList],
                        float_queries: jax.Array, *, max_steps: int = 0,
                        cluster: bool = True,
                        k_shards: int = 0) -> KernelSearchResult:
    """Float-keyed search (keys must have been encoded at build time)."""
    return search_kernel(state, encode_float_keys(float_queries),
                         max_steps=max_steps, cluster=cluster,
                         k_shards=k_shards)
