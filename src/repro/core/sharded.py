"""Sharded key-space skiplist — the index-larger-than-VMEM scaling path.

A single fused table tops out at ``VMEM_BUDGET_BYTES`` (~12 MiB per TPU
core, see ``kernels/ops.py``): ``levels * capacity * 2 * 4`` bytes for the
foresight variant.  Past that the single-tile Pallas kernel cannot pin the
index, so we partition the *key space* into ``S`` contiguous ranges — the
locality move of the B-Skiplist (2025) and the tiering move of the
skiplist-based LSM tree (2018) — and keep one independent ``SkipListState``
per range, each sized so its table fits a per-grid-step VMEM tile.

Layout
------
* ``shards``: one stacked ``SkipListState`` whose every leaf carries a
  leading ``[S]`` axis (``fused`` becomes ``[S, L, cap, 2]``, …).  The
  stacked form is what makes the Pallas shard-grid dimension a plain
  BlockSpec index (``lambda j, s: (s, 0, 0, 0)``) and lets host-side ops
  ``vmap`` over shards.
* ``boundaries``: ``[S]`` int32, ``boundaries[s]`` = smallest key of shard
  ``s`` (``boundaries[0]`` pinned to ``KEY_MIN``).  Shard ``s`` owns keys in
  ``[boundaries[s], boundaries[s+1])``; this invariant is preserved by
  routed inserts/deletes, so the flat array stays valid without rebuilds.

Routing is host-free: ``jnp.searchsorted(boundaries, q, side='right') - 1``
— one vectorized binary search over ``S`` int32s, negligible next to a
traversal.  VMEM-budget math: for ``n`` keys over ``S`` shards each shard
holds ``m = ceil(n / S)`` keys with capacity ``cap_s = pow2ceil(2 m + 4)``,
so the per-shard fused tile is ``L * cap_s * 8`` bytes; the builder picks
the smallest power-of-two ``S`` that brings that under the budget.

Empty shards (possible when ``n`` is not a multiple of ``S``) hold only the
two sentinels; their boundary degenerates to ``KEY_MAX`` so routing never
selects them, and cross-shard range scans walk straight through them.

Rebalancing (split / merge / repack)
------------------------------------
Boundaries are no longer frozen at build time.  ``split_shard`` divides one
shard at a key (default: its median) into two, ``merge_shards`` folds two
adjacent shards into one, ``repack`` rebuilds every boundary from observed
occupancy in one pass, and ``rebalance`` is the B-Skiplist-style watermark
driver over all three.  The rebalancing invariants, preserved by every one
of these operations (and checkable via ``check_sharded_invariant``):

* ``boundaries`` stays a flat, non-decreasing int32 array with
  ``boundaries[0] == KEY_MIN`` — so ``route`` / ``cluster_queries`` /
  ``shard_segments`` work unchanged on any rebalanced state;
* every live key stays inside its shard's ``[boundaries[s],
  boundaries[s+1])`` range;
* the live key/value *contents* are exactly preserved (``total_n`` is
  conserved; only the partition and the resampled tower heights change),
  so searches and scans are bit-identical before and after;
* ``shard_capacity`` and ``levels`` are constant — splits grow total
  capacity by adding shards, merges shrink it — so per-shard tiles keep
  fitting the same VMEM budget and ``build``'s compiled trace is reused.

Watermark semantics (fractions of the usable per-shard capacity,
``shard_capacity - 2``): a shard above ``high_water`` is split at its
median until none remain; two adjacent shards merge when their combined
occupancy fits under ``high_water`` and at least one of them sits below
``low_water``.  ``high_water > 0.5`` is required so a split's halves land
strictly below the high mark (no split/merge ping-pong).

Rebalancing runs in BOTH execution regimes.  ``apply_ops_sharded(...,
rebalance=True)`` guards capacity *before* applying (splitting ahead of any
shard the routed inserts would exhaust — linearization is untouched because
contents never change) and re-levels watermarks after.  Eagerly, the passes
here concretize occupancy on the host and grow/shrink the shard axis.
Under ``jit`` tracing, the call dispatches to ``core.rebalance_traced``:
the state must carry a static ``max_shards`` ceiling (``pad_shards`` /
``empty_sharded`` built at the ceiling — dead slots are masked by
degenerate ``KEY_MAX`` boundaries and zero live keys), and splits/merges
become in-place boundary/content edits on that fixed shape, so the whole
serving loop compiles ONCE at the ceiling no matter how many shards come
and go.  Nothing degrades silently: an eager host-pass failure warns (and
falls back to fixed boundaries for that batch), an untraceable traced
configuration raises at trace time (no exception is swallowed), and
capacity exhaustion at a full ceiling stays per-op SIGNALLED (result
flag 0) — the observable insert-failure contract, not a hidden one.

The segment-scoped batch scan survives tracing the same way: segment
widths that cannot concretize switch to a count-then-dispatch multi-pass
window loop (see ``apply_ops_sharded``) instead of the old dense ``S x B``
fallback, so traced callers keep the segment saving.
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.skiplist import (HEAD, KEY_MAX, KEY_MIN, NULL_VAL,
                                 OP_INSERT, OP_READ, SkipListState,
                                 apply_ops, build,
                                 check_foresight_invariant,
                                 effective_top_level, node_slots_for,
                                 sorted_live_kv, usable_capacity)


class ShardedSkipList(NamedTuple):
    """``S`` independent key-range shards + the flat routing array."""

    shards: SkipListState    # stacked pytree — every leaf has leading [S]
    boundaries: jax.Array    # [S] int32 — inclusive lower key bound per shard

    @property
    def n_shards(self) -> int:
        return self.boundaries.shape[0]

    @property
    def levels(self) -> int:
        arr = self.shards.nxt if self.shards.nxt is not None else self.shards.fused
        return arr.shape[1]

    @property
    def shard_capacity(self) -> int:
        return self.shards.keys.shape[1]

    @property
    def foresight(self) -> bool:
        return self.shards.fused is not None

    @property
    def node_width(self) -> int:
        return self.shards.node_width


def route(boundaries: jax.Array, queries: jax.Array) -> jax.Array:
    """Shard id per query: the shard whose key range contains it."""
    sid = jnp.searchsorted(boundaries, queries.astype(jnp.int32),
                           side="right") - 1
    return jnp.clip(sid, 0, boundaries.shape[0] - 1).astype(jnp.int32)


def shard_capacity_for(n: int, n_shards: int, node_width: int = 1) -> int:
    """Per-shard capacity for ``n`` total keys (2x headroom, pow2, +sentinels).

    Under a fat layout, capacity counts NODE slots: ``m`` keys pack into
    ``node_slots_for(m, node_width)`` half-full runs (the per-node slack
    that replaces the scalar layout's tail headroom), so the same element
    count needs a ``~node_width/2``-fold smaller table.
    """
    m = max(1, -(-n // n_shards))
    if node_width > 1:
        # node slots, with the same deliberate 2x headroom: skewed inserts
        # split full runs, and each split spends one free node slot
        m = node_slots_for(m, node_width)
    return max(8, 1 << (2 * m + 4 - 1).bit_length())


def partition_boundaries(sorted_keys: jax.Array, stride: int) -> jax.Array:
    """Boundary vector of a stride partition over padded sorted keys.

    ``sorted_keys`` must be non-decreasing with dead slots padded to
    ``KEY_MAX`` as a suffix; slice ``p`` owns ``sorted_keys[p*stride :
    (p+1)*stride]``.  Returns ``[len // stride]`` int32 lower bounds with
    slot 0 pinned to ``KEY_MIN`` (the first slice owns ``(-inf, b[1])``)
    and all-dead slices degenerating to ``KEY_MAX`` so routing never
    selects them.  This is the ONE partition rule shared by the per-shard
    boundaries of ``build_sharded`` and the per-device boundary vector of
    ``core.mesh_index`` — both layers route with the same
    ``searchsorted`` over a vector produced here.
    """
    b = sorted_keys[::stride].astype(jnp.int32)
    return b.at[0].set(KEY_MIN)


@functools.partial(jax.jit, static_argnames=("n_shards", "capacity", "levels",
                                             "foresight", "node_width"))
def build_sharded(keys: jax.Array, vals: jax.Array, *, n_shards: int,
                  capacity: int = 0, levels: int = 16, foresight: bool = True,
                  seed: int = 0, valid: Optional[jax.Array] = None,
                  node_width: int = 1) -> ShardedSkipList:
    """Partition sorted unique int32 ``keys`` into ``n_shards`` range shards.

    ``valid`` (optional prefix mask) supports callers with a dynamic live
    count (see ``kernels.ops.shard_state``); invalid positions must be a
    suffix and are forced to ``KEY_MAX`` padding.  ``node_width`` > 1
    builds every shard in the fat-node layout (``capacity`` then counts
    per-shard NODE slots, see ``core.skiplist``).
    """
    n = keys.shape[0]
    S = n_shards
    if capacity == 0:
        capacity = shard_capacity_for(n, S, node_width)
    # keys per shard (ceil); >= 1 so an empty build still pads every shard
    # to one invalid slot and the stride-m boundary slice stays well formed
    m = max(1, -(-n // S))
    if node_width > 1:
        assert node_slots_for(m, node_width) + 2 <= capacity, \
            "shard capacity must hold keys-per-shard packed into runs"
    else:
        assert m + 2 <= capacity, \
            "shard capacity must exceed keys-per-shard + 2"

    keys = keys.astype(jnp.int32)
    vals = vals.astype(jnp.int32)
    if valid is None:
        valid = jnp.ones((n,), jnp.bool_)
    keys = jnp.where(valid, keys, KEY_MAX)
    pad = S * m - n
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), KEY_MAX, jnp.int32)])
        vals = jnp.concatenate([vals, jnp.full((pad,), NULL_VAL, jnp.int32)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.bool_)])

    states = []
    for s in range(S):
        sk = keys[s * m:(s + 1) * m]
        sv = vals[s * m:(s + 1) * m]
        sm = valid[s * m:(s + 1) * m]
        states.append(build(sk, sv, capacity=capacity, levels=levels,
                            foresight=foresight, seed=seed + s, valid=sm,
                            node_width=node_width))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    # first key of each shard; shard 0 owns (-inf, b1)
    boundaries = partition_boundaries(keys, m)
    return ShardedSkipList(shards=stacked, boundaries=boundaries)


def empty_sharded(*, n_shards: int, capacity: int, levels: int = 16,
                  foresight: bool = True, seed: int = 0,
                  node_width: int = 1) -> ShardedSkipList:
    """An empty partitioned index (each shard holds only the sentinels).

    All but shard 0's boundary degenerate to ``KEY_MAX``, so every insert
    initially routes to shard 0; with ``apply_ops_sharded(...,
    rebalance=True)`` splits then carve out real boundaries as it fills —
    the growth path for callers that start from nothing (e.g. the paged
    KV page table).  Built at ``n_shards = max_shards`` this is exactly
    the padded fixed-shape state the traced rebalancer needs (every spare
    shard is a spendable split slot), so a ``jit``-wrapped apply loop
    compiles once at the ceiling — see ``core.rebalance_traced``.
    """
    z = jnp.zeros((0,), jnp.int32)
    return build_sharded(z, z, n_shards=n_shards, capacity=capacity,
                         levels=levels, foresight=foresight, seed=seed,
                         node_width=node_width)


# ---------------------------------------------------------------------------
# Batched search across shards (host-free routing + flat-gather traversal)
# ---------------------------------------------------------------------------

def _effective_tops(shl: ShardedSkipList) -> jax.Array:
    """[S] — per-shard highest populated level (+1 slack)."""
    return jax.vmap(effective_top_level)(shl.shards)


def search_sharded(shl: ShardedSkipList, queries: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """Batched lookup across the whole partitioned index: (found, vals).

    Each lane traverses only its own shard: the stacked tables are viewed as
    one flat array and every gather is offset by ``sid * L * cap`` — the
    same lock-step loop as ``skiplist.search_fast``, generalized by one
    index term.  No host round-trip anywhere.
    """
    return _search_sharded(shl, queries, count=False)


def search_sharded_counted(shl: ShardedSkipList, queries: jax.Array
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``search_sharded`` that also counts its loop: (found, vals, steps),
    ``steps`` per lane as ``skiplist.search_fast_counted`` counts them.
    Each lane starts at its own shard's effective top level."""
    return _search_sharded(shl, queries, count=True)


@jax.named_scope("search_sharded")
def _search_sharded(shl: ShardedSkipList, queries: jax.Array, *,
                    count: bool) -> tuple:
    """The loop of both; with ``count`` it also carries each lane's steps,
    returned last, as ``skiplist._search_fast`` does."""
    q = queries.astype(jnp.int32)
    B = q.shape[0]
    L, cap = shl.levels, shl.shard_capacity
    sid = route(shl.boundaries, q)
    x = jnp.zeros((B,), jnp.int32)
    lvl = jnp.take(_effective_tops(shl), sid)

    if shl.foresight:
        flat = shl.shards.fused.reshape((-1, 2))
        def gather(lv, xx):
            rec = jnp.take(flat, (sid * L + lv) * cap + xx, axis=0)
            return rec[..., 0], rec[..., 1]
    else:
        flat_nxt = shl.shards.nxt.reshape(-1)
        flat_keys = shl.shards.keys.reshape(-1)
        def gather(lv, xx):
            ptr = jnp.take(flat_nxt, (sid * L + lv) * cap + xx, axis=0)
            return ptr, jnp.take(flat_keys, sid * cap + ptr, axis=0)

    def cond(carry):
        return jnp.any(carry[1] >= 0)

    def body(carry):
        x, lvl = carry[:2]
        active = lvl >= 0
        ptr, fk = gather(jnp.maximum(lvl, 0), x)
        go = active & (fk < q)
        out = (jnp.where(go, ptr, x), jnp.where(go | ~active, lvl, lvl - 1))
        if count:
            out += (lax.add(carry[2],
                            lax.convert_element_type(active, jnp.int32)),)
        return out

    carry = lax.while_loop(cond, body, (x, lvl, x) if count else (x, lvl))
    x, steps = carry[0], carry[2:]
    cand, ck = gather(jnp.zeros((B,), jnp.int32), x)
    nw = shl.node_width
    if nw > 1:
        # fat postlude: one tile gather over the owning run + lane compare
        # (the host-side twin of the kernels' _fat_resolve)
        owner = jnp.where((ck == q) | (x == HEAD), cand, x)
        base = (sid * cap + owner) * nw
        run = jnp.take(shl.shards.fat_keys.reshape(-1),
                       base[:, None] + jnp.arange(nw)[None, :], axis=0)
        pos = jnp.sum((run < q[:, None]).astype(jnp.int32), axis=1)
        pos_c = jnp.minimum(pos, nw - 1)
        hit = jnp.take_along_axis(run, pos_c[:, None], axis=1)[:, 0]
        found = (pos < nw) & (hit == q)
        vals = jnp.where(found,
                         jnp.take(shl.shards.fat_vals.reshape(-1),
                                  base + pos_c), NULL_VAL)
        return (found, vals) + steps
    found = ck == q
    flat_vals = shl.shards.vals.reshape(-1)
    vals = jnp.where(found, jnp.take(flat_vals, sid * cap + cand), NULL_VAL)
    return (found, vals) + steps


def contains_sharded(shl: ShardedSkipList, queries: jax.Array) -> jax.Array:
    return search_sharded(shl, queries)[0]


# ---------------------------------------------------------------------------
# Cross-shard range scan: route lo, walk level 0, spill into successors
# ---------------------------------------------------------------------------

def range_scan_sharded(shl: ShardedSkipList, lo: jax.Array, hi: jax.Array,
                       max_out: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Collect up to ``max_out`` (key, val) pairs with lo <= key < hi.

    Routes ``lo`` to its owning shard, positions via that shard's
    predecessor search, then walks level 0.  Hitting a shard's tail
    (foreseen key == KEY_MAX) *spills* into the successor shard's head —
    range boundaries are invisible to the caller.  Runs ``max_out + S``
    iterations: each spill consumes one non-emitting step.
    """
    from repro.core import skiplist as sl

    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)
    S = shl.n_shards
    L, cap = shl.levels, shl.shard_capacity
    s0 = route(shl.boundaries, lo[None])[0]
    shard0 = jax.tree.map(lambda a: a[s0], shl.shards)
    x = sl.search(shard0, lo[None]).preds[0, 0]   # level-0 predecessor of lo
    if shl.node_width > 1:            # fat: (shard, node, lane) cursor walk
        return _fat_range_scan_sharded(shl, lo, hi, max_out, s0, x)

    if shl.foresight:
        flat = shl.shards.fused.reshape((-1, 2))
        def gather0(sid, xx):
            rec = flat[(sid * L + 0) * cap + xx]
            return rec[0], rec[1]
    else:
        flat_nxt = shl.shards.nxt.reshape(-1)
        flat_keys = shl.shards.keys.reshape(-1)
        def gather0(sid, xx):
            ptr = flat_nxt[(sid * L + 0) * cap + xx]
            return ptr, flat_keys[sid * cap + ptr]

    keys_out = jnp.full((max_out,), KEY_MAX, jnp.int32)
    vals_out = jnp.full((max_out,), NULL_VAL, jnp.int32)
    flat_vals = shl.shards.vals.reshape(-1)

    def body(_, carry):
        sid, x, keys_out, vals_out, count = carry
        ptr, k = gather0(sid, x)
        at_end = k == KEY_MAX                     # shard exhausted (or empty)
        spill = at_end & (sid < S - 1)
        take = ~at_end & (k >= lo) & (k < hi) & (count < max_out)
        slot = jnp.minimum(count, max_out - 1)
        keys_out = keys_out.at[slot].set(jnp.where(take, k, keys_out[slot]))
        vals_out = vals_out.at[slot].set(
            jnp.where(take, flat_vals[sid * cap + ptr], vals_out[slot]))
        count = count + jnp.where(take, 1, 0).astype(jnp.int32)
        new_sid = jnp.where(spill, sid + 1, sid)
        new_x = jnp.where(spill, jnp.int32(HEAD),
                          jnp.where(take, ptr, x))  # stop advancing past hi
        return new_sid, new_x, keys_out, vals_out, count

    _, _, keys_out, vals_out, count = lax.fori_loop(
        0, max_out + S, body,
        (s0, x, keys_out, vals_out, jnp.int32(0)))
    return keys_out, vals_out, count


def _fat_range_scan_sharded(shl: ShardedSkipList, lo, hi, max_out: int,
                            s0, x0) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Cross-shard scan over fat runs: a (shard, node, lane) cursor walk.

    The level-0 walk of ``range_scan_sharded`` generalized one axis: the
    cursor advances lane-by-lane inside the current node's run, hops to the
    level-0 successor at the run's KEY_MAX padding, and when the successor
    is the tail (foreseen min == KEY_MAX) *spills* into the next shard's
    head — shard boundaries stay invisible.  Iteration bound adds one hop
    per visited node and two steps per empty spilled shard.
    """
    S = shl.n_shards
    L, cap = shl.levels, shl.shard_capacity
    nw = shl.node_width
    flat_fk = shl.shards.fat_keys.reshape(-1)
    flat_fv = shl.shards.fat_vals.reshape(-1)
    if shl.foresight:
        flat = shl.shards.fused.reshape((-1, 2))
        def gather0(sid, xx):
            rec = flat[(sid * L + 0) * cap + xx]
            return rec[0], rec[1]
    else:
        flat_nxt = shl.shards.nxt.reshape(-1)
        flat_keys = shl.shards.keys.reshape(-1)
        def gather0(sid, xx):
            ptr = flat_nxt[(sid * L + 0) * cap + xx]
            return ptr, flat_keys[sid * cap + ptr]

    keys_out = jnp.full((max_out,), KEY_MAX, jnp.int32)
    vals_out = jnp.full((max_out,), NULL_VAL, jnp.int32)
    bound = 2 * max_out + nw + 2 * S + 4

    def body(_, carry):
        sid, node, lane, keys_out, vals_out, count, done = carry
        lane_c = jnp.minimum(lane, nw - 1)
        flat_at = (sid * cap + node) * nw + lane_c
        k = flat_fk[flat_at]
        v = flat_fv[flat_at]
        ptr, pk = gather0(sid, node)
        at_end = (k == KEY_MAX) | (lane >= nw)    # run exhausted
        succ_tail = pk == KEY_MAX                 # level-0 successor is tail
        spill = at_end & succ_tail & (sid < S - 1) & ~done
        hop = at_end & ~succ_tail & ~done
        # last shard's tail, or a LIVE lane at/past hi (padding must hop)
        stop = (at_end & succ_tail & (sid >= S - 1)) | (~at_end & (k >= hi))
        take = ~done & ~at_end & (k >= lo) & (k < hi) & (count < max_out)
        idx = jnp.minimum(count, max_out - 1)
        keys_out = keys_out.at[idx].set(jnp.where(take, k, keys_out[idx]))
        vals_out = vals_out.at[idx].set(jnp.where(take, v, vals_out[idx]))
        count = count + jnp.where(take, 1, 0).astype(jnp.int32)
        done = done | stop | (count >= max_out)
        new_sid = jnp.where(spill, sid + 1, sid)
        new_node = jnp.where(spill, jnp.int32(HEAD),
                             jnp.where(hop, ptr, node))
        new_lane = jnp.where(spill | hop, 0,
                             jnp.where(done, lane, lane + 1))
        return new_sid, new_node, new_lane, keys_out, vals_out, count, done

    _, _, _, keys_out, vals_out, count, _ = lax.fori_loop(
        0, bound, body,
        (s0, x0, jnp.int32(0), keys_out, vals_out, jnp.int32(0),
         jnp.bool_(False)))
    return keys_out, vals_out, count


# ---------------------------------------------------------------------------
# Rebalancing: shard split / merge, watermark driver, one-pass repack
# ---------------------------------------------------------------------------

HIGH_WATER = 0.75       # split a shard above this fraction of usable capacity
LOW_WATER = 0.25        # merge-eligible below this fraction
MAX_SHARDS = 1024       # hard ceiling on split growth


class RebalanceStats(NamedTuple):
    splits: int
    merges: int


def _shard_sorted_kv(shard: SkipListState) -> Tuple[jax.Array, jax.Array]:
    """One shard's live (key, val) pairs in key order, padded to cap - 2.

    Delegates to ``skiplist.sorted_live_kv`` — the fixed-shape compaction
    primitive shared with the traced rebalancer (``core.rebalance_traced``).
    """
    return sorted_live_kv(shard)


def _set_shard_slice(shl: ShardedSkipList, s: int, width: int,
                     replacement: SkipListState, boundaries: jax.Array
                     ) -> ShardedSkipList:
    """Splice ``replacement`` (leading axis = new shard(s)) over shards
    ``[s, s + width)`` of the stacked pytree."""
    new_shards = jax.tree.map(
        lambda full, ins: jnp.concatenate([full[:s], ins, full[s + width:]],
                                          axis=0),
        shl.shards, replacement)
    return ShardedSkipList(shards=new_shards, boundaries=boundaries)


# trace-ok: eager-only host pass (apply_ops_sharded dispatches to rebalance_traced under trace)
def split_shard(shl: ShardedSkipList, s: int,
                at_key: Optional[int] = None, *, seed: int = 0
                ) -> ShardedSkipList:
    """Split shard ``s`` into two at ``at_key`` (default: its median key).

    The left shard keeps keys ``< at_key``, the right keys ``>= at_key``;
    ``at_key`` becomes the right shard's boundary, so it must fall strictly
    inside shard ``s``'s current key range.  Contents are preserved exactly
    (both halves are re-bulk-built at the shared static capacity); only
    tower heights are resampled.  Host-side eager only (occupancy must
    concretize, and the shard axis grows): under ``jit`` use the fixed-
    shape ``rebalance_traced.split_shard_traced`` on a padded state.
    """
    s = int(s)
    S = shl.n_shards
    assert 0 <= s < S
    cap, L, fs = shl.shard_capacity, shl.levels, shl.foresight
    shard = jax.tree.map(lambda a: a[s], shl.shards)
    ks, vs = _shard_sorted_kv(shard)
    n = int(shard.n)
    ks_np = np.asarray(ks)
    if at_key is None:
        if n < 2:
            raise ValueError("cannot median-split a shard with < 2 keys; "
                             "pass an explicit at_key")
        at_key = int(ks_np[n // 2])
    at_key = int(at_key)
    b_np = np.asarray(shl.boundaries)
    hi = int(b_np[s + 1]) if s + 1 < S else int(KEY_MAX)
    if not int(b_np[s]) < at_key < hi:
        raise ValueError(f"at_key={at_key} outside shard {s}'s open range "
                         f"({int(b_np[s])}, {hi})")
    n_left = int((ks_np[:n] < at_key).sum())
    nw = shl.node_width
    # rebuilds repack at build fill, so each half must fit the fill mass
    # (a run-saturated fat shard can exceed it — only near-median cuts
    # are guaranteed feasible there)
    W = usable_capacity(cap, nw)
    if n_left > W or n - n_left > W:
        raise ValueError(f"split halves {n_left}/{n - n_left} exceed the "
                         f"build-fill capacity {W} (node_width={nw})")
    idx = jnp.arange(W)
    left = build(ks[:W], vs[:W], capacity=cap, levels=L, foresight=fs,
                 seed=seed, valid=idx < n_left, node_width=nw)
    right = build(jnp.roll(ks, -n_left)[:W], jnp.roll(vs, -n_left)[:W],
                  capacity=cap, levels=L, foresight=fs, seed=seed + 1,
                  valid=idx < n - n_left, node_width=nw)
    pair = jax.tree.map(lambda a, b: jnp.stack([a, b]), left, right)
    boundaries = jnp.concatenate([shl.boundaries[:s + 1],
                                  jnp.asarray([at_key], jnp.int32),
                                  shl.boundaries[s + 1:]])
    return _set_shard_slice(shl, s, 1, pair, boundaries)


# trace-ok: eager-only host pass (apply_ops_sharded dispatches to rebalance_traced under trace)
def merge_shards(shl: ShardedSkipList, s: int, *, seed: int = 0
                 ) -> ShardedSkipList:
    """Merge adjacent shards ``s`` and ``s + 1`` into one.

    Their combined live count must fit the shared static capacity
    (``n_a + n_b + 2 <= shard_capacity``); key ranges are adjacent and
    disjoint, so concatenating the two sorted live runs is already sorted.
    Host-side eager only (the shard axis shrinks): under ``jit`` use
    ``rebalance_traced.merge_shards_traced``.
    """
    s = int(s)
    S = shl.n_shards
    assert 0 <= s < S - 1, "merge needs a right-hand neighbour"
    cap, L, fs = shl.shard_capacity, shl.levels, shl.foresight
    a = jax.tree.map(lambda x: x[s], shl.shards)
    b = jax.tree.map(lambda x: x[s + 1], shl.shards)
    ka, va = _shard_sorted_kv(a)
    kb, vb = _shard_sorted_kv(b)
    na, nb = int(a.n), int(b.n)
    nw = shl.node_width
    if node_slots_for(na + nb, nw) + 2 > cap:
        raise ValueError(f"merged occupancy {na}+{nb} exceeds shard "
                         f"capacity {cap} (node_width={nw})")
    width = usable_capacity(cap, nw)  # rebuild repacks at build fill
    pad = width - na - nb
    ks = jnp.concatenate([ka[:na], kb[:nb],
                          jnp.full((pad,), KEY_MAX, jnp.int32)])
    vs = jnp.concatenate([va[:na], vb[:nb],
                          jnp.full((pad,), NULL_VAL, jnp.int32)])
    merged = build(ks, vs, capacity=cap, levels=L, foresight=fs, seed=seed,
                   valid=jnp.arange(width) < na + nb, node_width=nw)
    one = jax.tree.map(lambda x: x[None], merged)
    boundaries = jnp.concatenate([shl.boundaries[:s + 1],
                                  shl.boundaries[s + 2:]])
    return _set_shard_slice(shl, s, 2, one, boundaries)


def repack(shl: ShardedSkipList, n_shards: int = 0, *, seed: int = 0
           ) -> ShardedSkipList:
    """Rebuild every boundary from observed occupancy in ONE pass.

    Gathers all live keys in global sorted order (one argsort over the
    stacked key arrays — the ``S`` head sentinels sort first, dead slots
    last) and re-partitions them evenly into ``n_shards`` (default: keep
    the current count) at the same static per-shard capacity.  This is the
    amortized counterpart of incremental split/merge: after heavy skew it
    equalizes occupancy to within one key across shards.  Host-side eager
    only (by design, even after the traced rebalancer: a full re-partition
    is the amortization point where a host round-trip is already paid).
    """
    S = shl.n_shards
    S2 = int(n_shards) or S
    cap, L, fs = shl.shard_capacity, shl.levels, shl.foresight
    nw = shl.node_width
    nn = int(total_n(shl))
    if node_slots_for(-(-max(1, nn) // S2), nw) + 2 > cap:
        raise ValueError(f"{nn} keys over {S2} shards exceed per-shard "
                         f"capacity {cap} (node_width={nw})")
    if nw > 1:
        # fat lanes sort directly: sentinel rows are all KEY_MAX (no
        # KEY_MIN head lane exists), so live elements lead the order
        order = jnp.argsort(shl.shards.fat_keys.reshape(-1))
        ks = shl.shards.fat_keys.reshape(-1)[order][:nn]
        vs = shl.shards.fat_vals.reshape(-1)[order][:nn]
    else:
        order = jnp.argsort(shl.shards.keys.reshape(-1))
        ks = shl.shards.keys.reshape(-1)[order][S:S + nn]
        vs = shl.shards.vals.reshape(-1)[order][S:S + nn]
    return build_sharded(ks, vs, n_shards=S2, capacity=cap, levels=L,
                         foresight=fs, seed=seed, node_width=nw)


def validate_watermarks(high_water: float, low_water: float) -> None:
    """Shared public-kwarg validation (explicit raises: survive python -O)
    for the eager AND traced watermark drivers — one accepted range."""
    if not 0.5 < high_water <= 1.0:
        raise ValueError(f"high_water={high_water} must be in (0.5, 1.0] "
                         "(split halves must land below the high mark)")
    if not 0.0 < low_water < high_water:
        raise ValueError(f"low_water={low_water} must be in "
                         f"(0, high_water={high_water})")


# trace-ok: eager-only dispatch predicate (guarded by _is_tracing at the call site)
def _has_static_ceiling(shl: ShardedSkipList) -> bool:
    """Concrete check: does this (eager) state carry dead ceiling slots?

    A dead last slot (``KEY_MAX`` boundary, see ``rebalance_traced``)
    marks a padded fixed-shape state whose rebalancing must stay in place
    — the shape-changing host drivers would destroy the ceiling.  Forces
    a device readback; call only on rebalancing paths.  The ceiling is
    carried ONLY by this suffix: a padded state whose every slot has gone
    live is indistinguishable from a built-at-``S`` state and eager
    rebalancing may resume changing its shape (see ``apply_ops_sharded``).
    """
    return shl.n_shards > 1 and int(shl.boundaries[-1]) == int(KEY_MAX)


# trace-ok: eager-only host pass (apply_ops_sharded dispatches to rebalance_traced under trace)
def _watermark_rebalance(shl: ShardedSkipList, *, high_water: float,
                         low_water: float, max_shards: int, seed: int = 0
                         ) -> Tuple[ShardedSkipList, RebalanceStats]:
    """Split every shard above ``high_water``, then merge underfull
    neighbours.  See the module docstring for the watermark semantics and
    the termination argument (``high_water > 0.5`` keeps split halves
    below the high mark; merges only form shards below it)."""
    validate_watermarks(high_water, low_water)
    usable = usable_capacity(shl.shard_capacity, shl.node_width)
    splits = merges = 0
    while shl.n_shards < max_shards:
        ns = np.asarray(shl.shards.n)
        over = np.flatnonzero(ns > high_water * usable)
        if over.size == 0:
            break
        s = int(over[np.argmax(ns[over])])
        if ns[s] < 2:
            break
        shl = split_shard(shl, s, seed=seed + splits)
        splits += 1
    while shl.n_shards > 1:
        ns = np.asarray(shl.shards.n)
        b = np.asarray(shl.boundaries)
        comb = ns[:-1] + ns[1:]
        # dead ceiling slots (KEY_MAX boundary, see rebalance_traced) are
        # split headroom, not merge fodder: folding them away would strip
        # a padded state's static ceiling
        ok = (b[1:] < int(KEY_MAX)) & (comb <= high_water * usable) & \
             ((ns[:-1] < low_water * usable) | (ns[1:] < low_water * usable))
        cand = np.flatnonzero(ok)
        if cand.size == 0:
            break
        s = int(cand[np.argmin(comb[cand])])
        shl = merge_shards(shl, s, seed=seed + merges)
        merges += 1
    return shl, RebalanceStats(splits, merges)


def rebalance(shl: ShardedSkipList, *, high_water: float = HIGH_WATER,
              low_water: float = LOW_WATER, max_shards: int = MAX_SHARDS,
              seed: int = 0) -> Tuple[ShardedSkipList, RebalanceStats]:
    """Watermark-driven split/merge pass; returns (new_state, stats).

    Contents are exactly preserved; only the partition changes.  Callers
    treat the index functionally, so the returned ``ShardedSkipList``
    simply replaces the old one (any cached launch plan built against the
    OLD boundaries — e.g. a ``ClusterPlan`` — is stale and must be
    rebuilt; ``kernels.ops.search_kernel_sharded`` replans per call).

    A state carrying a static ceiling (dead ``KEY_MAX``-boundary last
    slot, see ``rebalance_traced``) — or any traced state — re-levels via
    the fixed-shape in-place driver, preserving the ceiling; only a fully
    live eager state uses the shape-changing host loop.
    """
    if _is_tracing(shl) or _has_static_ceiling(shl):
        from repro.core import rebalance_traced as rbt
        return rbt.watermark_rebalance_traced(
            shl, high_water=high_water, low_water=low_water,
            max_shards=max_shards, seed=seed)
    return _watermark_rebalance(shl, high_water=high_water,
                                low_water=low_water, max_shards=max_shards,
                                seed=seed)


# trace-ok: eager-only host pass (apply_ops_sharded dispatches to rebalance_traced under trace)
def _exhaustion_guard(shl: ShardedSkipList, op_types: jax.Array,
                      keys: jax.Array, *, max_shards: int, seed: int = 0
                      ) -> Tuple[ShardedSkipList, int]:
    """Split ahead of any shard the routed inserts of this batch would
    exhaust, so no insert fails on shard capacity that a rebalance could
    have provided.

    Projects per-shard occupancy as ``n_s + (# distinct NEW keys routed to
    s)`` — exact, because upserts of present keys do not grow ``n`` — and
    splits the worst offender at the median of its combined (live +
    incoming) key multiset until every projection fits or the keys are
    indivisible (then the normal signalled-failure contract applies).
    Contents never change, so linearization of the following apply is
    untouched.
    """
    usable = usable_capacity(shl.shard_capacity, shl.node_width)
    ins = np.asarray(op_types) == OP_INSERT
    if not ins.any():
        return shl, 0
    ins_keys = np.unique(np.asarray(keys)[ins]).astype(np.int32)
    # conservative projection first — every insert counted as new; only if
    # some shard could exceed does the exact (presence-filtered) pass pay
    # for a whole-index search to discount upserts
    sid0 = np.asarray(route(shl.boundaries, jnp.asarray(ins_keys)))
    ns0 = np.asarray(shl.shards.n)
    bound = ns0 + np.bincount(sid0, minlength=shl.n_shards)[:ns0.size]
    if not (bound > usable).any():
        return shl, 0
    present = np.asarray(search_sharded(shl, jnp.asarray(ins_keys))[0])
    new_keys = ins_keys[~present]
    splits = 0
    while new_keys.size and shl.n_shards < max_shards:
        sid = np.asarray(route(shl.boundaries, jnp.asarray(new_keys)))
        ns = np.asarray(shl.shards.n)
        proj = ns + np.bincount(sid, minlength=shl.n_shards)[:ns.size]
        over = np.flatnonzero(proj > usable)
        if over.size == 0:
            break
        s = int(over[np.argmax(proj[over])])
        shard = jax.tree.map(lambda a: a[s], shl.shards)
        live = np.asarray(_shard_sorted_kv(shard)[0])[:int(shard.n)]
        combined = np.sort(np.concatenate([live, new_keys[sid == s]]))
        at = int(combined[combined.size // 2])
        if at == int(combined[0]):                 # median won't separate
            bigger = combined[combined > combined[0]]
            if bigger.size == 0:                   # indivisible key mass
                break
            at = int(bigger[0])
        shl = split_shard(shl, s, at_key=at, seed=seed + splits)
        splits += 1
    return shl, splits


# ---------------------------------------------------------------------------
# Routed batched updates (the functional concurrency model, per shard)
# ---------------------------------------------------------------------------

def shard_segments(sid_sorted: jax.Array, n_shards: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """Per-shard ``[start, start+len)`` bounds of a shard-sorted array.

    ``sid_sorted`` must be non-decreasing (the stable route-sort order);
    empty shards get a zero-length segment at their insertion point.
    """
    s = jnp.arange(n_shards, dtype=jnp.int32)
    starts = jnp.searchsorted(sid_sorted, s, side="left").astype(jnp.int32)
    ends = jnp.searchsorted(sid_sorted, s, side="right").astype(jnp.int32)
    return starts, ends - starts


def _is_tracing(*trees) -> bool:
    """True when any leaf of any argument is a JAX tracer."""
    return any(isinstance(leaf, jax.core.Tracer)
               for t in trees for leaf in jax.tree.leaves(t))


def _segment_window(W: int) -> int:
    """Round a window width up to a power of two (>= 8).

    Positions past a segment's length are masked to no-op reads anyway,
    and pow2 windows bound the distinct (S, W) traces of the vmapped scan
    to log2(B) variants.
    """
    return max(8, 1 << (W - 1).bit_length())


def apply_ops_sharded(shl: ShardedSkipList, op_types: jax.Array,
                      keys: jax.Array, vals: jax.Array, *,
                      rebalance: bool = False,
                      high_water: float = HIGH_WATER,
                      low_water: float = LOW_WATER,
                      max_shards: int = MAX_SHARDS,
                      max_segment: int = 0,
                      seed=0
                      ) -> Tuple[ShardedSkipList, jax.Array]:
    """Apply a linearized mixed-op batch, routed per shard.

    Segment-scoped scan: the batch is stably sorted by routed shard id, so
    each shard's ops form one contiguous ``[start, start+len)`` segment
    (``shard_segments``); every shard then scans only a ``W``-wide window
    (``W`` = the longest segment) sliced at its own start, with positions
    past its length masked to no-op reads.  Total scan work is ``S * W``
    ops — ~``B`` when routing is balanced — instead of the dense ``S * B``.
    Linearization is preserved: shards hold disjoint key ranges, so only
    the relative order WITHIN a shard is observable, and the stable sort
    keeps it; results are unsorted back via the inverse permutation, so the
    outcome is bit-identical to the monolithic ``apply_ops``.

    The scan runs as a count-then-dispatch in BOTH regimes
    (``_apply_segment_passes``): phase one routes and counts, phase two
    sweeps each segment in ``max_segment``-wide passes via a
    ``lax.while_loop`` whose trip count is ``ceil(widest / max_segment)``.
    Eagerly the widest segment concretizes and one pass covers it; under
    ``jit`` it cannot, so the static window (``max_segment`` hint, default
    ``2 * ceil(B / S)`` rounded to a power of two) bounds each pass and
    the traced trip count tracks the widest segment — work is
    ``S * max_segment`` per pass, NOT the dense ``S * B`` of the removed
    fallback, and one shared implementation makes eager-vs-jit bit
    identity hold by construction.

    Capacity caveat: each shard has a FIXED capacity, so a key-skewed insert
    stream can exhaust one shard while others have room — those inserts
    return 0 (the same signalled-failure contract as monolithic capacity
    exhaustion, but reached earlier under skew).  ``rebalance=True`` removes
    that early failure: a pre-pass splits ahead of any shard this batch's
    routed inserts would exhaust (``_exhaustion_guard``; contents are
    untouched, so linearization and results stay bit-identical to the
    monolithic ``apply_ops`` given sufficient total capacity), and a post-
    pass re-levels the watermarks (splitting overfull shards, merging
    underfull neighbours) for the batches to come.  Eagerly those passes
    run on the host and grow/shrink the shard axis (up to ``max_shards``);
    under tracing they dispatch to ``core.rebalance_traced`` and edit the
    fixed-shape state in place — the state's static shard axis is the
    ceiling, so traced callers needing growth headroom must pad first
    (``rebalance_traced.pad_shards`` or an ``empty_sharded`` built at the
    ceiling).  Nothing degrades silently: an eager host-pass failure
    warns (then applies with fixed boundaries), an untraceable traced
    configuration raises at trace time, and inserts that exhaust a FULL
    ceiling stay per-op signalled (result 0) like any capacity failure.
    Note the ceiling is represented only by the dead-slot suffix: once
    every slot is live a padded state is indistinguishable from a
    built-at-``S`` one, so a later *eager* rebalance may legitimately
    grow/shrink the axis again (a jitted apply never can — shapes are
    static inside the trace; the next eager→jit handoff simply retraces
    once at the new shape).  ``seed`` feeds the tower resampling of every
    guard/watermark split and merge (eager and traced), so differently-
    seeded streams grow different tower layouts.
    """
    op_types = op_types.astype(jnp.int32)
    keys = keys.astype(jnp.int32)
    vals = vals.astype(jnp.int32)
    traced = _is_tracing(shl, op_types, keys, vals, seed)
    in_place = False
    if rebalance:
        # A padded fixed-shape state rebalances in place even EAGERLY: the
        # host drivers would grow the axis past the ceiling (guard) and
        # merge the padding away (watermark), silently destroying the
        # one-trace contract of the next jitted call.  (Checked only under
        # rebalance: _has_static_ceiling is a device readback.)
        in_place = traced or _has_static_ceiling(shl)
        if in_place:
            from repro.core import rebalance_traced as rbt
            with jax.named_scope("exhaustion_guard"):
                shl, _ = rbt.exhaustion_guard_traced(
                    shl, op_types, keys, max_shards=max_shards, seed=seed)
        else:
            try:
                shl, _ = _exhaustion_guard(shl, op_types, keys,
                                           max_shards=max_shards, seed=seed)
            except jax.errors.JAXTypeError as e:
                warnings.warn(
                    "apply_ops_sharded(rebalance=True): the eager host "
                    f"rebalance passes are unavailable here ({e!r}); "
                    "falling back to FIXED boundaries for this batch — "
                    "skewed inserts may fail on shard capacity",
                    RuntimeWarning, stacklevel=2)
                rebalance = False
    S = shl.n_shards
    B = keys.shape[0]
    sid = route(shl.boundaries, keys)
    perm = jnp.argsort(sid, stable=True)
    sid_s = sid[perm]
    starts, lens = shard_segments(sid_s, S)
    if B == 0:
        return shl, jnp.zeros((B,), jnp.int32)
    if not traced and not max_segment:
        # eager default: concretize the widest segment so the pass loop
        # dispatches in ONE window (>= 1: segment lengths sum to B > 0)
        max_segment = int(jnp.max(lens))  # trace-ok: eager branch only (traced callers hit the static-window path)
    with jax.named_scope("segment_passes"):
        out, results = _apply_segment_passes(shl, op_types, keys, vals,
                                             perm, starts, lens,
                                             max_segment=max_segment)
    if rebalance:
        if in_place:
            with jax.named_scope("rebalance"):
                out, _ = rbt.watermark_rebalance_traced(
                    out, high_water=high_water, low_water=low_water,
                    max_shards=max_shards, seed=seed)
        else:
            out, _ = _watermark_rebalance(out, high_water=high_water,
                                          low_water=low_water,
                                          max_shards=max_shards, seed=seed)
    return out, results


def default_segment_window(batch: int, n_shards: int) -> int:
    """Auto ``max_segment`` hint: twice the balanced-routing segment width
    (``ceil(B / S)``), pow2-rounded — one pass when routing is within 2x of
    balanced, graceful multi-pass degradation under skew."""
    return min(max(1, batch), _segment_window(2 * (-(-batch // n_shards))))


def _apply_segment_passes(shl: ShardedSkipList, op_types: jax.Array,
                          keys: jax.Array, vals: jax.Array,
                          perm: jax.Array, starts: jax.Array,
                          lens: jax.Array, *, max_segment: int = 0
                          ) -> Tuple[ShardedSkipList, jax.Array]:
    """Count-then-dispatch segment scan (the ONLY batch-scan path, eager
    and traced — eager-vs-jit bit-identity holds by construction).

    Phase one already happened in the caller: routing, the stable sort and
    the per-shard ``[start, start+len)`` segments.  Phase two sweeps every
    segment in static ``W``-wide windows: pass ``p`` has shard ``s`` scan
    ``[starts[s] + p*W, ... + W)`` with positions past its segment length
    masked to no-op reads (which touch neither state nor RNG, so the
    windowing is unobservable), and the ``lax.while_loop`` runs
    ``ceil(max(lens) / W)`` passes — a traced trip count, so one trace
    serves every skew.  Eager calls concretize the widest segment as ``W``
    and dispatch in a single pass.
    """
    S = shl.n_shards
    B = keys.shape[0]
    W = int(max_segment) or default_segment_window(B, S)  # trace-ok: max_segment is a static python knob, never traced
    W = min(B, _segment_window(W))
    maxlen = jnp.max(lens)
    # pad the sorted batch by W no-op reads; windows with any live lane
    # start at < B, so they never clamp (all-dead windows may, harmlessly)
    ops_p = jnp.concatenate([op_types[perm],
                             jnp.full((W,), OP_READ, jnp.int32)])
    keys_p = jnp.concatenate([keys[perm], jnp.zeros((W,), jnp.int32)])
    vals_p = jnp.concatenate([vals[perm], jnp.zeros((W,), jnp.int32)])

    def cond(carry):
        _, _, p = carry
        return p * W < maxlen

    def body(carry):
        shards, res_sorted, p = carry
        off = starts + p * W

        def window(start, ln):
            o = lax.dynamic_slice(ops_p, (start,), (W,))
            k = lax.dynamic_slice(keys_p, (start,), (W,))
            v = lax.dynamic_slice(vals_p, (start,), (W,))
            valid = p * W + jnp.arange(W) < ln
            return jnp.where(valid, o, OP_READ), k, v, valid

        ops_w, keys_w, vals_w, valid_w = jax.vmap(window)(off, lens)
        shards, res_w = jax.vmap(apply_ops)(shards, ops_w, keys_w, vals_w)
        gpos = off[:, None] + jnp.arange(W)[None, :]
        res_sorted = res_sorted.at[jnp.where(valid_w, gpos, B)].set(
            res_w, mode="drop")
        return shards, res_sorted, p + 1

    shards, res_sorted, _ = lax.while_loop(
        cond, body, (shl.shards, jnp.zeros((B,), jnp.int32), jnp.int32(0)))
    results = res_sorted[jnp.argsort(perm)]
    return shl._replace(shards=shards), results


# ---------------------------------------------------------------------------
# Invariants / introspection
# ---------------------------------------------------------------------------

def check_sharded_invariant(shl: ShardedSkipList,
                            expect_n=None) -> jax.Array:
    """Foresight invariant on every shard + the partition invariants.

    Checks, in order: per-shard foresight records, boundary sortedness
    (non-decreasing with ``boundaries[0] == KEY_MIN`` — the rebalancing
    operations must never produce an unsorted routing array), per-shard
    key-range containment, and — when ``expect_n`` is given — conservation
    of the total live count (split/merge/repack move keys, never drop or
    duplicate them).
    """
    ok = jnp.bool_(True)
    if shl.foresight:
        ok = jnp.all(jax.vmap(check_foresight_invariant)(shl.shards))
    # boundaries stay a flat sorted routing array pinned at KEY_MIN
    b = shl.boundaries
    ok = ok & (b[0] == KEY_MIN) & jnp.all(b[1:] >= b[:-1])
    # every live key sits inside its shard's [boundaries[s], boundaries[s+1])
    keys = shl.shards.keys                                  # [S, cap]
    live = (keys != KEY_MAX) & (keys != KEY_MIN)
    lo_b = b[:, None]
    hi_b = jnp.concatenate([b[1:],
                            jnp.full((1,), KEY_MAX, jnp.int32)])[:, None]
    # degenerate (empty-shard) boundaries hold KEY_MAX; live keys never do
    in_range = jnp.where(live, (keys >= lo_b) & (keys < hi_b), True)
    ok = ok & jnp.all(in_range)
    if expect_n is not None:
        ok = ok & (total_n(shl) == jnp.asarray(expect_n, jnp.int32))
    return ok


def total_n(shl: ShardedSkipList) -> jax.Array:
    return jnp.sum(shl.shards.n)
