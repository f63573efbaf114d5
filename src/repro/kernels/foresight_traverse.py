"""Pallas TPU kernels for batched skiplist traversal.

TPU-native rethink of the paper's mechanism (DESIGN.md §7):

* The fused index table is pinned in **VMEM** via an explicit BlockSpec (one
  block covering the table — index tiles are sized to the VMEM budget; larger
  indexes shard the key space across grid rows, see ``ops.py``).
* Queries are processed in **lane-vector blocks** of ``QBLK`` (the VPU's
  128-lane registers play the role of the paper's threads).
* The traversal loop is **level-synchronous**: each iteration every live lane
  either advances right or descends.  The foresight kernel issues ONE
  dependent VMEM gather per iteration (the fused ``(ptr, key)`` record —
  pair-atomic by layout, the MOVDQA analogue); the base kernel issues TWO
  chained gathers (pointer, then pointee key).  Halving the dependent-gather
  chain is exactly the paper's cache-miss saving, expressed in the
  HBM→VMEM→VREG hierarchy.
* ``max_steps`` is a static SAFETY bound (lock-step traversals are
  wait-free: at most ``levels + total-advances`` iterations).  The loop
  itself is an early-exit ``lax.while_loop`` — it stops the moment every
  lane has settled (``lvl < 0``), so the bound is only a never-paid
  ceiling, not the iteration count.  ``traversal_bound`` derives the
  default per tile from levels + occupancy (advances strictly increase
  the predecessor key, so ``capacity - 2`` bounds them exactly); see its
  docstring for why this cannot truncate where the old ``4*levels + 16``
  heuristic theoretically could.

Sharded grids (index > VMEM) come in two flavors:

* ``*_traverse_sharded`` — grid ``(B // QBLK, S)``: every shard tile is
  DMA'd HBM→VMEM for every query block; tiles with no routed lanes skip the
  *compute* via ``pl.when`` but still pay the copy.  Kept as the dense
  reference path (and for un-clustered callers).
* ``*_traverse_clustered`` — grid ``(B // QBLK, K)`` on
  ``pltpu.PrefetchScalarGridSpec``: the caller sorts queries by shard id
  (``ops.cluster_queries``) and prefetches a per-block shard-assignment
  array ``block_sids [nblk, K]``; the table-tile ``index_map`` reads that
  scalar ref, so only the tiles a block actually needs are DMA'd.  Slots
  past a block's distinct-shard count repeat the previous shard id —
  Pallas coalesces revisited tiles (same block index on consecutive grid
  steps ⇒ no copy), so padding slots are free, as is the common case where
  consecutive blocks share a shard.

DMA cost model (see also ``ops.py``): the dense sharded grid moves
``nblk * S * tile_bytes``; the clustered grid moves ``loads * tile_bytes``
where ``loads`` counts index-map *transitions* in the flattened
``block_sids`` visit order — under query locality (Zipf routing, sorted
batches) ``loads`` approaches ``S`` or even 1, independent of ``nblk``.
Clustering wins whenever queries cluster (loads << nblk*S); the static K
must grow toward S only when single blocks straddle many shards (uniform
routing at small batch), where the clustered grid degenerates to the dense
one and nothing is lost but the argsort.

Rebalancing (``core.sharded.split_shard`` / ``merge_shards`` / ``repack``)
changes the shard count S between launches.  Every wrapper therefore
re-derives its grid, K, and ``traversal_bound`` from the shapes of the
state it is handed on THAT call — S from the stacked table's leading axis,
the step ceiling from ``levels``/``capacity`` — never from constants baked
at first launch.  A ``ClusterPlan`` is only valid against the boundary
array it was built from; the clustered wrappers statically reject a plan
whose K exceeds the current S (the cheap detectable half of staleness —
``ops.search_kernel_sharded`` replans per call so callers never hold one
across a rebalance).  Each distinct S compiles its own kernel; eager
splits move S by ±1, so an eager rebalance burst costs a handful of
(small) retraces.  States padded to a static ceiling
(``core.rebalance_traced.pad_shards`` — the traced-rebalance
representation) keep S pinned at that ceiling instead, so ONE compiled
kernel serves every split/merge the traced drivers perform.  Masked
(dead) shards are tolerated by construction: routing never emits their
sid, so the dense grid skips their compute via ``pl.when(any(mine))``
(the tile copy remains — dense is the reference path) and the clustered
``block_sids`` never name them at all (no copy either).

Fat-node layout (``node_width`` > 1): every kernel accepts an optional
``fat_keys`` tile (``[cap, B]`` per shard, lane-major sorted runs — see
``core.skiplist``).  The traversal loop is untouched — the skip structure
is built over *nodes*, so ``fused``/``nxt`` keep their shapes and the
routing keys are the per-node run minima.  Only the postlude changes:
instead of reading the level-0 candidate's key, ``_fat_resolve`` issues
ONE more tile gather (the owning node's whole ``node_width`` run into
VREGs) and a lane-wide compare — a vectorized ``searchsorted`` over a
VMEM-resident tile — to land on the element.  One gather therefore
services ``node_width`` comparisons, and because ``capacity`` counts node
slots the whole dependent-gather chain (``traversal_bound``) shrinks
~``node_width/2``-fold for the same element count.

``plan_launch`` is the ONE derivation site for grid geometry and the
step ceiling — every wrapper (and the degeneration split in ``ops.py``)
re-derives its launch from the shapes of the state it is handed on THAT
call, which is the rebalance-safety contract above.

Every wrapper takes a required ``interpret`` flag; the public entry
points (``ops.py``, ``mesh_launch.py``) pass ``interpret_mode()``, which is
true on the CPU backend only.  Kernels are validated interpreted on CPU
(bit-exact against ``ref.py``) and compiled by Mosaic on a TPU; tables
reach them as lane-dense ``[rows, 128]`` planes (see ``_fused_plane``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# +inf key sentinel (core.skiplist.KEY_MAX as a python int: pallas kernels
# reject captured jnp scalars, and a literal folds into the compare)
_KEY_MAX = 2**31 - 1

QBLK = 128     # query lanes per grid step == VPU lane width

# Scoped VMEM each kernel asks Mosaic for.  The default scoped limit
# (16 MiB on v5e) cannot hold a budget-sized index tile twice, and Pallas
# double-buffers a tile whose block index changes across the grid (the
# sharded and clustered launches).  v5e has 128 MiB of VMEM per core.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
VMEM_BUDGET_BYTES = 12 * 1024 * 1024  # index-tile budget (headroom for I/O
                                      # blocks and compiler temporaries)


def tile_bytes(levels: int, capacity: int, foresight: bool,
               node_width: int = 1) -> int:
    """Bytes one skiplist index tile occupies in VMEM.

    foresight: ``levels * capacity`` fused (ptr, key) int32 pairs;
    base: ``levels * capacity`` int32 pointers + ``capacity`` int32 keys.
    Fat layout (``node_width`` > 1) adds the ``fat_keys`` run tile
    (``capacity * node_width`` int32) — ``capacity`` counts NODE slots
    there, so for a fixed element count the skip tables shrink by the
    fill factor while the run tile holds the elements themselves
    (``fat_vals`` never ships to a kernel; values resolve outside).
    The lane-dense planes the kernels take add no padding past a row of
    128, so these are also the bytes Mosaic allocates per tile buffer;
    ``tests/test_tpu_compile.py`` holds the budget to the v5e compiler.
    """
    base = (levels * capacity * 2 * 4 if foresight
            else levels * capacity * 4 + capacity * 4)
    if node_width > 1:
        base += capacity * node_width * 4
    return base


# ---------------------------------------------------------------------------
# Shared lock-step traversal loop (all six kernels; they differ only in the
# gather strategy — ONE fused gather vs TWO chained — and the lane mask)
# ---------------------------------------------------------------------------

def _traverse_loop(q, lanes, gather, *, levels: int, max_steps: int):
    """Run the level-synchronous loop; returns the final predecessors [QBLK].

    ``gather(lvl, x) -> (ptr, foreseen_key)`` embodies the base-vs-foresight
    distinction; ``lanes`` masks out query lanes owned by another shard tile
    (all-true for the monolithic kernels).

    Early exit: the loop is a ``while`` on "any routed lane still live"
    (``lvl >= 0``), capped at ``max_steps``.  Clustered blocks whose shard
    drains quickly stop immediately instead of idling out the static bound.
    Masked-out lanes start settled so they never hold the block open.
    """
    x = jnp.zeros_like(q)
    lvl = jnp.where(lanes, jnp.full_like(q, levels - 1), -1)

    def cond(carry):
        step, _, lvl = carry
        return (step < max_steps) & jnp.any(lvl >= 0)

    def body(carry):
        step, x, lvl = carry
        active = lvl >= 0
        ptr, fk = gather(jnp.maximum(lvl, 0), x)
        go = active & (fk < q)
        x = jnp.where(go, ptr, x)
        lvl = jnp.where(go | ~active, lvl, lvl - 1)
        return step + 1, x, lvl

    _, x, _ = lax.while_loop(cond, body, (jnp.int32(0), x, lvl))
    return x


def traversal_bound(levels: int, capacity: int) -> int:
    """Safety ceiling for the lock-step traversal over a well-formed tile.

    Every loop step either descends (at most ``levels`` of those) or
    advances, and every advance moves the predecessor to a strictly larger
    key — so a tile holding at most ``capacity - 2`` live keys (two slots
    are sentinels) can never need more than ``levels + capacity - 2``
    steps.  Unlike the historical heuristic ``4*levels + 16`` this ceiling
    PROVABLY cannot truncate a search (tall-tower tail cases included);
    and unlike a ``fori_loop`` trip count it is never paid — the
    early-exit while loop stops at the actual path length, typically
    ``levels + O(log n)``.  Per-shard tiles inherit a proportionally
    smaller ceiling through their smaller ``capacity``, which is the
    occupancy-derived tightening the sharded wrappers share.
    """
    return levels + max(2, capacity) - 2 + 16


class LaunchPlan(NamedTuple):
    """Launch geometry shared by every traversal wrapper (and ``ops.py``).

    One derivation site for the grid and the step ceiling so the sharded,
    clustered and fat-node variants cannot drift; all fields come from the
    static shapes of the state handed to THIS call (rebalance safety).
    """
    grid: Tuple[int, ...]
    nblk: int
    max_steps: int


def plan_launch(*, levels: int, capacity: int, batch: int,
                max_steps: int = 0,
                n_shards: Optional[int] = None) -> LaunchPlan:
    """Derive grid and traversal ceiling for one kernel launch.

    ``capacity`` counts NODE slots — under a fat layout that is
    elements/fill, so the derived ``traversal_bound`` (the worst-case
    dependent-gather chain the compiled kernel budgets) shrinks with the
    node width even though the formula is unchanged.  ``n_shards`` adds
    the minor grid axis: the dense shard count S, or the clustered K.
    """
    assert batch % QBLK == 0, "pad queries to a multiple of QBLK"
    nblk = batch // QBLK
    if max_steps == 0:
        max_steps = traversal_bound(levels, capacity)
    grid = (nblk,) if n_shards is None else (nblk, n_shards)
    return LaunchPlan(grid, nblk, max_steps)


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted: on the CPU backend only.

    The ONE place the choice is made.  On a TPU every ``pallas_call`` is
    compiled by Mosaic; any other backend has no kernel path and raises
    rather than silently swapping the chip for the interpreter.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path on backend {backend!r}: "
                       "kernels compile on TPU and run interpreted on CPU")


# ---------------------------------------------------------------------------
# Lane-dense tables and the per-lane row gather
# ---------------------------------------------------------------------------
#
# Mosaic lowers no gather from a large VMEM table (only in-vreg 2-D
# gathers), so every table reaches a kernel as a row-major lane-dense
# PLANE ``[rows, 128]``.  A gather of one element per query lane is then:
# the 128 row indices go VMEM -> SMEM, a scalar loop copies each lane's
# row into a ``[QBLK, 128]`` scratch (one dynamic row load per lane), and
# one transpose + masked sublane sum selects each lane's element
# (``_pick``).  The fused plane (``_fused_plane``) is the TPU's own
# ``(2, 128)`` tiling of ``fused [L, cap, 2]``: per 128-node chunk a row of
# pointers, then the row of their foreseen keys.  Both halves of a record
# sit in the SAME tile, so the foresight step still costs one dependent
# tile gather, the base step two; and the plane is a free reinterpretation
# of the stored table, not a relayout.

ROW = 128                        # lanes per plane row
_UNROLL = 8                      # row loads per row-gather loop iteration


def _plane(a: jax.Array, lead: int = 0) -> jax.Array:
    """``a`` flattened after its first ``lead`` axes into ``[..., rows, 128]``
    (row-major, zero-padded to a whole row)."""
    head = a.shape[:lead]
    flat = a.reshape(head + (-1,))
    pad = (-flat.shape[-1]) % ROW
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * lead + [(0, pad)])
    return flat.reshape(head + (-1, ROW))


def _row_padded(n: int) -> int:
    return n + (-n) % ROW


def _fused_plane(fused: jax.Array, lead: int = 0) -> jax.Array:
    """``fused [..., L, cap, 2]`` as ``[..., L * cap_p // 64, 128]``: for the
    128-node chunk ``c`` of level ``l`` (``cap`` zero-padded to ``cap_p``, a
    multiple of 128), row ``2 * (l * cap_p // 128 + c)`` holds the pointers
    and the next row their foreseen keys.  This is the order the TPU keeps
    the table in (layout ``T(2, 128)``), so no copy is made."""
    *head, L, cap, _ = fused.shape
    cap_p = _row_padded(cap)
    if cap_p > cap:
        fused = jnp.pad(fused, [(0, 0)] * (lead + 1) + [(0, cap_p - cap),
                                                        (0, 0)])
    t = fused.reshape(*head, L, cap_p // ROW, ROW, 2)
    return jnp.swapaxes(t, -1, -2).reshape(*head, -1, ROW)


def _row_scratch():
    """Scratch of one per-lane row gather: row ids in VMEM and SMEM, and
    two ``[QBLK, 128]`` buffers the rows land in."""
    return [pltpu.VMEM((1, QBLK), jnp.int32),
            pltpu.SMEM((1, QBLK), jnp.int32),
            pltpu.VMEM((QBLK, ROW), jnp.int32),
            pltpu.VMEM((QBLK, ROW), jnp.int32)]


def _gather_rows(tab_ref, row, scratch, n: int = 1):
    """ONE dependent gather of ``n`` adjacent rows per lane: returns
    ``[T_0, ...]`` with ``T_k[:, i] = tab[row[i] + k, :]`` (``[128, QBLK]``)."""
    idx_v, idx_s, *bufs = scratch
    idx_v[...] = row
    pltpu.sync_copy(idx_v, idx_s)

    def body(i, carry):
        # unrolled by hand: Mosaic takes no partial ``unroll=`` here
        for u in range(_UNROLL):
            r = i * _UNROLL + u
            src = idx_s[0, r]
            for k in range(n):
                bufs[k][pl.ds(r, 1), :] = tab_ref[pl.ds(src + k, 1), :]
        return carry

    lax.fori_loop(0, QBLK // _UNROLL, body, 0)
    return [b[...].T for b in bufs[:n]]


def _pick(rows_t, col):
    """In-row lane select: lane ``i`` of the result is ``rows_t[col[i], i]``."""
    j = lax.broadcasted_iota(jnp.int32, rows_t.shape, 0)
    return jnp.sum(jnp.where(j == col, rows_t, 0), axis=0, keepdims=True)


def _fused_gather(fused_ref, cap: int, scratch):
    """ONE tile gather per step: the (ptr, key) record, pair-atomic by
    layout (``cap`` is the plane's padded per-level node count)."""

    def gather(lvl, x):
        row = 2 * (lvl * (cap // ROW) + (x >> 7))
        ptr_t, key_t = _gather_rows(fused_ref, row, scratch, 2)  # one tile
        col = x & (ROW - 1)
        return _pick(ptr_t, col), _pick(key_t, col)
    return gather


def _base_gather(nxt_ref, keys_ref, cap: int, scratch):
    """TWO chained row gathers per step: pointer, then pointee key."""

    def gather(lvl, x):
        e = lvl * cap + x
        ptr = _pick(_gather_rows(nxt_ref, e >> 7, scratch)[0], e & (ROW - 1))
        key = _pick(_gather_rows(keys_ref, ptr >> 7, scratch)[0],  # DEPENDENT
                    ptr & (ROW - 1))
        return ptr, key
    return gather


def _fat_resolve(gather, fatk_ref, q, x, node_width: int, scratch):
    """Fat-node postlude: one run gather + lane-wide compare land on the
    element.

    ``x`` is the node-level predecessor; its level-0 successor ``cand`` is
    the candidate node.  The query lives in ``cand``'s run when the
    foreseen min-key equals it exactly (runs carry their minimum as the
    routing key) or when the predecessor is the head sentinel; otherwise
    it lies inside ``x``'s own run.  ONE row gather pulls the owner's
    whole ``node_width`` run (``node_width`` divides 128, so a run never
    straddles a plane row); the in-run ``<`` count is the searchsorted
    position.  Returns an ELEMENT-flat node id (``owner * node_width +
    pos``) and the key at that position (``KEY_MAX`` when the query
    exceeds the whole run) so the caller's ``key == q`` found-test is
    layout-independent.
    """
    cand, ck = gather(jnp.zeros_like(q), x)
    owner = jnp.where((ck == q) | (x == 0), cand, x)
    start = owner * node_width
    rows_t = _gather_rows(fatk_ref, start >> 7, scratch)[0]
    off = start & (ROW - 1)
    j = lax.broadcasted_iota(jnp.int32, rows_t.shape, 0)
    in_run = (j >= off) & (j < off + node_width)
    pos = jnp.sum((in_run & (rows_t < q)).astype(jnp.int32), axis=0,
                  keepdims=True)
    pos_c = jnp.minimum(pos, node_width - 1)
    hit = _pick(rows_t, off + pos_c)
    key = jnp.where(pos < node_width, hit, jnp.int32(_KEY_MAX))
    return start + pos_c, key


def _check_node_width(node_width: int) -> None:
    if ROW % node_width:
        raise ValueError(f"node_width={node_width} must divide {ROW}: a "
                         "kernel gathers a fat run from one plane row")


# ---------------------------------------------------------------------------
# Kernel bodies.  Refs: lane blocks are [1, QBLK]; tables are planes
# [rows, 128]; ``rest`` = ([fat run plane,] node, key, *row-gather scratch)
# ---------------------------------------------------------------------------

def _split_rest(rest, node_width: int):
    io, scratch = list(rest[:-4]), rest[-4:]
    fatk_ref = io.pop(0) if node_width > 1 else None
    node_ref, key_ref = io
    return fatk_ref, node_ref, key_ref, scratch


def _finish(gather, fatk_ref, q, x, node_width: int, scratch):
    """(node, key) of the query's candidate from the final predecessor."""
    if node_width > 1:
        return _fat_resolve(gather, fatk_ref, q, x, node_width, scratch)
    return gather(jnp.zeros_like(q), x)    # level-0 successor = candidate


# Foresight kernel: ONE dependent row gather per lock-step iteration

def _foresight_kernel(q_ref, fused_ref, *rest, levels: int, cap: int,
                      max_steps: int, node_width: int = 1):
    fatk_ref, node_ref, key_ref, scratch = _split_rest(rest, node_width)
    q = q_ref[...]                                   # [1, QBLK] int32
    gather = _fused_gather(fused_ref, cap, scratch)  # fused plane in VMEM
    x = _traverse_loop(q, jnp.ones_like(q, jnp.bool_), gather,
                       levels=levels, max_steps=max_steps)
    node_ref[...], key_ref[...] = _finish(gather, fatk_ref, q, x,
                                          node_width, scratch)


# Base kernel: TWO chained row gathers per lock-step iteration

def _base_kernel(q_ref, nxt_ref, keys_ref, *rest, levels: int, cap: int,
                 max_steps: int, node_width: int = 1):
    fatk_ref, node_ref, key_ref, scratch = _split_rest(rest, node_width)
    q = q_ref[...]
    gather = _base_gather(nxt_ref, keys_ref, cap, scratch)
    x = _traverse_loop(q, jnp.ones_like(q, jnp.bool_), gather,
                       levels=levels, max_steps=max_steps)
    node_ref[...], key_ref[...] = _finish(gather, fatk_ref, q, x,
                                          node_width, scratch)


# ---------------------------------------------------------------------------
# pallas_call plumbing shared by every wrapper
# ---------------------------------------------------------------------------

_LANE_SPEC = pl.BlockSpec((None, 1, QBLK), lambda j, *_: (j, 0, 0))


def _lanes(a: jax.Array) -> jax.Array:
    """[B] -> [B // QBLK, 1, QBLK]: one lane-dense block per grid row."""
    return a.astype(jnp.int32).reshape(-1, 1, QBLK)


def _launch(kernel, grid, in_specs, operands, *, interpret: bool,
            num_scalar_prefetch: int = 0):
    """One ``pallas_call`` returning (node[B], cand_key[B])."""
    nblk = grid[0]
    out = jax.ShapeDtypeStruct((nblk, 1, QBLK), jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=grid,
        in_specs=in_specs, out_specs=[_LANE_SPEC, _LANE_SPEC],
        scratch_shapes=_row_scratch())
    node, key = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret)(*operands)
    return node.reshape(-1), key.reshape(-1)


def _with_fat(in_specs, operands, fat_keys, index_map, lead: int):
    """Append the fat run plane (when present); returns node_width."""
    if fat_keys is None:
        return 1
    nw = fat_keys.shape[-1]
    _check_node_width(nw)
    fat_p = _plane(fat_keys, lead)
    block = ((None,) * lead) + fat_p.shape[lead:]
    in_specs.append(pl.BlockSpec(block, index_map))
    operands.append(fat_p)
    return nw


@functools.partial(jax.jit, static_argnames=("max_steps", "interpret"))
def foresight_traverse(fused: jax.Array, queries: jax.Array,
                       fat_keys: Optional[jax.Array] = None, *,
                       max_steps: int = 0, interpret: bool):
    """Batched foresight search. Returns (node[B], cand_key[B]).

    ``queries`` length must be a multiple of QBLK (ops.py pads).  With
    ``fat_keys [cap, node_width]`` the node id is ELEMENT-flat
    (``owner * node_width + pos``, see ``_fat_resolve``).
    """
    L, cap, _ = fused.shape
    plan = plan_launch(levels=L, capacity=cap, batch=queries.shape[0],
                       max_steps=max_steps)
    tab = _fused_plane(fused)
    in_specs = [_LANE_SPEC, pl.BlockSpec(tab.shape, lambda *_: (0, 0))]
    operands = [_lanes(queries), tab]
    nw = _with_fat(in_specs, operands, fat_keys, lambda *_: (0, 0), 0)
    kernel = functools.partial(_foresight_kernel, levels=L,
                               cap=_row_padded(cap),
                               max_steps=plan.max_steps, node_width=nw)
    return _launch(kernel, plan.grid, in_specs, operands,
                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("max_steps", "interpret"))
def base_traverse(nxt: jax.Array, keys: jax.Array, queries: jax.Array,
                  fat_keys: Optional[jax.Array] = None, *,
                  max_steps: int = 0, interpret: bool):
    """Batched base (no-foresight) search. Returns (node[B], cand_key[B])."""
    L, cap = nxt.shape
    plan = plan_launch(levels=L, capacity=cap, batch=queries.shape[0],
                       max_steps=max_steps)
    nxt_p, keys_p = _plane(nxt), _plane(keys)
    in_specs = [_LANE_SPEC, pl.BlockSpec(nxt_p.shape, lambda *_: (0, 0)),
                pl.BlockSpec(keys_p.shape, lambda *_: (0, 0))]
    operands = [_lanes(queries), nxt_p, keys_p]
    nw = _with_fat(in_specs, operands, fat_keys, lambda *_: (0, 0), 0)
    kernel = functools.partial(_base_kernel, levels=L, cap=cap,
                               max_steps=plan.max_steps, node_width=nw)
    return _launch(kernel, plan.grid, in_specs, operands,
                   interpret=interpret)


# ---------------------------------------------------------------------------
# Sharded kernels: grid (B // QBLK, S) — the key space streams tile by tile
# ---------------------------------------------------------------------------
#
# One pallas_call serves the whole partitioned index.  The shard axis is the
# MINOR grid dimension, so for a fixed query block the S shard tiles are
# visited consecutively and the output block stays resident in VMEM across
# them (the standard revisited-block accumulation pattern): we initialize at
# s == 0 and each shard masks in the lanes it owns (sid == s).  BlockSpec
# ``lambda j, s: (s, 0, 0)`` pins exactly one per-shard table plane —
# sized under VMEM_BUDGET_BYTES by the builder — per grid step, which is
# precisely the sharded key-space path the module docstring promises.
# Shard tiles with no routed lanes skip the traversal loop via pl.when.

def _serve_mine(mine, first, live, node_ref, key_ref, search):
    """Masked revisited-block update shared by the sharded kernels: zero
    the outputs at the first minor step, then (when ``live``) write
    ``search()``'s results into the lanes this tile owns."""

    @pl.when(first)
    def _init():
        node_ref[...] = jnp.zeros_like(mine, jnp.int32)
        key_ref[...] = jnp.zeros_like(mine, jnp.int32)

    @pl.when(live)
    def _traverse():
        node, key = search()
        node_ref[...] = jnp.where(mine, node, node_ref[...])
        key_ref[...] = jnp.where(mine, key, key_ref[...])


def _foresight_sharded_kernel(q_ref, sid_ref, fused_ref, *rest,
                              levels: int, cap: int, max_steps: int,
                              node_width: int = 1):
    fatk_ref, node_ref, key_ref, scratch = _split_rest(rest, node_width)
    s = pl.program_id(1)
    q = q_ref[...]                                   # [1, QBLK] int32
    mine = sid_ref[...] == s                         # lanes routed to tile s

    def search():
        gather = _fused_gather(fused_ref, cap, scratch)
        x = _traverse_loop(q, mine, gather, levels=levels,
                           max_steps=max_steps)
        return _finish(gather, fatk_ref, q, x, node_width, scratch)

    _serve_mine(mine, s == 0, jnp.any(mine), node_ref, key_ref, search)


def _base_sharded_kernel(q_ref, sid_ref, nxt_ref, keys_ref, *rest,
                         levels: int, cap: int, max_steps: int,
                         node_width: int = 1):
    fatk_ref, node_ref, key_ref, scratch = _split_rest(rest, node_width)
    s = pl.program_id(1)
    q = q_ref[...]
    mine = sid_ref[...] == s

    def search():
        gather = _base_gather(nxt_ref, keys_ref, cap, scratch)
        x = _traverse_loop(q, mine, gather, levels=levels,
                           max_steps=max_steps)
        return _finish(gather, fatk_ref, q, x, node_width, scratch)

    _serve_mine(mine, s == 0, jnp.any(mine), node_ref, key_ref, search)


@functools.partial(jax.jit, static_argnames=("max_steps", "interpret"))
def foresight_traverse_sharded(fused: jax.Array, shard_ids: jax.Array,
                               queries: jax.Array,
                               fat_keys: Optional[jax.Array] = None, *,
                               max_steps: int = 0, interpret: bool):
    """Sharded foresight search over stacked tables ``fused [S, L, cap, 2]``.

    ``shard_ids [B]`` routes each (padded) query lane to its key-range shard
    (see ``core.sharded.route``).  Returns (node[B], cand_key[B]) with node
    ids local to the owning shard (element-flat under ``fat_keys
    [S, cap, node_width]``).
    """
    S, L, cap, _ = fused.shape
    plan = plan_launch(levels=L, capacity=cap, batch=queries.shape[0],
                       max_steps=max_steps, n_shards=S)
    tab = _fused_plane(fused, 1)
    tile = lambda j, s: (s, 0, 0)                    # noqa: E731
    in_specs = [_LANE_SPEC, _LANE_SPEC,
                pl.BlockSpec((None,) + tab.shape[1:], tile)]
    operands = [_lanes(queries), _lanes(shard_ids), tab]
    nw = _with_fat(in_specs, operands, fat_keys, tile, 1)
    kernel = functools.partial(_foresight_sharded_kernel, levels=L,
                               cap=_row_padded(cap),
                               max_steps=plan.max_steps, node_width=nw)
    return _launch(kernel, plan.grid, in_specs, operands,
                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("max_steps", "interpret"))
def base_traverse_sharded(nxt: jax.Array, keys: jax.Array,
                          shard_ids: jax.Array, queries: jax.Array,
                          fat_keys: Optional[jax.Array] = None, *,
                          max_steps: int = 0, interpret: bool):
    """Sharded base search over ``nxt [S, L, cap]`` / ``keys [S, cap]``."""
    S, L, cap = nxt.shape
    plan = plan_launch(levels=L, capacity=cap, batch=queries.shape[0],
                       max_steps=max_steps, n_shards=S)
    nxt_p, keys_p = _plane(nxt, 1), _plane(keys, 1)
    tile = lambda j, s: (s, 0, 0)                    # noqa: E731
    in_specs = [_LANE_SPEC, _LANE_SPEC,
                pl.BlockSpec((None,) + nxt_p.shape[1:], tile),
                pl.BlockSpec((None,) + keys_p.shape[1:], tile)]
    operands = [_lanes(queries), _lanes(shard_ids), nxt_p, keys_p]
    nw = _with_fat(in_specs, operands, fat_keys, tile, 1)
    kernel = functools.partial(_base_sharded_kernel, levels=L, cap=cap,
                               max_steps=plan.max_steps, node_width=nw)
    return _launch(kernel, plan.grid, in_specs, operands,
                   interpret=interpret)


# ---------------------------------------------------------------------------
# Clustered kernels: grid (B // QBLK, K) on PrefetchScalarGridSpec — only
# the shard tiles a query block actually needs are DMA'd
# ---------------------------------------------------------------------------
#
# The caller (``ops.cluster_queries``) stably sorts the padded query batch
# by shard id, so each QBLK block of sorted lanes touches a small contiguous
# run of shards.  Two scalar-prefetch arrays drive the launch:
#
# * ``block_sids [nblk, K]`` — slot k of block j names the k-th distinct
#   shard among block j's lanes; slots past the distinct count repeat the
#   block's last shard so the table-tile index_map re-selects the resident
#   tile (coalesced ⇒ no DMA).
# * ``ndist [nblk]`` — the distinct-shard count; slots with ``k >= ndist``
#   skip compute entirely via ``pl.when`` (their lanes were already served
#   by the earlier slot holding the same shard id).
#
# Outputs are indexed by j only, so the output block stays resident across
# the K minor steps (same revisited-block accumulation as the dense grid).

def _foresight_clustered_kernel(bsids_ref, ndist_ref, q_ref, sid_ref,
                                fused_ref, *rest,
                                levels: int, cap: int, max_steps: int,
                                node_width: int = 1):
    fatk_ref, node_ref, key_ref, scratch = _split_rest(rest, node_width)
    j = pl.program_id(0)
    k = pl.program_id(1)
    q = q_ref[...]                                   # [1, QBLK] shard-sorted
    mine = sid_ref[...] == bsids_ref[j, k]

    def search():
        gather = _fused_gather(fused_ref, cap, scratch)
        x = _traverse_loop(q, mine, gather, levels=levels,
                           max_steps=max_steps)
        return _finish(gather, fatk_ref, q, x, node_width, scratch)

    _serve_mine(mine, k == 0, k < ndist_ref[j], node_ref, key_ref, search)


def _base_clustered_kernel(bsids_ref, ndist_ref, q_ref, sid_ref, nxt_ref,
                           keys_ref, *rest,
                           levels: int, cap: int, max_steps: int,
                           node_width: int = 1):
    fatk_ref, node_ref, key_ref, scratch = _split_rest(rest, node_width)
    j = pl.program_id(0)
    k = pl.program_id(1)
    q = q_ref[...]
    mine = sid_ref[...] == bsids_ref[j, k]

    def search():
        gather = _base_gather(nxt_ref, keys_ref, cap, scratch)
        x = _traverse_loop(q, mine, gather, levels=levels,
                           max_steps=max_steps)
        return _finish(gather, fatk_ref, q, x, node_width, scratch)

    _serve_mine(mine, k == 0, k < ndist_ref[j], node_ref, key_ref, search)


def _check_plan(B: int, block_sids: jax.Array, S: int) -> None:
    nblk, K = block_sids.shape
    assert B == nblk * QBLK, "queries must be padded to block_sids' blocks"
    assert K <= S, (f"ClusterPlan with K={K} > S={S}: plan built against a "
                    "different shard count (stale after a rebalance?) — "
                    "rebuild it from the current boundaries")


@functools.partial(jax.jit, static_argnames=("max_steps", "interpret"))
def foresight_traverse_clustered(fused: jax.Array, block_sids: jax.Array,
                                 ndist: jax.Array, shard_ids: jax.Array,
                                 queries: jax.Array,
                                 fat_keys: Optional[jax.Array] = None, *,
                                 max_steps: int = 0, interpret: bool):
    """Clustered foresight search over ``fused [S, L, cap, 2]``.

    ``queries``/``shard_ids`` must be shard-sorted and ``block_sids [nblk,
    K]`` / ``ndist [nblk]`` built for that order (``ops.cluster_queries``).
    Returns (node[B], cand_key[B]) in the SORTED order; the caller unsorts
    with the inverse permutation.
    """
    S, L, cap, _ = fused.shape
    B = queries.shape[0]
    _check_plan(B, block_sids, S)
    plan = plan_launch(levels=L, capacity=cap, batch=B,
                       max_steps=max_steps, n_shards=block_sids.shape[1])
    tab = _fused_plane(fused, 1)
    tile = lambda j, k, bs, nd: (bs[j, k], 0, 0)     # noqa: E731
    in_specs = [_LANE_SPEC, _LANE_SPEC,
                pl.BlockSpec((None,) + tab.shape[1:], tile)]
    operands = [block_sids.astype(jnp.int32), ndist.astype(jnp.int32),
                _lanes(queries), _lanes(shard_ids), tab]
    nw = _with_fat(in_specs, operands, fat_keys, tile, 1)
    kernel = functools.partial(_foresight_clustered_kernel, levels=L,
                               cap=_row_padded(cap), max_steps=plan.max_steps,
                               node_width=nw)
    return _launch(kernel, plan.grid, in_specs, operands,
                   interpret=interpret, num_scalar_prefetch=2)


@functools.partial(jax.jit, static_argnames=("max_steps", "interpret"))
def base_traverse_clustered(nxt: jax.Array, keys: jax.Array,
                            block_sids: jax.Array, ndist: jax.Array,
                            shard_ids: jax.Array, queries: jax.Array,
                            fat_keys: Optional[jax.Array] = None, *,
                            max_steps: int = 0, interpret: bool):
    """Clustered base search over ``nxt [S, L, cap]`` / ``keys [S, cap]``."""
    S, L, cap = nxt.shape
    B = queries.shape[0]
    _check_plan(B, block_sids, S)
    plan = plan_launch(levels=L, capacity=cap, batch=B,
                       max_steps=max_steps, n_shards=block_sids.shape[1])
    nxt_p, keys_p = _plane(nxt, 1), _plane(keys, 1)
    tile = lambda j, k, bs, nd: (bs[j, k], 0, 0)     # noqa: E731
    in_specs = [_LANE_SPEC, _LANE_SPEC,
                pl.BlockSpec((None,) + nxt_p.shape[1:], tile),
                pl.BlockSpec((None,) + keys_p.shape[1:], tile)]
    operands = [block_sids.astype(jnp.int32), ndist.astype(jnp.int32),
                _lanes(queries), _lanes(shard_ids), nxt_p, keys_p]
    nw = _with_fat(in_specs, operands, fat_keys, tile, 1)
    kernel = functools.partial(_base_clustered_kernel, levels=L, cap=cap,
                               max_steps=plan.max_steps, node_width=nw)
    return _launch(kernel, plan.grid, in_specs, operands,
                   interpret=interpret, num_scalar_prefetch=2)
