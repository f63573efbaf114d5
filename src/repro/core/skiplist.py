"""Foresight skiplist — functional, structure-of-arrays, JAX-native.

This is the paper's core contribution adapted to TPU (see DESIGN.md §2):

* The skiplist lives in HBM as structure-of-arrays.  A traversal step is a
  *dependent gather*; the chain of dependent gathers is the TPU analogue of the
  paper's cache-miss chain.
* **Base** variant stores ``nxt[L, cap]`` pointers only: each traversal step
  gathers the successor pointer, then (dependently) gathers that successor's
  key — two serialized HBM round-trips per step.
* **Foresight** variant stores ``fused[L, cap, 2]`` records where
  ``fused[l, i] = (next_ptr, next_key)`` interleaved in the minor dimension:
  one gather per step fetches both.  The pair is always written together —
  the functional analogue of the paper's 16-byte atomic SIMD store.
* "Concurrency" is batched, level-synchronous vectorized traversal: a batch of
  queries advances in lock-step (lanes = the paper's threads).  Updates are
  functional (``lax.scan`` of linearized single ops → a new version).

Node 0 is the head sentinel (key = KEY_MIN) and node 1 the tail sentinel
(key = KEY_MAX), so every ``next`` pointer is always valid and the traversal
loop is branch-free.  Keys are int32 in the open interval (KEY_MIN, KEY_MAX).

Fat-node layout (``node_width`` > 1)
------------------------------------

The scalar layout above resolves ONE key per dependent gather.  The
fat-node layout (B-Skiplist style; see ISSUE 10 / PAPERS.md) packs each
node with a contiguous sorted *run* of up to ``node_width`` (= B, naturally
128 on TPU — the VPU lane width) keys stored lane-major:

* ``fat_keys [cap, B]`` / ``fat_vals [cap, B]`` — per-node runs, ascending,
  padded with ``KEY_MAX`` / ``NULL_VAL`` past ``nlen[node]`` live lanes;
* ``keys[node]`` holds the run's exact MINIMUM (the routing key) and the
  skip structure (``fused`` / ``nxt``) is built over *nodes*, unchanged in
  shape — so the whole traversal loop is layout-agnostic and one fused
  gather now services a ``B``-wide tile of comparisons;
* the final within-node position is a single ``searchsorted``-style lane
  compare over a VMEM-resident ``[B]`` tile — not a dependent gather;
* builds pack runs at ``pack_fill(B) = B // 2`` so every node carries
  per-node insert slack (the fat analogue of the scalar tail padding);
  a full node splits at its median (``_fat_insert`` case 2), an emptied
  node splices out and returns to the freelist (``_fat_delete``).

``n`` counts live ELEMENTS; ``bump`` / ``free_list`` allocate NODE slots.
``capacity`` keeps its meaning of node-slot count everywhere, so the
scalar engine is exactly ``node_width=1`` (``fat_keys is None``) and the
two layouts are differentially testable against each other.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

KEY_MIN = jnp.int32(-(2**31))          # head sentinel key (-inf)
KEY_MAX = jnp.int32(2**31 - 1)         # tail sentinel key (+inf)
HEAD = 0                               # node id of head sentinel
TAIL = 1                               # node id of tail sentinel
NULL_VAL = jnp.int32(-1)


class SkipListState(NamedTuple):
    """Functional skiplist state (a pytree).

    Exactly one of ``nxt`` (base) / ``fused`` (foresight) is set, so the two
    variants are memory-fair: base keeps no successor keys at all.
    """

    keys: jax.Array          # [cap] int32 — node key (KEY_MAX for unused slots)
    vals: jax.Array          # [cap] int32 — payload
    height: jax.Array        # [cap] int32 — tower height (sentinels = L)
    nxt: Optional[jax.Array]    # [L, cap] int32 — base variant only
    fused: Optional[jax.Array]  # [L, cap, 2] int32 — foresight variant only
    n: jax.Array             # [] int32 — live element count (excl. sentinels)
    free_top: jax.Array      # [] int32 — freelist stack top (== #free slots)
    free_list: jax.Array     # [cap] int32 — stack of recycled node ids
    bump: jax.Array          # [] int32 — next never-used slot (bump allocator)
    rng: jax.Array           # [2] uint32 — jax PRNG key for tower heights
    fat_keys: Optional[jax.Array] = None  # [cap, B] int32 — fat layout only
    fat_vals: Optional[jax.Array] = None  # [cap, B] int32 — fat layout only
    nlen: Optional[jax.Array] = None      # [cap] int32 — live lanes per run

    @property
    def levels(self) -> int:
        arr = self.nxt if self.nxt is not None else self.fused
        return arr.shape[0]

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    @property
    def foresight(self) -> bool:
        return self.fused is not None

    @property
    def node_width(self) -> int:
        # shape[-1] so the property also answers on stacked (sharded) states
        return self.fat_keys.shape[-1] if self.fat_keys is not None else 1


# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------

def pack_fill(node_width: int) -> int:
    """Elements packed per node at build time (fat layout): half-full runs
    leave per-node insert slack — the fat analogue of tail padding."""
    return max(1, node_width // 2)


def node_slots_for(n_elems: int, node_width: int) -> int:
    """Node slots needed to pack ``n_elems`` elements at build fill.

    ``n_elems`` must be a static python int — every capacity decision is
    shape arithmetic, never a traced value.
    """
    return max(1, -(-n_elems // pack_fill(node_width)))


def usable_capacity(capacity: int, node_width: int = 1) -> int:
    """Conservative insertable-element budget at ``capacity`` node slots.

    Scalar: ``capacity - 2`` (every non-sentinel slot holds one element).
    Fat: ``(capacity - 2) * pack_fill(node_width)`` — the build-fill mass;
    runs can individually grow to ``node_width`` but watermarking against
    the fill keeps split headroom ahead of node-slot exhaustion.
    """
    return (capacity - 2) * pack_fill(node_width)


def empty(capacity: int, levels: int = 20, *, foresight: bool = True,
          seed: int = 0, node_width: int = 1) -> SkipListState:
    """An empty skiplist with room for ``capacity - 2`` elements."""
    keys = jnp.full((capacity,), KEY_MAX, jnp.int32)
    keys = keys.at[HEAD].set(KEY_MIN)
    vals = jnp.full((capacity,), NULL_VAL, jnp.int32)
    height = jnp.zeros((capacity,), jnp.int32)
    height = height.at[HEAD].set(levels).at[TAIL].set(levels)
    nxt = fused = None
    if foresight:
        fused = jnp.zeros((levels, capacity, 2), jnp.int32)
        fused = fused.at[:, HEAD, 0].set(TAIL)
        fused = fused.at[:, HEAD, 1].set(KEY_MAX)
        fused = fused.at[:, TAIL, 0].set(TAIL)
        fused = fused.at[:, TAIL, 1].set(KEY_MAX)
    else:
        nxt = jnp.zeros((levels, capacity), jnp.int32)
        nxt = nxt.at[:, HEAD].set(TAIL)
        nxt = nxt.at[:, TAIL].set(TAIL)
    fat_keys = fat_vals = nlen = None
    if node_width > 1:
        fat_keys = jnp.full((capacity, node_width), KEY_MAX, jnp.int32)
        fat_vals = jnp.full((capacity, node_width), NULL_VAL, jnp.int32)
        nlen = jnp.zeros((capacity,), jnp.int32)
    return SkipListState(
        keys=keys, vals=vals, height=height, nxt=nxt, fused=fused,
        n=jnp.int32(0), free_top=jnp.int32(0),
        free_list=jnp.zeros((capacity,), jnp.int32), bump=jnp.int32(2),
        rng=jax.random.PRNGKey(seed),
        fat_keys=fat_keys, fat_vals=fat_vals, nlen=nlen,
    )


def sample_heights(rng: jax.Array, shape, levels: int) -> jax.Array:
    """Geometric(1/2) tower heights in [1, levels] (Synchrobench's G(1/2))."""
    bits = jax.random.bits(rng, shape, jnp.uint32)
    # height = 1 + number of trailing one-bits, capped at levels.
    inv = ~bits
    ctz = _count_trailing_zeros(inv)
    return jnp.minimum(ctz.astype(jnp.int32) + 1, levels)


def _count_trailing_zeros(x: jax.Array) -> jax.Array:
    """ctz for uint32 (32 for x == 0)."""
    lsb = x & (~x + jnp.uint32(1))
    safe = jnp.where(lsb == 0, jnp.uint32(1), lsb)
    # Portable integer log2 of a power of two via float conversion.
    f = safe.astype(jnp.float64) if jax.config.read("jax_enable_x64") else safe.astype(jnp.float32)
    ctz = jnp.log2(f).astype(jnp.int32)
    return jnp.where(x == 0, jnp.int32(32), ctz)


@functools.partial(jax.jit, static_argnames=("capacity", "levels", "foresight",
                                             "node_width"))
def build(keys: jax.Array, vals: jax.Array, *, capacity: int,
          levels: int = 20, foresight: bool = True,
          seed: int = 0, valid: Optional[jax.Array] = None,
          node_width: int = 1) -> SkipListState:
    """Bulk-build from sorted, unique int32 keys (vectorized; no python loop).

    Elements get node ids ``2 .. n+1`` in key order.  For every level ``l``,
    the nodes whose tower reaches ``l`` form the linked list at that level;
    the successor of position ``i`` is the next position ``j > i`` whose
    tower also reaches ``l`` (computed with a reversed cumulative-min).

    ``valid`` (optional, [n] bool) marks real entries; invalid positions must
    form a suffix and are built as height-0, never-linked padding.  This lets
    a caller with a dynamic element count (e.g. the sharded builder, which
    pads every shard to a common static length) reuse the static-shape build.

    ``node_width`` > 1 selects the fat-node layout: elements are packed into
    runs of ``pack_fill(node_width)`` keys per node and the skip structure is
    built over the node minima (see module docstring).  ``capacity`` still
    counts NODE slots, so a fat build needs only
    ``node_slots_for(n, node_width) + 2`` of them.
    """
    if node_width > 1:
        return _build_fat(keys, vals, capacity=capacity, levels=levels,
                          foresight=foresight, seed=seed, valid=valid,
                          node_width=node_width)
    return _build_scalar(keys, vals, capacity=capacity, levels=levels,
                         foresight=foresight, seed=seed, valid=valid)


def _build_scalar(keys: jax.Array, vals: jax.Array, *, capacity: int,
                  levels: int, foresight: bool, seed: int,
                  valid: Optional[jax.Array]) -> SkipListState:
    n = keys.shape[0]
    assert n + 2 <= capacity, "capacity must exceed n + 2 sentinels"
    st = empty(capacity, levels, foresight=foresight, seed=seed)
    rng, sub = jax.random.split(st.rng)
    heights = sample_heights(sub, (n,), levels)
    if valid is None:
        valid = jnp.ones((n,), jnp.bool_)
    heights = jnp.where(valid, heights, 0)       # padding: no tower, no links
    keys = jnp.where(valid, keys.astype(jnp.int32), KEY_MAX)
    vals = jnp.where(valid, vals.astype(jnp.int32), NULL_VAL)

    # Elements take the contiguous node ids 2 .. n+1, so every write below
    # is a static slice, never a scatter.
    new_keys = st.keys.at[2:n + 2].set(keys)
    new_vals = st.vals.at[2:n + 2].set(vals)
    new_height = st.height.at[2:n + 2].set(heights)

    pos = jnp.arange(n, dtype=jnp.int32)

    def link_level(lvl, table):
        """Level ``lvl``'s links; one level at a time keeps the build's
        temporaries at O(n) instead of O(levels * n)."""
        reach = heights > lvl                             # [n]
        # suffix_min[i] = first position j >= i whose tower reaches lvl
        suffix_min = lax.cummin(jnp.where(reach, pos, n)[::-1])[::-1]
        first_pos = suffix_min[0] if n > 0 else jnp.int32(n)
        # successor of the node at position i = next reaching position > i
        succ_pos = jnp.concatenate([suffix_min[1:],
                                    jnp.full((1,), n, jnp.int32)])
        succ_id = jnp.where(succ_pos >= n, TAIL, succ_pos + 2)
        succ_key = jnp.where(succ_pos >= n, KEY_MAX,
                             keys[jnp.clip(succ_pos, 0, n - 1)])
        head_id = jnp.where(first_pos >= n, TAIL, first_pos + 2)
        head_key = jnp.where(first_pos >= n, KEY_MAX,
                             keys[jnp.clip(first_pos, 0, n - 1)])
        succ_id = jnp.where(reach, succ_id, 0)            # only real levels
        if foresight:
            succ_key = jnp.where(reach, succ_key, 0)
            rows = jnp.stack([succ_id, succ_key], axis=-1)[None]
            table = lax.dynamic_update_slice(table, rows, (lvl, 2, 0))
            head = jnp.stack([head_id, head_key])[None, None]
            return lax.dynamic_update_slice(table, head, (lvl, HEAD, 0))
        table = lax.dynamic_update_slice(table, succ_id[None], (lvl, 2))
        return lax.dynamic_update_slice(table, head_id[None, None],
                                        (lvl, HEAD))

    table = lax.fori_loop(0, levels, link_level,
                          st.fused if foresight else st.nxt)
    nxt, fused = (None, table) if foresight else (table, None)

    # Padded (invalid) slots are bit-identical to never-used ones (KEY_MAX
    # key, zero height, unlinked), so the bump allocator stops at the live
    # prefix and reuses the padding as free capacity — essential for shards
    # re-bulk-built from full-width padded arrays (sharded.split_shard /
    # merge_shards), whose padding IS their entire insert headroom.
    n_live = jnp.sum(valid).astype(jnp.int32)
    return st._replace(keys=new_keys, vals=new_vals, height=new_height,
                       nxt=nxt, fused=fused, n=n_live,
                       bump=n_live + jnp.int32(2), rng=rng)


def _build_fat(keys: jax.Array, vals: jax.Array, *, capacity: int,
               levels: int, foresight: bool, seed: int,
               valid: Optional[jax.Array], node_width: int) -> SkipListState:
    """Fat-layout build: pack runs at ``pack_fill`` then node-level build.

    The element stream reshapes into ``[n_nodes, fill]`` runs (lane-padded
    to ``node_width`` with KEY_MAX) and the scalar builder links the run
    minima — dead trailing nodes (from a ``valid`` prefix shorter than the
    static input) come out as height-0 KEY_MAX padding exactly like scalar
    padding slots, so the node-slot bump allocator reuses them for splits.
    """
    Bw = node_width
    fill = pack_fill(Bw)
    n_in = keys.shape[0]
    if valid is None:
        valid = jnp.ones((n_in,), jnp.bool_)
    keys = jnp.where(valid, keys.astype(jnp.int32), KEY_MAX)
    vals = jnp.where(valid, vals.astype(jnp.int32), NULL_VAL)
    n_nodes = -(-n_in // fill) if n_in else 0
    assert n_nodes + 2 <= capacity, \
        "capacity (node slots) must exceed packed node count + 2 sentinels"
    pad = n_nodes * fill - n_in
    kp = jnp.concatenate([keys, jnp.full((pad,), KEY_MAX, jnp.int32)])
    vp = jnp.concatenate([vals, jnp.full((pad,), NULL_VAL, jnp.int32)])
    vm = jnp.concatenate([valid, jnp.zeros((pad,), jnp.bool_)])
    runs_k = jnp.concatenate(
        [kp.reshape(n_nodes, fill),
         jnp.full((n_nodes, Bw - fill), KEY_MAX, jnp.int32)], axis=1)
    runs_v = jnp.concatenate(
        [vp.reshape(n_nodes, fill),
         jnp.full((n_nodes, Bw - fill), NULL_VAL, jnp.int32)], axis=1)
    node_valid = vm[::fill]       # valid is a prefix => first-lane validity
    st = _build_scalar(runs_k[:, 0], jnp.full((n_nodes,), NULL_VAL, jnp.int32),
                       capacity=capacity, levels=levels, foresight=foresight,
                       seed=seed, valid=node_valid)
    fat_keys = jnp.full((capacity, Bw), KEY_MAX, jnp.int32)
    fat_vals = jnp.full((capacity, Bw), NULL_VAL, jnp.int32)
    nlen = jnp.zeros((capacity,), jnp.int32)
    n_live = jnp.sum(valid).astype(jnp.int32)
    if n_nodes:
        ids = jnp.arange(2, n_nodes + 2, dtype=jnp.int32)
        fat_keys = fat_keys.at[ids].set(runs_k)
        fat_vals = fat_vals.at[ids].set(runs_v)
        per = jnp.clip(n_live - jnp.arange(n_nodes, dtype=jnp.int32) * fill,
                       0, fill)
        nlen = nlen.at[ids].set(per)
    return st._replace(fat_keys=fat_keys, fat_vals=fat_vals, nlen=nlen,
                       n=n_live)


# ---------------------------------------------------------------------------
# Gather helpers — the heart of the base-vs-foresight distinction
# ---------------------------------------------------------------------------

def _gather_fused(fused: jax.Array, lvl: jax.Array, x: jax.Array):
    """ONE gather: fetch (next_ptr, next_key) for nodes ``x`` at levels ``lvl``.

    Through a flat ``[L * cap, 2]`` view of the table.  The fat layout's
    search and updates, and the level-0 walks of ``range_scan`` and
    ``to_sorted_keys``, read the table this way.  The scalar search and
    write path read the table's two planes instead (``_split``): on TPU the
    table lies as those planes, pair dimension outermost, so the flat view
    is a relayout copy of the whole table, which the compiler sinks into
    the body of a loop that reads it, one copy per search step.
    """
    cap = fused.shape[1]
    flat = fused.reshape((-1, 2))
    rec = jnp.take(flat, lvl * cap + x, axis=0)           # [B, 2]
    return rec[..., 0], rec[..., 1]


def _read_fused(fused: jax.Array, lvl: jax.Array, x: jax.Array):
    """``_gather_fused`` for read-only programs: indexes the table in place.

    ``search_fast`` reads the table this way.  Not for a program that also
    writes the table: on TPU, a loop that reads the table this way followed
    by an in-place write to it makes the compiler copy the whole table into
    a layout 64 times its size.  The scalar write path reads the table's
    planes (``_split``); the fat layout's keeps ``_gather_fused``.
    """
    rec = fused[lvl, x]                                   # [B, 2]
    return rec[..., 0], rec[..., 1]


def _gather_base(nxt: jax.Array, keys: jax.Array, lvl: jax.Array, x: jax.Array):
    """TWO dependent gathers: fetch next_ptr, then dereference for its key."""
    cap = nxt.shape[1]
    ptr = jnp.take(nxt.reshape(-1), lvl * cap + x, axis=0)  # gather 1
    fk = jnp.take(keys, ptr, axis=0)                        # gather 2 (dependent)
    return ptr, fk


def _table_gather(state: SkipListState):
    """(gather(lvl, x) -> (next_ptr, next_key), dependent gathers a step)
    over the state's own table."""
    if state.foresight:
        return functools.partial(_gather_fused, state.fused), 1
    return functools.partial(_gather_base, state.nxt, state.keys), 2


# ---------------------------------------------------------------------------
# The scalar table as planes
# ---------------------------------------------------------------------------
#
# The scalar search and write path hold the table as planes of [L, cap]:
# the fused table's pointers and next keys, ``(fused[..., 0],
# fused[..., 1])``, or the base table ``(nxt,)``.  A search indexes each
# plane in place and a splice writes them with element scatters.  On TPU a
# loop that reads and writes the fused ``[L, cap, 2]`` table holds it as
# two such planes anyway (pair dimension outermost), and reading it through
# a flat view made the compiler copy the whole table once per search step.
# ``apply_ops`` splits once before its scan and stacks once after it.

def _split(state: SkipListState) -> Tuple[SkipListState, tuple]:
    """(the state without its table, the table's planes)."""
    if state.foresight:
        return (state._replace(fused=None),
                (state.fused[..., 0], state.fused[..., 1]))
    return state._replace(nxt=None), (state.nxt,)


def _stack(rest: SkipListState, planes: tuple) -> SkipListState:
    """Inverse of ``_split``.  Stacked on a leading axis and moved last, the
    planes reach the TPU's tiling of the fused table with fewer temporaries
    than a stack on the last axis (5.50 against 7.38 GB at capacity 2^24,
    the same 2.42 GB at 2^23)."""
    if len(planes) == 2:
        return rest._replace(fused=jnp.moveaxis(jnp.stack(planes), 0, -1))
    return rest._replace(nxt=planes[0])


def _plane_gather(planes: tuple, keys: jax.Array):
    """``_table_gather`` over planes, each indexed in place: one gather a
    step with foresight; base keeps its two dependent gathers."""
    if len(planes) == 2:
        ptr, nkey = planes
        return (lambda lvl, x: (ptr[lvl, x], nkey[lvl, x])), 1
    nxt = planes[0]

    def gather(lvl, x):
        ptr = nxt[lvl, x]
        return ptr, jnp.take(keys, ptr, axis=0)
    return gather, 2


# ---------------------------------------------------------------------------
# Batched level-synchronous search (the paper's Algorithm 1 / 2, vectorized)
# ---------------------------------------------------------------------------

class SearchResult(NamedTuple):
    found: jax.Array     # [B] bool
    vals: jax.Array      # [B] int32 (NULL_VAL when absent)
    node: jax.Array      # [B] int32 — node id with the key (TAIL when absent)
    preds: jax.Array     # [B, L] int32 — last node visited per level
    steps: jax.Array     # [] int32 — lock-step iterations executed
    gathers: jax.Array   # [] int32 — dependent-gather count (arch. counter)


def _search_loop(gather, per_step: int, L: int, q: jax.Array,
                 stop_level: int):
    """The level-synchronous traversal loop: (x, preds, steps, gathers).

    ``gather(lvl, x)`` fetches ``(next_ptr, next_key)`` with ``per_step``
    dependent gathers (``_table_gather``, ``_plane_gather``).
    Layout-agnostic — under the fat layout ``keys``/``fused`` are node-level
    (run minima), so ``x`` lands on the level-``stop_level`` predecessor
    NODE and each counted gather is a tile gather servicing ``node_width``
    comparisons.
    """
    B = q.shape[0]
    x = jnp.zeros((B,), jnp.int32)                # start at head
    lvl = jnp.full((B,), L - 1, jnp.int32)
    preds = jnp.zeros((B, L), jnp.int32)
    steps = jnp.int32(0)
    gathers = jnp.int32(0)

    def cond(carry):
        x, lvl, preds, steps, gathers = carry
        return jnp.any(lvl >= stop_level)

    def body(carry):
        x, lvl, preds, steps, gathers = carry
        active = lvl >= stop_level
        safe_lvl = jnp.maximum(lvl, 0)
        ptr, fk = gather(safe_lvl, x)
        go_right = active & (fk < q)
        new_x = jnp.where(go_right, ptr, x)
        # On descend, record predecessor for the level we are leaving.
        desc = active & ~go_right
        preds = _scatter_rows(preds, safe_lvl, x, desc)
        new_lvl = jnp.where(go_right, lvl, lvl - 1)
        new_lvl = jnp.where(active, new_lvl, lvl)
        steps = steps + 1
        gathers = gathers + per_step * jnp.sum(active).astype(jnp.int32)
        return new_x, jnp.where(active, new_lvl, lvl), preds, steps, gathers

    with jax.named_scope("search_loop"):
        x, lvl, preds, steps, gathers = lax.while_loop(
            cond, body, (x, lvl, preds, steps, gathers))
    return x, preds, steps, gathers


def _fat_resolve_batch(state: SkipListState, q: jax.Array, x: jax.Array,
                       cand: jax.Array, cand_key: jax.Array):
    """Owner node + within-run position for fat-layout queries [B].

    ``x`` is the level-0 predecessor node, ``cand`` its successor.  The
    owner of ``q``'s position is ``cand`` when ``q`` matches its min (or
    when nothing precedes it, i.e. ``x`` is still the head), else ``x``.
    The lane position is one tile compare over the owner's run — VMEM
    arithmetic, not a dependent gather.
    """
    Bw = state.node_width
    owner = jnp.where((cand_key == q) | (x == HEAD), cand, x)
    run = jnp.take(state.fat_keys, owner, axis=0)          # [B, Bw]
    pos = jnp.sum(run < q[:, None], axis=1).astype(jnp.int32)
    pos_c = jnp.minimum(pos, Bw - 1)
    hit = jnp.take_along_axis(run, pos_c[:, None], axis=1)[:, 0]
    found = (pos < Bw) & (hit == q)
    return owner, pos, pos_c, found


def search(state: SkipListState, queries: jax.Array,
           *, stop_level: int = 0, count_accesses: bool = False
           ) -> SearchResult:
    """Batched search for int32 ``queries`` [B].

    Level-synchronous: every query advances right or descends once per
    lock-step iteration.  Foresight needs ONE dependent gather per iteration;
    base needs TWO (pointer, then pointee key).  ``preds`` records the last
    node visited per level — the predecessors array used by updates.

    Under the fat layout the loop runs over node minima, so ``gathers``
    counts TILE gathers — one fused record per step, each servicing up to
    ``node_width`` comparisons — and ``node`` is the flat element slot
    ``owner * node_width + lane``.  The within-run compare is VMEM-resident
    and deliberately NOT counted, mirroring the scalar counter's exclusion
    of the final candidate gather (fig8 comparability across layouts).
    """
    q = queries.astype(jnp.int32)
    if state.node_width == 1:
        return _locate(_split(state)[1], state.keys, state.vals, q,
                       stop_level)
    B = q.shape[0]
    gather, per_step = _table_gather(state)
    x, preds, steps, gathers = _search_loop(gather, per_step, state.levels,
                                            q, stop_level)
    # The candidate is the successor of the level-``stop_level`` predecessor.
    cand, cand_key = gather(jnp.full((B,), stop_level, jnp.int32), x)
    owner, pos, pos_c, found = _fat_resolve_batch(state, q, x, cand, cand_key)
    flat = owner * state.node_width + pos_c
    vals = jnp.where(found,
                     jnp.take(state.fat_vals.reshape(-1), flat), NULL_VAL)
    node = jnp.where(found, flat, TAIL)
    return SearchResult(found, vals, node, preds, steps, gathers)


def _locate(planes: tuple, keys: jax.Array, vals: jax.Array, q: jax.Array,
            stop_level: int = 0) -> SearchResult:
    """``search`` of the scalar layout, on the table's planes."""
    B = q.shape[0]
    gather, per_step = _plane_gather(planes, keys)
    x, preds, steps, gathers = _search_loop(gather, per_step,
                                            planes[0].shape[0], q, stop_level)
    # The candidate is the successor of the level-``stop_level`` predecessor.
    cand, cand_key = gather(jnp.full((B,), stop_level, jnp.int32), x)
    found = cand_key == q
    vals = jnp.where(found, jnp.take(vals, cand), NULL_VAL)
    node = jnp.where(found, cand, TAIL)
    return SearchResult(found, vals, node, preds, steps, gathers)


def contains(state: SkipListState, queries: jax.Array) -> jax.Array:
    return search(state, queries).found


def effective_top_level(state: SkipListState) -> jax.Array:
    """Highest level where the head has a real successor (+1 slack).

    Starting traversals here instead of at L-1 skips the empty upper levels
    — for n elements only ~log2(n) levels are populated (§Perf iteration 8).
    """
    if state.foresight:
        head_next = state.fused[:, HEAD, 0]
    else:
        head_next = state.nxt[:, HEAD]
    populated = head_next != TAIL
    top = jnp.max(jnp.where(populated,
                            jnp.arange(state.levels), -1))
    return jnp.minimum(top + 1, state.levels - 1).astype(jnp.int32)


def search_fast(state: SkipListState, queries: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Read-only lookup: (found [B], vals [B]).

    §Perf iterations 8-9 on the paper's own data structure: vs ``search``
    this (a) drops predecessor tracking — read paths don't need preds, and
    the per-step [B, L] one-hot bookkeeping dominated the lock-step cost at
    wide batches, washing out Foresight's gather saving — and (b) starts at
    the effective top level, skipping ~L - log2(n) empty iterations.
    """
    return _search_fast(state, queries, count=False)


def search_fast_counted(state: SkipListState, queries: jax.Array
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``search_fast`` that also counts its loop: (found, vals, steps).

    ``steps[b]`` is the number of lock-step iterations in which lane ``b``
    was still searching, each one dependent gather (two without
    foresight).  A lane that stops stays stopped, so the loop ran
    ``max(steps)`` iterations.  ``search`` counts the same gathers (also
    without the final candidate gather) but starts every lane at level
    ``L - 1``, one step a level above ``effective_top_level``, so its
    ``gathers`` exceed ``g * sum(steps)`` by exactly
    ``B * g * (L - 1 - effective_top_level)``.  The count is a
    primitive-level add in the loop, so the eager read path lowers no
    nested program for it.
    """
    return _search_fast(state, queries, count=True)


@jax.named_scope("search_fast")
def _search_fast(state: SkipListState, queries: jax.Array, *, count: bool
                 ) -> tuple:
    """The loop of both; with ``count`` it also carries each lane's steps,
    returned last.  Without, the eager read path lowers the smaller
    program on every call."""
    q = queries.astype(jnp.int32)
    B = q.shape[0]
    x = jnp.zeros((B,), jnp.int32)
    lvl = jnp.broadcast_to(effective_top_level(state), (B,))

    def cond(carry):
        return jnp.any(carry[1] >= 0)

    def body(carry):
        x, lvl = carry[:2]
        active = lvl >= 0
        safe_lvl = jnp.maximum(lvl, 0)
        if state.foresight:
            ptr, fk = _read_fused(state.fused, safe_lvl, x)
        else:
            ptr, fk = _gather_base(state.nxt, state.keys, safe_lvl, x)
        go = active & (fk < q)
        out = (jnp.where(go, ptr, x), jnp.where(go | ~active, lvl, lvl - 1))
        if count:
            out += (lax.add(carry[2],
                            lax.convert_element_type(active, jnp.int32)),)
        return out

    carry = lax.while_loop(cond, body, (x, lvl, x) if count else (x, lvl))
    x, steps = carry[0], carry[2:]
    if state.foresight:
        cand, ck = _read_fused(state.fused, jnp.zeros((B,), jnp.int32), x)
    else:
        cand, ck = _gather_base(state.nxt, state.keys,
                                jnp.zeros((B,), jnp.int32), x)
    if state.node_width > 1:
        owner, pos, pos_c, found = _fat_resolve_batch(state, q, x, cand, ck)
        flat = owner * state.node_width + pos_c
        vals = jnp.where(found,
                         jnp.take(state.fat_vals.reshape(-1), flat), NULL_VAL)
        return (found, vals) + steps
    found = ck == q
    vals = jnp.where(found, jnp.take(state.vals, cand), NULL_VAL)
    return (found, vals) + steps


def _scatter_rows(preds: jax.Array, lvl: jax.Array, x: jax.Array,
                  mask: jax.Array) -> jax.Array:
    """preds[b, lvl[b]] = x[b] where mask[b]."""
    B, L = preds.shape
    onehot = jax.nn.one_hot(lvl, L, dtype=jnp.bool_)
    upd = mask[:, None] & onehot
    return jnp.where(upd, x[:, None], preds)


# ---------------------------------------------------------------------------
# Single-element insert / delete (linearized; scanned for batches)
# ---------------------------------------------------------------------------

def _alloc(state: SkipListState) -> Tuple[SkipListState, jax.Array, jax.Array]:
    """Pop a node id from the freelist, else bump. Returns (state, id, ok)."""
    has_free = state.free_top > 0
    free_id = state.free_list[jnp.maximum(state.free_top - 1, 0)]
    bump_ok = state.bump < state.capacity
    nid = jnp.where(has_free, free_id, state.bump)
    ok = has_free | bump_ok
    new_top = jnp.where(has_free, state.free_top - 1, state.free_top)
    new_bump = jnp.where(has_free, state.bump,
                         jnp.where(bump_ok, state.bump + 1, state.bump))
    return state._replace(free_top=new_top, bump=new_bump), nid, ok


def insert(state: SkipListState, key: jax.Array, val: jax.Array
           ) -> Tuple[SkipListState, jax.Array]:
    """Insert (upsert) a single key. Returns (state, inserted_new: bool).

    Foresight maintenance mirrors the paper exactly: when predecessor ``p``'s
    successor at level ``l`` changes to the new node, we write the pair
    ``(new_id, key)`` into ``p``'s fused record *together* (the SIMD-store
    analogue), and the new node's fused record inherits ``p``'s old pair.
    The splice is ``_apply_one``'s, the one ``apply_ops`` scans.

    Fat layout dispatches to ``_fat_insert`` (lane-shift into the owner run,
    median split when full) — same signalled-failure contract on node-slot
    exhaustion.
    """
    if state.node_width > 1:
        return _fat_insert(state, key, val)
    return _apply_single(state, OP_INSERT, key, val)


def delete(state: SkipListState, key: jax.Array
           ) -> Tuple[SkipListState, jax.Array]:
    """Delete a single key. Returns (state, deleted: bool).

    Splice-out rewrites each predecessor's fused pair to the deleted node's
    pair at that level (again pair-at-once).  The slot is pushed on the
    freelist; its key/height stay intact until reuse — the versioned-world
    analogue of epoch-based reclamation (see DESIGN.md §8).  The splice is
    ``_apply_one``'s, the one ``apply_ops`` scans.

    Fat layout dispatches to ``_fat_delete`` (lane-shift out of the owner
    run; an emptied node splices out and returns to the freelist).
    """
    if state.node_width > 1:
        return _fat_delete(state, key)
    return _apply_single(state, OP_DELETE, key, jnp.int32(0))


def _apply_single(state: SkipListState, op: int, key: jax.Array,
                  val: jax.Array) -> Tuple[SkipListState, jax.Array]:
    """``insert`` / ``delete`` of the scalar layout: one ``_apply_one``."""
    rest, planes = _split(state)
    rest, planes, ok = _apply_one(rest, planes, jnp.int32(op),
                                  jnp.asarray(key, jnp.int32),
                                  jnp.asarray(val, jnp.int32))
    return _stack(rest, planes), ok.astype(jnp.bool_)


def _apply_one(rest: SkipListState, planes: tuple, t: jax.Array,
               key: jax.Array, val: jax.Array
               ) -> Tuple[SkipListState, tuple, jax.Array]:
    """One linearized op of any type on the scalar table's planes.

    Returns (rest, planes, result): found / inserted / deleted as int32.
    One search with predecessors serves all three types and the splice is
    branch-free, so no ``lax.switch`` or ``lax.cond`` passes the table
    through.  An insert links a new node after its predecessors, which take
    ``(new_id, key)``, the new node inheriting their old records; a delete
    gives its predecessors the deleted node's records.  Each plane takes
    element scatters at the same levels in the same step, so a record's
    pointer and next key change together (the paper's pair-at-once store).
    A read, a failed insert (allocation exhausted) and a delete of an
    absent key write back what they read.  An insert advances the RNG
    whether it links or not; reads and deletes leave it.
    """
    L = planes[0].shape[0]
    is_ins = t == OP_INSERT
    res = _locate(planes, rest.keys, rest.vals, key[None])
    found, node, preds = res.found[0], res.node[0], res.preds[0]
    st, nid, ok = _alloc(rest)
    rng, sub = jax.random.split(rest.rng)
    h = sample_heights(sub, (), L)
    do = is_ins & ok & ~found                    # a new node ``nid`` goes in
    gone = (t == OP_DELETE) & found              # node ``node`` comes out
    lvls = jnp.arange(L, dtype=jnp.int32)
    link = do & (lvls < h)
    unlink = gone & (lvls < rest.height[node])
    nid_l = jnp.full((L,), nid, jnp.int32)
    node_l = jnp.full((L,), node, jnp.int32)
    out = []
    for tab, linked in zip(planes, (nid, key)):
        old = tab[lvls, preds]
        removed = tab[lvls, node_l]
        tab = tab.at[lvls, nid_l].set(jnp.where(link, old, tab[lvls, nid_l]))
        out.append(tab.at[lvls, preds].set(
            jnp.where(link, linked, jnp.where(unlink, removed, old))))

    # The one slot whose key, value and height change: the new node, or
    # the found one (an upsert's value, a delete's key and height).
    slot = jnp.where(do, nid, node)
    keys = rest.keys.at[slot].set(
        jnp.where(do, key, jnp.where(gone, KEY_MAX, rest.keys[slot])))
    vals = rest.vals.at[slot].set(
        jnp.where(is_ins & (do | found), val, rest.vals[slot]))
    height = rest.height.at[slot].set(
        jnp.where(do, h, jnp.where(gone, 0, rest.height[slot])))
    free_list = rest.free_list.at[rest.free_top].set(
        jnp.where(gone, node, rest.free_list[rest.free_top]))
    one = jnp.int32(1)
    rest = rest._replace(
        keys=keys, vals=vals, height=height, free_list=free_list,
        # an allocation that did not link rolls back
        free_top=jnp.where(do, st.free_top,
                           rest.free_top + jnp.where(gone, one, 0)),
        bump=jnp.where(do, st.bump, rest.bump),
        n=rest.n + jnp.where(do, one, 0) - jnp.where(gone, one, 0),
        rng=jnp.where(is_ins, rng, rest.rng))
    return rest, tuple(out), jnp.where(is_ins, do, found).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Fat-layout single-element updates (node_width > 1)
# ---------------------------------------------------------------------------

def _fat_locate(state: SkipListState, key: jax.Array):
    """(owner, pos, present, preds, x) for one fat-layout key."""
    gather, per_step = _table_gather(state)
    x, preds, _, _ = _search_loop(gather, per_step, state.levels, key[None], 0)
    cand, ck = gather(jnp.zeros((1,), jnp.int32), x)
    owner, pos, _, present = _fat_resolve_batch(state, key[None], x, cand, ck)
    return owner[0], pos[0], present[0], preds[0], x[0]


def _splice_node(state: SkipListState, nid: jax.Array, nkey: jax.Array,
                 h: jax.Array, preds: jax.Array, do: jax.Array
                 ) -> SkipListState:
    """Link node ``nid`` (key ``nkey``, height ``h``) after ``preds`` where
    ``do`` — the pair-at-once foresight splice from scalar ``insert``."""
    L = state.levels
    lvls = jnp.arange(L, dtype=jnp.int32)
    link = do & (lvls < h)
    nid_full = jnp.full((L,), nid, jnp.int32)
    if state.foresight:
        fused = state.fused
        old = fused[lvls, preds, :]
        new_pair = jnp.where(link[:, None], old, fused[lvls, nid_full, :])
        fused = fused.at[lvls, nid_full, :].set(new_pair)
        pred_pair = jnp.stack([jnp.where(link, nid, old[:, 0]),
                               jnp.where(link, nkey, old[:, 1])], axis=-1)
        fused = fused.at[lvls, preds, :].set(pred_pair)
        state = state._replace(fused=fused)
    else:
        nxt = state.nxt
        old_ptr = nxt[lvls, preds]
        new_ptr = jnp.where(link, old_ptr, nxt[lvls, nid_full])
        nxt = nxt.at[lvls, nid_full].set(new_ptr)
        nxt = nxt.at[lvls, preds].set(jnp.where(link, nid, old_ptr))
        state = state._replace(nxt=nxt)
    keys = state.keys.at[nid].set(jnp.where(do, nkey, state.keys[nid]))
    height = state.height.at[nid].set(jnp.where(do, h, state.height[nid]))
    return state._replace(keys=keys, height=height)


def _set_node_min(state: SkipListState, owner: jax.Array, new_min: jax.Array,
                  preds: jax.Array, do: jax.Array) -> SkipListState:
    """Update ``owner``'s routing min to ``new_min`` where ``do``, fixing
    every foreseen key in ``preds``' fused records that references it.

    Only called when ``preds`` is the predecessor chain of ``owner``'s
    (old or new) minimum, so the guard ``old_ptr == owner`` selects exactly
    the levels whose foreseen key is stale.
    """
    keys = state.keys.at[owner].set(
        jnp.where(do, new_min, state.keys[owner]))
    if not state.foresight:
        return state._replace(keys=keys)
    L = state.levels
    lvls = jnp.arange(L, dtype=jnp.int32)
    old = state.fused[lvls, preds, :]
    fix = do & (old[:, 0] == owner)
    pair = jnp.stack([old[:, 0], jnp.where(fix, new_min, old[:, 1])], axis=-1)
    fused = state.fused.at[lvls, preds, :].set(pair)
    return state._replace(keys=keys, fused=fused)


def _fat_insert(state: SkipListState, key: jax.Array, val: jax.Array
                ) -> Tuple[SkipListState, jax.Array]:
    """Fat-layout insert: upsert / lane-shift / median split / first node.

    One locate resolves the owner run; ``lax.switch`` picks among
    (0) value upsert, (1) lane-shift insert into a run with room,
    (2) full run: allocate a node slot, splice it after the owner at the
    run median, move the upper half, then insert into the correct half,
    (3) empty list: allocate the first node.  Allocation failure in (2)/(3)
    signals via the returned flag, exactly like the scalar path.
    """
    key = key.astype(jnp.int32)
    val = val.astype(jnp.int32)
    Bw = state.node_width
    half = Bw // 2
    owner, pos, present, preds, x = _fat_locate(state, key)
    pos_c = jnp.minimum(pos, Bw - 1)
    run_k = state.fat_keys[owner]
    run_v = state.fat_vals[owner]
    # New global minimum: only possible with the head as level-0 pred —
    # when owner == x, run_k[0] = keys[x] < key forces pos >= 1.
    at_front = (x == HEAD) & ~present
    rng, sub = jax.random.split(state.rng)
    h = sample_heights(sub, (), state.levels)
    state = state._replace(rng=rng)
    lane = jnp.arange(Bw, dtype=jnp.int32)

    def shift_in(rk, rv, p):
        src = jnp.clip(lane - 1, 0, Bw - 1)
        nk = jnp.where(lane > p, rk[src], rk)
        nk = jnp.where(lane == p, key, nk)
        nv = jnp.where(lane > p, rv[src], rv)
        nv = jnp.where(lane == p, val, nv)
        return nk, nv

    def case_upsert(st):
        fv = st.fat_vals.at[owner, pos_c].set(val)
        return st._replace(fat_vals=fv), jnp.bool_(False)

    def case_room(st):
        nk, nv = shift_in(run_k, run_v, pos)
        st = st._replace(fat_keys=st.fat_keys.at[owner].set(nk),
                         fat_vals=st.fat_vals.at[owner].set(nv),
                         nlen=st.nlen.at[owner].add(1),
                         n=st.n + jnp.int32(1))
        return _set_node_min(st, owner, key, preds, at_front), jnp.bool_(True)

    def case_split(st):
        st2, nid, ok = _alloc(st)
        new_min = run_k[half]
        # Splice preds for the median — strictly inside the owner's run, so
        # the level-0 predecessor is the owner itself; the new node lands
        # AFTER it, which keeps ``preds`` (head chain) valid for at_front.
        _x2, preds2, _s2, _g2 = _search_loop(*_table_gather(st), st.levels,
                                             new_min[None], 0)
        st2 = _splice_node(st2, nid, new_min, h, preds2[0], ok)
        hi_k = jnp.where(lane < Bw - half,
                         run_k[jnp.minimum(lane + half, Bw - 1)], KEY_MAX)
        hi_v = jnp.where(lane < Bw - half,
                         run_v[jnp.minimum(lane + half, Bw - 1)], NULL_VAL)
        lo_k = jnp.where(lane < half, run_k, KEY_MAX)
        lo_v = jnp.where(lane < half, run_v, NULL_VAL)
        into_lo = key < new_min                 # == new_min impossible here
        lo_ik, lo_iv = shift_in(lo_k, lo_v, pos)
        hi_ik, hi_iv = shift_in(hi_k, hi_v, pos - half)
        owner_k = jnp.where(into_lo, lo_ik, lo_k)
        owner_v = jnp.where(into_lo, lo_iv, lo_v)
        nid_k = jnp.where(into_lo, hi_k, hi_ik)
        nid_v = jnp.where(into_lo, hi_v, hi_iv)
        owner_len = jnp.where(into_lo, half + 1, half).astype(jnp.int32)
        nid_len = (Bw - half) + jnp.where(into_lo, 0, 1).astype(jnp.int32)
        fk = st2.fat_keys.at[owner].set(jnp.where(ok, owner_k, run_k))
        fk = fk.at[nid].set(jnp.where(ok, nid_k, fk[nid]), mode="drop")
        fv = st2.fat_vals.at[owner].set(jnp.where(ok, owner_v, run_v))
        fv = fv.at[nid].set(jnp.where(ok, nid_v, fv[nid]), mode="drop")
        nl = st2.nlen.at[owner].set(
            jnp.where(ok, owner_len, st2.nlen[owner]))
        nl = nl.at[nid].set(jnp.where(ok, nid_len, nl[nid]), mode="drop")
        st2 = st2._replace(fat_keys=fk, fat_vals=fv, nlen=nl,
                           n=st2.n + jnp.where(ok, 1, 0).astype(jnp.int32))
        st2 = _set_node_min(st2, owner, key, preds, ok & at_front)
        st2 = lax.cond(ok, lambda s: s,
                       lambda s: s._replace(free_top=st.free_top,
                                            bump=st.bump), st2)
        return st2, ok

    def case_first(st):
        st2, nid, ok = _alloc(st)
        st2 = _splice_node(st2, nid, key, h, preds, ok)   # preds all HEAD
        ek = jnp.full((Bw,), KEY_MAX, jnp.int32).at[0].set(key)
        ev = jnp.full((Bw,), NULL_VAL, jnp.int32).at[0].set(val)
        fk = st2.fat_keys.at[nid].set(
            jnp.where(ok, ek, st2.fat_keys[nid]), mode="drop")
        fv = st2.fat_vals.at[nid].set(
            jnp.where(ok, ev, st2.fat_vals[nid]), mode="drop")
        nl = st2.nlen.at[nid].set(
            jnp.where(ok, 1, st2.nlen[nid]), mode="drop")
        st2 = st2._replace(fat_keys=fk, fat_vals=fv, nlen=nl,
                           n=st2.n + jnp.where(ok, 1, 0).astype(jnp.int32))
        st2 = lax.cond(ok, lambda s: s,
                       lambda s: s._replace(free_top=st.free_top,
                                            bump=st.bump), st2)
        return st2, ok

    case = jnp.where(present, 0,
                     jnp.where(owner == TAIL, 3,
                               jnp.where(state.nlen[owner] < Bw, 1, 2)))
    return lax.switch(case, [case_upsert, case_room, case_split, case_first],
                      state)


def _fat_delete(state: SkipListState, key: jax.Array
                ) -> Tuple[SkipListState, jax.Array]:
    """Fat-layout delete: lane-shift out; an emptied run splices its node
    out (scalar splice-out on the node level) and frees the slot."""
    key = key.astype(jnp.int32)
    Bw = state.node_width
    owner, pos, present, preds, _x = _fat_locate(state, key)
    run_k = state.fat_keys[owner]
    run_v = state.fat_vals[owner]
    lane = jnp.arange(Bw, dtype=jnp.int32)
    src = jnp.minimum(lane + 1, Bw - 1)
    nk = jnp.where(lane >= pos,
                   jnp.where(lane == Bw - 1, KEY_MAX, run_k[src]), run_k)
    nv = jnp.where(lane >= pos,
                   jnp.where(lane == Bw - 1, NULL_VAL, run_v[src]), run_v)
    new_len = state.nlen[owner] - 1
    gone = present & (new_len == 0)
    keep = present & (new_len > 0)
    new_min = nk[0]
    L = state.levels
    lvls = jnp.arange(L, dtype=jnp.int32)
    link_out = gone & (lvls < state.height[owner])
    if state.foresight:
        fused = state.fused
        d_pair = fused[lvls, jnp.full((L,), owner), :]
        old = fused[lvls, preds, :]
        # pos == 0 deletes the owner's min: ``preds`` is exactly its
        # predecessor chain (the located key IS keys[owner]), so patch the
        # foreseen key wherever it references the owner.
        fix = keep & (pos == 0) & (old[:, 0] == owner)
        p0 = jnp.where(link_out, d_pair[:, 0], old[:, 0])
        p1 = jnp.where(link_out, d_pair[:, 1],
                       jnp.where(fix, new_min, old[:, 1]))
        fused = fused.at[lvls, preds, :].set(jnp.stack([p0, p1], axis=-1))
        state = state._replace(fused=fused)
    else:
        nxt = state.nxt
        d_ptr = nxt[lvls, jnp.full((L,), owner)]
        old = nxt[lvls, preds]
        nxt = nxt.at[lvls, preds].set(jnp.where(link_out, d_ptr, old))
        state = state._replace(nxt=nxt)
    keys = state.keys.at[owner].set(
        jnp.where(gone, KEY_MAX,
                  jnp.where(keep & (pos == 0), new_min, state.keys[owner])))
    height = state.height.at[owner].set(
        jnp.where(gone, 0, state.height[owner]))
    fk = state.fat_keys.at[owner].set(jnp.where(present, nk, run_k))
    fv = state.fat_vals.at[owner].set(jnp.where(present, nv, run_v))
    nlen = state.nlen.at[owner].set(
        jnp.where(present, new_len, state.nlen[owner]))
    free_list = state.free_list.at[state.free_top].set(
        jnp.where(gone, owner, state.free_list[state.free_top]))
    free_top = state.free_top + jnp.where(gone, 1, 0).astype(jnp.int32)
    n = state.n - jnp.where(present, 1, 0).astype(jnp.int32)
    return state._replace(keys=keys, height=height, fat_keys=fk, fat_vals=fv,
                          nlen=nlen, n=n, free_list=free_list,
                          free_top=free_top), present


# ---------------------------------------------------------------------------
# Batched (linearized) update application — the functional concurrency model
# ---------------------------------------------------------------------------

OP_READ, OP_INSERT, OP_DELETE = 0, 1, 2


def apply_ops(state: SkipListState, op_types: jax.Array, keys: jax.Array,
              vals: jax.Array) -> Tuple[SkipListState, jax.Array]:
    """Apply a linearized batch of mixed ops via ``lax.scan``.

    Returns (new_state, results[B]) where results is the op outcome
    (found / inserted / deleted as int32 0/1).  This is the functional
    analogue of a concurrent update window: the batch linearizes exactly like
    the paper's concurrent operations do.

    The scalar layout scans ``_apply_one`` over the table's planes, split
    once before the scan and stacked once after it, so no op copies the
    table.  The fat layout switches over ``insert`` / ``delete``.
    """
    ops = (op_types.astype(jnp.int32), keys.astype(jnp.int32),
           vals.astype(jnp.int32))
    if state.node_width == 1:
        def scalar_step(carry, op):
            rest, planes, r = _apply_one(*carry, *op)
            return (rest, planes), r

        (rest, planes), results = lax.scan(scalar_step, _split(state), ops)
        return _stack(rest, planes), results

    def step(st, op):
        t, k, v = op
        def do_read(s):
            r = search(s, k[None])
            return s, r.found[0].astype(jnp.int32)
        def do_ins(s):
            with jax.named_scope("insert"):
                s2, okk = insert(s, k, v)
            return s2, okk.astype(jnp.int32)
        def do_del(s):
            with jax.named_scope("delete"):
                s2, okk = delete(s, k)
            return s2, okk.astype(jnp.int32)
        return lax.switch(t, [do_read, do_ins, do_del], st)

    return lax.scan(step, state, ops)


# ---------------------------------------------------------------------------
# Introspection / invariants (used by tests and benchmarks)
# ---------------------------------------------------------------------------

def check_foresight_invariant(state: SkipListState) -> jax.Array:
    """True iff every live fused record satisfies next_key == keys[next_ptr].

    This is THE data-structure invariant Foresight adds (paper §3.1): a
    foreseen key must match the actual key of the node the pointer references.
    """
    assert state.foresight
    L, cap, _ = state.fused.shape
    ptr = state.fused[..., 0]
    fk = state.fused[..., 1]
    actual = state.keys[ptr.reshape(-1)].reshape(L, cap)
    lvls = jnp.arange(L, dtype=jnp.int32)[:, None]
    live = (state.height[None, :] > lvls)
    live = live.at[:, HEAD].set(True)
    ok = jnp.where(live, fk == actual, True)
    return jnp.all(ok)


def check_fat_invariant(state: SkipListState) -> jax.Array:
    """Fat-layout structural invariants (on top of the foresight one):

    * a live node's routing key equals its run's first lane (exact min);
    * runs are strictly ascending over their live lanes;
    * lanes past ``nlen`` hold KEY_MAX (padding is canonical);
    * live lane counts sum to ``n``; live nodes are non-empty.
    """
    assert state.node_width > 1
    cap, Bw = state.fat_keys.shape
    ids = jnp.arange(cap)
    live = (ids >= 2) & (state.height > 0)
    lane = jnp.arange(Bw)
    in_run = lane[None, :] < state.nlen[:, None]
    fk = state.fat_keys
    min_ok = jnp.all(jnp.where(live, fk[:, 0] == state.keys, True))
    sorted_ok = jnp.all(jnp.where(in_run[:, 1:],
                                  fk[:, 1:] > fk[:, :-1], True))
    pad_ok = jnp.all(jnp.where(~in_run, fk == KEY_MAX, True))
    count_ok = jnp.sum(jnp.where(live, state.nlen, 0)) == state.n
    len_ok = jnp.all(jnp.where(live, state.nlen >= 1, state.nlen == 0))
    return min_ok & sorted_ok & pad_ok & count_ok & len_ok


def sorted_live_kv(state: SkipListState) -> Tuple[jax.Array, jax.Array]:
    """Live (key, val) pairs in key order, padded to ``capacity - 2``.

    The fixed-shape compaction primitive under every split/merge rebuild
    (``core.sharded`` and ``core.rebalance_traced``): unused, deleted, and
    tail slots all hold ``KEY_MAX`` and the head ``KEY_MIN``, so a single
    argsort recovers the live run at positions ``1 .. n``; everything past
    ``state.n`` is padding.  Output shape is static, so the caller can pair
    it with a ``valid`` prefix mask and re-``build`` at the same capacity —
    the in-place relayout move that works identically eager and traced.

    Fat layout: the run-packing primitive.  All ``cap * B`` lanes flat-sort;
    sentinel and padding lanes hold ``KEY_MAX`` (the head's fat row is
    KEY_MAX too — no KEY_MIN lane exists), so the live elements are exactly
    the first ``state.n`` entries and the static output width is
    ``(cap - 2) * node_width``.  Callers must size against ``ks.shape[0]``,
    not ``cap - 2``.
    """
    cap = state.capacity
    if state.node_width > 1:
        flat_k = state.fat_keys.reshape(-1)
        flat_v = state.fat_vals.reshape(-1)
        order = jnp.argsort(flat_k)
        w = (cap - 2) * state.node_width
        return flat_k[order][:w], flat_v[order][:w]
    order = jnp.argsort(state.keys)
    return state.keys[order][1:cap - 1], state.vals[order][1:cap - 1]


def to_sorted_keys(state: SkipListState, max_n: int) -> jax.Array:
    """Walk level 0 and return keys in order (KEY_MAX padded), for tests."""
    gather = _table_gather(state)[0]

    def body(i, carry):
        x, out = carry
        ptr, fk = gather(jnp.zeros((1,), jnp.int32), x[None])
        out = out.at[i].set(fk[0])
        return ptr[0], out

    out = jnp.full((max_n,), KEY_MAX, jnp.int32)
    _, out = lax.fori_loop(0, max_n, body, (jnp.int32(HEAD), out))
    return out


# ---------------------------------------------------------------------------
# Range queries — the skiplist's signature advantage over hash indexes
# ---------------------------------------------------------------------------

@jax.named_scope("range_scan")
def range_scan(state: SkipListState, lo: jax.Array, hi: jax.Array,
               max_out: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Collect up to ``max_out`` (key, val) pairs with lo <= key < hi.

    Positions via a (batched, foresight-accelerated) search for ``lo``, then
    walks level 0.  Returns (keys [max_out], vals [max_out], count []);
    unused slots hold KEY_MAX / NULL_VAL.  This is the ordered-scan primitive
    behind the data pipeline's shard assignment and the page table's
    range-release — the workload class the paper cites skiplists for.
    """
    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)
    if state.node_width > 1:
        return _fat_range_scan(state, lo, hi, max_out)
    r = search(state, lo[None])
    x = r.preds[0, 0]                         # level-0 predecessor of lo

    keys_out = jnp.full((max_out,), KEY_MAX, jnp.int32)
    vals_out = jnp.full((max_out,), NULL_VAL, jnp.int32)

    gather = _table_gather(state)[0]

    def body(i, carry):
        x, keys_out, vals_out, count = carry
        ptr, k = gather(jnp.zeros((1,), jnp.int32), x[None])
        ptr, k = ptr[0], k[0]
        take = (k >= lo) & (k < hi)
        keys_out = keys_out.at[i].set(jnp.where(take, k, keys_out[i]))
        vals_out = vals_out.at[i].set(
            jnp.where(take, state.vals[ptr], vals_out[i]))
        count = count + jnp.where(take, 1, 0).astype(jnp.int32)
        nxt_x = jnp.where(take, ptr, x)       # stop advancing past hi
        return nxt_x, keys_out, vals_out, count

    x, keys_out, vals_out, count = lax.fori_loop(
        0, max_out, body, (x, keys_out, vals_out, jnp.int32(0)))
    return keys_out, vals_out, count


def _fat_range_scan(state: SkipListState, lo: jax.Array, hi: jax.Array,
                    max_out: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fat-layout range scan: a (node, lane) cursor walk.

    Starts at the level-0 predecessor NODE of ``lo`` (its run may straddle
    ``lo``), advances lane-by-lane, hops to the next node at the run's
    KEY_MAX padding, and stops at the tail's self-loop or past ``hi``.
    Emitted pairs compact from slot 0 (matching the scalar walk's output
    contract).  Iteration bound: <= node_width skipped lanes in the first
    node + max_out emissions + one hop per visited node.
    """
    Bw = state.node_width
    gather, per_step = _table_gather(state)
    x, _preds, _s, _g = _search_loop(gather, per_step, state.levels,
                                     lo[None], 0)
    keys_out = jnp.full((max_out,), KEY_MAX, jnp.int32)
    vals_out = jnp.full((max_out,), NULL_VAL, jnp.int32)
    bound = 2 * max_out + Bw + 4

    def body(i, carry):
        node, lane, keys_out, vals_out, count, done = carry
        lane_c = jnp.minimum(lane, Bw - 1)
        k = state.fat_keys[node, lane_c]
        v = state.fat_vals[node, lane_c]
        ptr = gather(jnp.zeros((1,), jnp.int32), node[None])[0][0]
        at_end = (k == KEY_MAX) | (lane >= Bw)
        hop = at_end & (ptr != node) & ~done
        # tail self-loop, or a LIVE lane at/past hi (padding must hop)
        stop = (at_end & (ptr == node)) | (~at_end & (k >= hi))
        take = ~done & ~at_end & (k >= lo) & (k < hi) & (count < max_out)
        idx = jnp.minimum(count, max_out - 1)
        keys_out = keys_out.at[idx].set(jnp.where(take, k, keys_out[idx]))
        vals_out = vals_out.at[idx].set(jnp.where(take, v, vals_out[idx]))
        count = count + jnp.where(take, 1, 0).astype(jnp.int32)
        done = done | stop | (count >= max_out)
        new_node = jnp.where(hop, ptr, node)
        new_lane = jnp.where(hop, 0, jnp.where(done, lane, lane + 1))
        return new_node, new_lane, keys_out, vals_out, count, done

    node0 = x[0]
    _, _, keys_out, vals_out, count, _ = lax.fori_loop(
        0, bound, body,
        (node0, jnp.int32(0), keys_out, vals_out, jnp.int32(0),
         jnp.bool_(False)))
    return keys_out, vals_out, count
