"""Mesh-distributed key-space index — shards across devices via shard_map.

The sharded skiplist (``core.sharded``) scales one device past VMEM by
partitioning the key space into range shards; this module applies the same
move one level up and partitions the key space across the DEVICES of a 1-D
``("index",)`` mesh (``launch.mesh.make_index_mesh``), so the index scales
past a single device's HBM.  Each device owns one contiguous key slice and
holds an independent per-device ``ShardedSkipList`` for it; a global
``device_boundaries`` vector — produced by the SAME stride-partition rule
as the per-shard boundaries (``sharded.partition_boundaries``) — routes
batches with one host-free ``searchsorted``.

Data path (inside ``shard_map``, per device)
--------------------------------------------
1. route my chunk of the global batch over the replicated
   ``device_boundaries`` (destination device per lane);
2. stable-sort lanes by destination and slice per-destination segments
   (``sharded.shard_segments`` — the same primitive the single-device
   batch apply uses);
3. ``lax.all_to_all`` the route-sorted lanes so every lane lands on its
   owning device (dead bucket slots carry no-op fills);
4. run the EXISTING single-device engine on the received lanes —
   ``search_sharded`` / ``apply_ops_sharded`` here, the clustered
   ``pallas_call`` in ``kernels.mesh_launch``;
5. ``all_to_all`` the results back and inverse-permute into the original
   lane order — bit-identical to running the single-device engine on the
   whole batch.

Linearization: the arriving lanes on each device are ordered (source
device, original position) — exactly the restriction of the global batch
order to that device's key slice — and ``apply_ops_sharded``'s stable
route-sort preserves relative order within each shard, so a mixed op
batch linearizes exactly as the single-device oracle does.

Rebalancing stays DEVICE-LOCAL: each device re-levels its own shards
under its own static ceiling (``core.rebalance_traced``), and
``device_boundaries`` never move inside a traced step.  Cross-device skew
is therefore surfaced — ``apply_ops_mesh`` returns
``rebalance_traced.DeviceLoadStats`` counters — never silently absorbed;
the amortized fix is an eager host re-partition (rebuild), the mesh
analogue of ``sharded.repack``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding

from repro.core.rebalance_traced import DeviceLoadStats, cross_device_load
from repro.core.sharded import (HIGH_WATER, LOW_WATER, ShardedSkipList,
                                apply_ops_sharded, build_sharded,
                                check_sharded_invariant, partition_boundaries,
                                route, search_sharded, shard_capacity_for,
                                shard_segments, total_n)
from repro.core.skiplist import KEY_MAX, KEY_MIN, NULL_VAL, OP_READ
from repro.parallel.sharding import (INDEX_AXIS, index_batch_spec,
                                     index_replicated_spec, index_state_spec)


class MeshShardedIndex(NamedTuple):
    """``D`` per-device sharded skiplists + the global device routing array.

    Every leaf of ``local`` carries a leading ``[D]`` device axis (the
    ``shard_map`` in_spec shards exactly that axis); ``device_boundaries``
    is replicated.  Device ``d`` owns keys in ``[device_boundaries[d],
    device_boundaries[d + 1])``, with slot 0 pinned to ``KEY_MIN`` and
    dead slices degenerate at ``KEY_MAX`` — the same contract as the
    per-shard ``boundaries`` one level down.
    """

    local: ShardedSkipList       # stacked pytree — every leaf leads with [D]
    device_boundaries: jax.Array  # [D] int32 — inclusive lower key bound

    @property
    def n_devices(self) -> int:
        return self.device_boundaries.shape[0]

    @property
    def local_shards(self) -> int:
        return self.local.shards.keys.shape[1]

    @property
    def shard_capacity(self) -> int:
        return self.local.shards.keys.shape[2]

    @property
    def levels(self) -> int:
        arr = (self.local.shards.nxt if self.local.shards.nxt is not None
               else self.local.shards.fused)
        return arr.shape[2]

    @property
    def foresight(self) -> bool:
        return self.local.shards.fused is not None

    @property
    def node_width(self) -> int:
        return self.local.node_width


def route_devices(mx: MeshShardedIndex, queries: jax.Array) -> jax.Array:
    """Owning device id per query — same searchsorted as shard routing."""
    return route(mx.device_boundaries, queries)


def build_mesh_index(keys: jax.Array, vals: jax.Array, *, mesh,
                     n_devices: int, n_shards: int, capacity: int = 0,
                     levels: int = 16, foresight: bool = True, seed: int = 0,
                     node_width: int = 1) -> MeshShardedIndex:
    """Partition sorted unique int32 ``keys`` across ``n_devices`` slices.

    Each device slice holds ``m = ceil(n / D)`` keys and is built as an
    independent ``ShardedSkipList`` with ``n_shards`` range shards at a
    shared static ``capacity`` (auto-sized for ``m`` over ``n_shards``
    when 0).  The global ``device_boundaries`` come from the same
    ``partition_boundaries`` stride rule as the per-shard boundaries.
    Eager build (like ``build_sharded`` it is called once per index
    lifetime); the result feeds the jitted ``search_mesh`` /
    ``apply_ops_mesh`` data path.  Each slice is built on, and stays on,
    its own device of the ``mesh`` it will run on (``_assemble``).
    """
    D = int(n_devices)
    if D < 1:
        raise ValueError(f"n_devices must be >= 1, got {D}")
    n = keys.shape[0]
    m = max(1, -(-n // D))
    if capacity == 0:
        capacity = shard_capacity_for(m, n_shards, node_width)
    keys = keys.astype(jnp.int32)
    vals = vals.astype(jnp.int32)
    valid = jnp.ones((n,), jnp.bool_)
    pad = D * m - n
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), KEY_MAX, jnp.int32)])
        vals = jnp.concatenate([vals, jnp.full((pad,), NULL_VAL, jnp.int32)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.bool_)])

    def slice_d(d):
        return build_sharded(
            keys[d * m:(d + 1) * m], vals[d * m:(d + 1) * m],
            n_shards=n_shards, capacity=capacity, levels=levels,
            foresight=foresight, seed=seed + d * n_shards,
            valid=valid[d * m:(d + 1) * m], node_width=node_width)

    return _assemble(slice_d, D, partition_boundaries(keys, m), mesh)


def empty_mesh_index(*, mesh, n_devices: int, n_shards: int, capacity: int,
                     levels: int = 16, foresight: bool = True, seed: int = 0,
                     key_span: int = int(KEY_MAX),
                     node_width: int = 1) -> MeshShardedIndex:
    """An empty mesh index with ``[0, key_span)`` split evenly per device.

    Unlike ``build_mesh_index`` (boundaries from observed keys) the empty
    index has nothing to observe, so the device slices are a uniform
    static partition of the expected key span — callers whose keys are
    dense in ``[0, key_span)`` (e.g. the page-key space of the paged KV
    cache) get balanced devices by construction.  Each device starts as
    an ``empty_sharded``-style state built at ``n_shards`` (the per-
    device ceiling when applied with ``rebalance=True``), placed like
    ``build_mesh_index``'s slices when ``mesh`` is given.
    """
    D = int(n_devices)
    z = jnp.zeros((0,), jnp.int32)
    step = max(1, key_span // D)
    db = (jnp.arange(D, dtype=jnp.int32) * step).at[0].set(KEY_MIN)
    return _assemble(
        lambda d: build_sharded(z, z, n_shards=n_shards, capacity=capacity,
                                levels=levels, foresight=foresight,
                                seed=seed + d * n_shards,
                                node_width=node_width),
        D, db, mesh)


def _assemble(slice_d, D: int, device_boundaries: jax.Array,
              mesh) -> MeshShardedIndex:
    """Stack the ``D`` device slices ``slice_d(d)`` into one index.

    Each slice is built on, and stays on, its own device of the ``mesh``'s
    ``index`` axis: the stacked leaves carry a ``NamedSharding`` over that
    axis, so the jitted data path finds every shard where it already
    lives.
    """
    if D != mesh.shape[INDEX_AXIS]:
        raise ValueError(f"n_devices={D} but the mesh's {INDEX_AXIS!r} "
                         f"axis has {mesh.shape[INDEX_AXIS]} devices")
    devices = list(mesh.devices.reshape(-1))
    states = []
    for dev in devices:
        with jax.default_device(dev):
            st = slice_d(len(states))
        states.append(jax.device_put(st, dev))   # committed to ``dev``
    sharding = NamedSharding(mesh, index_state_spec())
    local = jax.tree.map(
        lambda *xs: jax.make_array_from_single_device_arrays(
            (D,) + xs[0].shape, sharding, [x[None] for x in xs]),
        *states)
    db = jax.device_put(device_boundaries,
                        NamedSharding(mesh, index_replicated_spec()))
    return MeshShardedIndex(local, db)


# ---------------------------------------------------------------------------
# Lane exchange: bucket by destination device, all_to_all, inverse-permute
# ---------------------------------------------------------------------------

def _exchange_out(did: jax.Array, payloads, fills, D: int):
    """Route-sort lanes, bucket per destination, ``all_to_all`` outbound.

    Returns ``(received, recv_live, perm, starts, did_sorted)`` where
    ``received[i]`` is payload ``i`` as a flat ``[D * C]`` per-device
    batch (source-major: lanes from source ``s`` occupy ``[s*C,
    (s+1)*C)``, in the source's original lane order) and ``recv_live``
    flags which received lanes are real (vs bucket fill).
    """
    C = did.shape[0]
    perm = jnp.argsort(did, stable=True)
    did_s = jnp.take(did, perm)
    starts, lens = shard_segments(did_s, D)
    idx = jnp.clip(starts[:, None] + jnp.arange(C)[None, :], 0, C - 1)
    valid = jnp.arange(C)[None, :] < lens[:, None]            # [D, C]
    received = []
    for p, fill in zip(payloads, fills):
        send = jnp.where(valid, jnp.take(p, perm)[idx], fill)
        received.append(
            lax.all_to_all(send, INDEX_AXIS, split_axis=0,
                           concat_axis=0).reshape(D * C))
    recv_live = lax.all_to_all(valid, INDEX_AXIS, split_axis=0,
                               concat_axis=0).reshape(D * C)
    return received, recv_live, perm, starts, did_s


def _exchange_back(result: jax.Array, perm: jax.Array, starts: jax.Array,
                   did_s: jax.Array, D: int) -> jax.Array:
    """Send per-lane results back to their source and restore lane order.

    ``result`` is ``[D * C]`` in the received (source-major) layout; after
    the return ``all_to_all``, row ``b`` of the ``[D, C]`` buffer holds my
    bucket-``b`` lanes' results in bucket order, so the sorted-order
    result is ``back[did_s[j], j - starts[did_s[j]]]`` and the inverse
    permutation undoes the route-sort — the round trip is the identity on
    lane order.
    """
    C = did_s.shape[0]
    back = lax.all_to_all(result.reshape(D, C), INDEX_AXIS, split_axis=0,
                          concat_axis=0)
    j = jnp.arange(C)
    res_sorted = back[did_s, j - starts[did_s]]
    return jnp.take(res_sorted, jnp.argsort(perm))


def _chunk(arrs, B: int, D: int, fills):
    """Pad each [B] array to ``D * ceil(B / D)`` lanes with its fill."""
    C = -(-max(B, 1) // D)
    out = []
    for a, fill in zip(arrs, fills):
        pad = D * C - B
        if pad:
            a = jnp.concatenate([a, jnp.full((pad,), fill, a.dtype)])
        out.append(a)
    return out, C


def _validate(mx: MeshShardedIndex, mesh) -> int:
    if INDEX_AXIS not in mesh.axis_names:
        raise ValueError(f"mesh axes {mesh.axis_names} lack the "
                         f"'{INDEX_AXIS}' axis (see launch.mesh."
                         "make_index_mesh)")
    D = int(mesh.shape[INDEX_AXIS])
    if D != mx.n_devices:
        raise ValueError(f"index was partitioned for {mx.n_devices} "
                         f"device(s) but the mesh has {D} on the "
                         f"'{INDEX_AXIS}' axis; rebuild the index for "
                         "this mesh")
    return D


# ---------------------------------------------------------------------------
# The jitted collective data paths
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _search_fn(mesh):
    D = int(mesh.shape[INDEX_AXIS])

    def body(local, db, q):
        local = jax.tree.map(lambda a: a[0], local)
        did = route(db, q)
        (rq,), _, perm, starts, did_s = _exchange_out(
            did, (q,), (jnp.int32(0),), D)
        found, vals = search_sharded(local, rq)
        found_b = _exchange_back(found.astype(jnp.int32), perm, starts,
                                 did_s, D)
        vals_b = _exchange_back(vals, perm, starts, did_s, D)
        return found_b.astype(jnp.bool_), vals_b

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(index_state_spec(), index_replicated_spec(),
                  index_batch_spec()),
        out_specs=(index_batch_spec(), index_batch_spec()),
        check_vma=False)
    return jax.jit(fn)


def search_mesh(mx: MeshShardedIndex, queries: jax.Array, *, mesh
                ) -> Tuple[jax.Array, jax.Array]:
    """Batched lookup across the whole mesh: (found, vals).

    Routes each lane to its owning device, exchanges via ``all_to_all``,
    runs the single-device ``search_sharded`` loop on the received lanes,
    and inverse-permutes results back — bit-identical to
    ``search_sharded`` on an equivalent single-device index.
    """
    D = _validate(mx, mesh)
    q = queries.astype(jnp.int32)
    B = q.shape[0]
    (qp,), _ = _chunk((q,), B, D, (jnp.int32(0),))
    found, vals = _search_fn(mesh)(mx.local, mx.device_boundaries, qp)
    return found[:B], vals[:B]


@functools.lru_cache(maxsize=None)
def _apply_fn(mesh, rebalance, high_water, low_water, max_shards,
              max_segment):
    D = int(mesh.shape[INDEX_AXIS])

    def body(local, db, ops, keys, vals, seed):
        local = jax.tree.map(lambda a: a[0], local)
        did = route(db, keys)
        (rops, rkeys, rvals), recv_live, perm, starts, did_s = _exchange_out(
            did, (ops, keys, vals),
            (jnp.int32(OP_READ), jnp.int32(0), jnp.int32(0)), D)
        # every device applies its received lanes with the SAME engine a
        # single device uses; rebalance (when on) dispatches to the traced
        # in-place drivers and stays inside this device's static ceiling
        new_local, res = apply_ops_sharded(
            local, rops, rkeys, rvals, rebalance=rebalance,
            high_water=high_water, low_water=low_water,
            max_shards=max_shards, max_segment=max_segment,
            seed=seed + lax.axis_index(INDEX_AXIS))
        res_b = _exchange_back(res, perm, starts, did_s, D)
        live = total_n(new_local).astype(jnp.int32)
        routed = jnp.sum(recv_live).astype(jnp.int32)
        return (jax.tree.map(lambda a: a[None], new_local), res_b,
                live[None], routed[None])

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(index_state_spec(), index_replicated_spec(),
                  index_batch_spec(), index_batch_spec(), index_batch_spec(),
                  index_replicated_spec()),
        out_specs=(index_state_spec(), index_batch_spec(),
                   index_batch_spec(), index_batch_spec()),
        check_vma=False)
    return jax.jit(fn)


def apply_ops_mesh(mx: MeshShardedIndex, op_types: jax.Array,
                   keys: jax.Array, vals: jax.Array, *, mesh,
                   rebalance: bool = False, high_water: float = HIGH_WATER,
                   low_water: float = LOW_WATER, max_shards: int = 0,
                   max_segment: int = 0, seed=0
                   ) -> Tuple[MeshShardedIndex, jax.Array, DeviceLoadStats]:
    """Apply a linearized mixed-op batch across the mesh.

    Lanes are routed and exchanged exactly like ``search_mesh``; each
    device then runs ``apply_ops_sharded`` on its received lanes (with
    device-local rebalancing when ``rebalance=True`` — the per-device
    shard axis is the ceiling).  Results come back in original lane
    order, bit-identical to the single-device apply; the third return is
    the :class:`~repro.core.rebalance_traced.DeviceLoadStats` counter
    pack surfacing cross-device imbalance (which device-local rebalancing
    deliberately cannot fix).
    """
    D = _validate(mx, mesh)
    ops = op_types.astype(jnp.int32)
    keys = keys.astype(jnp.int32)
    vals = vals.astype(jnp.int32)
    B = keys.shape[0]
    (opp, keyp, valp), _ = _chunk(
        (ops, keys, vals), B, D,
        (jnp.int32(OP_READ), jnp.int32(0), jnp.int32(0)))
    fn = _apply_fn(mesh, bool(rebalance), float(high_water),
                   float(low_water), int(max_shards), int(max_segment))
    new_local, res, live, routed = fn(
        mx.local, mx.device_boundaries, opp, keyp, valp,
        jnp.asarray(seed, jnp.int32))
    new_mx = MeshShardedIndex(local=new_local,
                              device_boundaries=mx.device_boundaries)
    return new_mx, res[:B], cross_device_load(live, routed)


# ---------------------------------------------------------------------------
# Invariants / introspection (eager, on the global stacked arrays)
# ---------------------------------------------------------------------------

def total_n_mesh(mx: MeshShardedIndex) -> jax.Array:
    return jnp.sum(mx.local.shards.n)


def device_live(mx: MeshShardedIndex) -> jax.Array:
    """[D] live key count per device — the load the counters report."""
    return jnp.sum(mx.local.shards.n, axis=1).astype(jnp.int32)


def check_mesh_invariant(mx: MeshShardedIndex,
                         expect_n: Optional[int] = None) -> jax.Array:
    """Per-device sharded invariants + the device-partition invariants.

    Checks every device's ``check_sharded_invariant``, the device
    boundary vector (sorted, pinned at ``KEY_MIN``), and that every live
    key sits inside its device's ``[db[d], db[d+1])`` slice — routing
    can only ever deliver in-slice keys, so a violation means the
    partition itself was corrupted.  ``expect_n`` additionally checks
    conservation of the global live count.
    """
    ok = jnp.all(jax.vmap(check_sharded_invariant)(mx.local))
    db = mx.device_boundaries
    ok = ok & (db[0] == KEY_MIN) & jnp.all(db[1:] >= db[:-1])
    keys = mx.local.shards.keys                       # [D, S, cap]
    live = (keys != KEY_MAX) & (keys != KEY_MIN)
    lo = db[:, None, None]
    hi = jnp.concatenate([db[1:],
                          jnp.full((1,), KEY_MAX, jnp.int32)])[:, None, None]
    ok = ok & jnp.all(jnp.where(live, (keys >= lo) & (keys < hi), True))
    if expect_n is not None:
        ok = ok & (total_n_mesh(mx) == jnp.asarray(expect_n, jnp.int32))
    return ok
