"""Mesh launch: the clustered ``pallas_call`` per device under shard_map.

``core.mesh_index.search_mesh`` runs the pure-``jnp`` traversal loop on
each device; this module is its kernel-backed twin.  The routing /
``all_to_all`` exchange is byte-for-byte the same (it reuses the same
private exchange helpers), but step 4 of the data path launches the
clustered scalar-prefetch ``pallas_call``
(``kernels.ops.search_kernel_sharded``) on every device's received lanes:
grid ``(C' // QBLK, K)`` per device, only routed tiles DMA'd, with
``check_vma=False`` on the ``shard_map`` because Pallas calls carry no
replication rule.

``k_shards`` must be static inside the trace (the clustered grid's K).
The default ``0`` resolves to ``min(QBLK, S_local)`` — the always-
sufficient bound from ``search_kernel_sharded``'s contract — so the mesh
kernel path is bit-identical to the single-device kernel on the same
keys.  A smaller explicit ``k_shards`` trades that guarantee for a
smaller grid: under-K lanes degrade to a SIGNALLED miss (never a wrong
hit), exactly the single-device traced contract.

Node ids come back device-global: ``device * (S_local * cap * node_width)
+ local`` (``node_width = 1`` on the scalar layout), ``-1`` for unserved
lanes — the mesh analogue of the sharded path's ``sid * cap + node``
composition, element-flat under the fat layout.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.mesh_index import (MeshShardedIndex, _chunk, _exchange_back,
                                   _exchange_out, _validate)
from repro.core.sharded import route
from repro.kernels.foresight_traverse import QBLK
from repro.kernels.ops import KernelSearchResult, search_kernel_sharded
from repro.parallel.sharding import (INDEX_AXIS, index_batch_spec,
                                     index_replicated_spec, index_state_spec)


@functools.lru_cache(maxsize=None)
def _kernel_search_fn(mesh, k_shards, max_steps):
    D = int(mesh.shape[INDEX_AXIS])

    def body(local, db, q):
        local = jax.tree.map(lambda a: a[0], local)
        did = route(db, q)
        (rq,), _, perm, starts, did_s = _exchange_out(
            did, (q,), (jnp.int32(0),), D)
        res = search_kernel_sharded(local, rq, max_steps=max_steps,
                                    cluster=True, k_shards=k_shards)
        cap = local.shard_capacity
        S = local.n_shards
        nw = local.node_width   # fat ids are element-flat: stride cap * nw
        me = lax.axis_index(INDEX_AXIS).astype(jnp.int32)
        gnode = jnp.where(res.node >= 0, me * (S * cap * nw) + res.node, -1)
        found = _exchange_back(res.found.astype(jnp.int32), perm, starts,
                               did_s, D)
        vals = _exchange_back(res.vals, perm, starts, did_s, D)
        node = _exchange_back(gnode, perm, starts, did_s, D)
        return found.astype(jnp.bool_), vals, node

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(index_state_spec(), index_replicated_spec(),
                  index_batch_spec()),
        out_specs=(index_batch_spec(), index_batch_spec(),
                   index_batch_spec()),
        check_vma=False)
    return jax.jit(fn)


def search_kernel_mesh(mx: MeshShardedIndex, queries: jax.Array, *, mesh,
                       max_steps: int = 0,
                       k_shards: int = 0) -> KernelSearchResult:
    """Kernel-backed mesh search: route, exchange, clustered launch, gather.

    Bit-identical to ``kernels.ops.search_kernel_sharded`` on an
    equivalent single-device index (and to ``mesh_index.search_mesh``),
    with node ids composed device-globally.  ``k_shards=0`` auto-selects
    the always-sufficient static ``min(QBLK, S_local)``.
    """
    D = _validate(mx, mesh)
    if k_shards == 0:
        k_shards = min(QBLK, mx.local_shards)
    q = queries.astype(jnp.int32)
    B = q.shape[0]
    (qp,), _ = _chunk((q,), B, D, (jnp.int32(0),))
    fn = _kernel_search_fn(mesh, int(k_shards), int(max_steps))
    found, vals, node = fn(mx.local, mx.device_boundaries, qp)
    return KernelSearchResult(found[:B], vals[:B], node[:B])


def dma_model_bytes_mesh(mx: MeshShardedIndex, n_queries: int) -> int:
    """Modeled WORST-CASE per-device HBM->VMEM index-tile traffic.

    Each device receives at most the full (padded) batch and its dense
    grid would DMA every local tile per block; the clustered launch's
    realized traffic is measured by the benchmark, this bound is the
    denominator it reports against.  Single-device comparison point:
    ``kernels.ops.dma_model_bytes`` on the equivalent monolithic
    ``ShardedSkipList``.
    """
    from repro.kernels.ops import shard_vmem_footprint
    D = mx.n_devices
    C = -(-max(n_queries, 1) // D)
    Bp = D * C + (-(D * C)) % QBLK
    tile = shard_vmem_footprint(mx.levels, mx.shard_capacity, mx.foresight)
    return (Bp // QBLK) * mx.local_shards * tile


__all__ = ["search_kernel_mesh", "dma_model_bytes_mesh"]
