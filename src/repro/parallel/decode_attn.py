"""Distributed flash-decode: sequence-sharded KV attention with LSE combine.

GQA decode can't shard 8 KV heads over a 16-way model axis.  Instead the KV
cache's *sequence* dim is sharded and each shard computes partial attention;
shards are combined with the numerically-exact log-sum-exp trick:

    m      = pmax(m_local)
    l      = psum(exp(m_local - m) * l_local)
    o      = psum(exp(m_local - m) * o_local) / l

Collective volume per layer is O(B·H·D) (the partial outputs) instead of the
O(B·S·Hkv·D) KV all-gather GSPMD would otherwise insert — this is one of the
§Perf levers and shows up directly in the dry-run collective-bytes term.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_distributed_decode_attn(mesh: Mesh, batch_axes, seq_axes: Tuple[str, ...]):
    """Build a drop-in replacement for ``layers.decode_attention``.

    Args:
      mesh: the device mesh.
      batch_axes: mesh axes sharding the batch dim (None / str / tuple).
      seq_axes: mesh axes sharding the KV sequence dim (combine runs here).
    """
    b = batch_axes
    s = seq_axes if len(seq_axes) > 1 else (seq_axes[0] if seq_axes else None)

    q_spec = P(b, None, None, None)           # [B,1,H,D] replicated over seq
    kv_spec = P(b, s, None, None)             # [B,S_loc,Hkv,D]
    len_spec = P(b)
    out_spec = P(b, None, None, None)

    nshards = 1
    for a in seq_axes:
        nshards *= mesh.shape[a]

    def local_attn(q, k, v, length):
        B, S_loc, Hkv, D = k.shape
        H = q.shape[2]
        rep = H // Hkv
        # Global positions of this shard's KV slots.
        idx = jnp.int32(0)
        mult = 1
        for a in reversed(seq_axes):
            idx = idx + lax.axis_index(a) * mult
            mult *= mesh.shape[a]
        pos = idx * S_loc + jnp.arange(S_loc)
        valid = pos[None, :] < jnp.reshape(length, (-1, 1))     # [B,S_loc]

        kg = jnp.repeat(k, rep, axis=2)
        vg = jnp.repeat(v, rep, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, kg,
                        preferred_element_type=jnp.float32) / math.sqrt(D)
        sc = jnp.where(valid[:, None, None, :], sc, -1e30)
        m_loc = jnp.max(sc, axis=-1)                            # [B,H,1]
        m = lax.pmax(m_loc, seq_axes)
        p = jnp.exp(sc - m[..., None])
        l_loc = jnp.sum(p, axis=-1)                             # [B,H,1]
        o_loc = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vg.dtype), vg,
                           preferred_element_type=jnp.float32)
        l = lax.psum(l_loc, seq_axes)
        o = lax.psum(o_loc, seq_axes)
        out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return out.astype(q.dtype)

    fn = jax.shard_map(local_attn, mesh=mesh,
                       in_specs=(q_spec, kv_spec, kv_spec, len_spec),
                       out_specs=out_spec, check_vma=False)

    def decode_attn(q, k_cache, v_cache, length):
        return fn(q, k_cache, v_cache, length)

    return decode_attn
