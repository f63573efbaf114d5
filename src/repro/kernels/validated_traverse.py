"""Pallas kernel for Optimistic-Validation search (paper Algorithm 3).

The torn-read-safe traversal as a TPU kernel: the (possibly stale) fused
table AND the authoritative key table are both VMEM-resident; upper levels
advance on the foreseen key but validate against the authoritative key
before committing; level 0 ignores foresight entirely.  Mirrors
``repro.core.validated.search_validated`` bit-exactly (tested in
tests/test_kernels_validated.py across shapes and corruption rates).

This kernel is the serving-plane fast path for *mixed-view* reads
(VersionedIndex.read_view(lag>0)): pipelined queries against a stale fused
snapshot validated against fresh keys — the paper's concurrency story at
version granularity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.foresight_traverse import (QBLK, ROW, _LANE_SPEC,
                                              _fused_gather, _fused_plane,
                                              _gather_rows, _lanes, _launch,
                                              _pick, _plane, _row_padded)


def _validated_kernel(q_ref, fused_ref, keys_ref, node_ref, key_ref,
                      *scratch, levels: int, cap: int, max_steps: int):
    q = q_ref[...]                                  # [1, QBLK]
    fused_gather = _fused_gather(fused_ref, cap, scratch)

    def auth(ptr):                                  # validation gather
        rows_t = _gather_rows(keys_ref, ptr >> 7, scratch)[0]
        return _pick(rows_t, ptr & (ROW - 1))

    x = jnp.zeros_like(q)
    lvl = jnp.full_like(q, levels - 1)

    def body(_, carry):
        x, lvl = carry
        active = lvl >= 0
        at0 = lvl == 0
        ptr, fk = fused_gather(jnp.maximum(lvl, 0), x)   # fused gather (pair)
        real = auth(ptr)
        # Alg. 3: upper levels advance iff foreseen AND validated;
        # level 0 trusts only the authoritative key.
        go = active & (real < q) & (at0 | (fk < q))
        x = jnp.where(go, ptr, x)
        lvl = jnp.where(go | ~active, lvl, lvl - 1)
        return x, lvl

    x, lvl = lax.fori_loop(0, max_steps, body, (x, lvl))
    cand, _ = fused_gather(jnp.zeros_like(q), x)    # level-0 successor
    node_ref[...] = cand
    key_ref[...] = auth(cand)


@functools.partial(jax.jit, static_argnames=("max_steps", "interpret"))
def validated_traverse(fused: jax.Array, auth_keys: jax.Array,
                       queries: jax.Array, *, max_steps: int = 0,
                       interpret: bool):
    """Batched validated search. Returns (node[B], cand_key[B]).

    ``fused`` may carry arbitrarily corrupt foreseen keys; results are
    exact w.r.t. ``auth_keys`` + the pointer structure.
    """
    L, cap, _ = fused.shape
    B = queries.shape[0]
    assert B % QBLK == 0, "pad queries to a multiple of QBLK"
    if max_steps == 0:
        max_steps = 4 * L + 16
    tab, keys_p = _fused_plane(fused), _plane(auth_keys)
    kernel = functools.partial(_validated_kernel, levels=L,
                               cap=_row_padded(cap), max_steps=max_steps)
    in_specs = [_LANE_SPEC, pl.BlockSpec(tab.shape, lambda *_: (0, 0)),
                pl.BlockSpec(keys_p.shape, lambda *_: (0, 0))]
    return _launch(kernel, (B // QBLK,), in_specs,
                   [_lanes(queries), tab, keys_p], interpret=interpret)
