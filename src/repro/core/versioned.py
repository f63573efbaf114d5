"""Double-buffered (versioned) index — the concurrency model on TPU.

The paper's threads mutate one shared skiplist under CAS/locks.  A JAX/TPU
deployment instead *pipelines*: readers issue batched searches against a
published version ``t`` while an update batch is folded (functionally) into
version ``t+1``.  The hazard window of the paper — a traversal observing a
``(next, next_key)`` pair whose halves belong to different moments — maps to
a reader whose fused table and authoritative key table straddle a version
boundary (e.g. host-side page-table snapshots refreshed at different times).

``VersionedIndex`` makes that explicit:

* ``publish`` installs a new version (monotonic version counter).
* ``read_view(lag)`` returns a *mixed* view: fused records from version
  ``t - lag``, authoritative keys from version ``t`` — the torn-read model.
* Plain foresight search is only legal on an unmixed view; mixed views must
  go through ``search_validated`` (enforced here), mirroring the paper's
  rule that unsynchronized foresight reads require Optimistic Validation.

Slot reuse across versions is the EBR analogue (DESIGN.md §8): a version
still readable by in-flight queries keeps its arrays alive simply because
they are immutable JAX values; "reclamation" is garbage collection of
unpublished versions once readers drop them.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import skiplist as sl
from repro.core.validated import search_validated


class IndexView(NamedTuple):
    fused: jax.Array       # possibly stale fused records [L, cap, 2]
    auth_keys: jax.Array   # authoritative keys [cap]
    vals: jax.Array        # authoritative payloads [cap]
    mixed: bool            # True -> must use validated search


class VersionedIndex:
    """Host-side version manager around the functional skiplist."""

    def __init__(self, state: sl.SkipListState, history: int = 4):
        assert state.foresight, "VersionedIndex requires the foresight variant"
        self._versions: List[sl.SkipListState] = [state]
        self._history = history
        self.version = 0

    @property
    def current(self) -> sl.SkipListState:
        return self._versions[-1]

    def publish(self, state: sl.SkipListState) -> int:
        self._versions.append(state)
        if len(self._versions) > self._history:
            self._versions.pop(0)          # EBR-style reclamation
        self.version += 1
        return self.version

    def read_view(self, lag: int = 0) -> IndexView:
        lag = min(lag, len(self._versions) - 1)
        stale = self._versions[-1 - lag]
        cur = self._versions[-1]
        return IndexView(fused=stale.fused, auth_keys=cur.keys, vals=cur.vals,
                         mixed=lag > 0)

    def search(self, queries: jax.Array, *, lag: int = 0,
               use_kernel: bool = False):
        """Batched search; validated automatically iff the view is mixed."""
        view = self.read_view(lag)
        if view.mixed:
            if use_kernel:
                from repro.kernels.validated_traverse import \
                    validated_traverse
                from repro.kernels.ops import _pad, interpret_mode
                q, B = _pad(queries.astype(jnp.int32))
                node, ck = validated_traverse(view.fused, view.auth_keys, q,
                                              interpret=interpret_mode())
                node, ck = node[:B], ck[:B]
                found = ck == queries.astype(jnp.int32)
                vals = jnp.where(found, jnp.take(view.vals, node), -1)
                from repro.core.skiplist import SearchResult
                zero = jnp.int32(0)
                return SearchResult(found, vals, node,
                                    jnp.zeros((B, 1), jnp.int32), zero, zero)
            return search_validated(view.fused, view.auth_keys, view.vals,
                                    queries)
        return sl.search(self.current, queries)

    def update(self, op_types: jax.Array, keys: jax.Array,
               vals: jax.Array):
        """Fold a linearized op batch into a new version and publish it."""
        new_state, results = sl.apply_ops(self.current, op_types, keys, vals)
        self.publish(new_state)
        return results
