"""Spans and counters of the served paths.

``span(name, **attrs)`` is a profiler ``TraceAnnotation`` named
``repro.<name>``: it lands in the same trace, on the same clock, as the
device's ops and the caller's own annotations, and costs a few
microseconds when no trace records.  The served entry points open one at
each layer boundary, with the call's key count as ``ops``.

The read traversals count their work on the device: each lane's steps,
the lock-step iterations in which it was still searching.  While a
profiler trace records, ``count_search`` adds one call's counts into a
fixed-size device accumulator, with no host sync: the keys, the
lane-steps, the lane slots the loop ran (its trips, the slowest lane's
steps, times the keys) and the dependent gathers.  With no trace
(``counting()`` false) the entry points run the uncounted traversal and
nothing is added: the counts cover the traced calls since the last
reset.  ``snapshot``
copies them to the host (one sync), ``reset`` zeroes them.  The counters
are process-wide, like the profiler they sit beside.
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

COUNTERS = ("keys", "lane_steps", "lane_slots", "gathers")


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(f"repro.{name}", **attrs)


def counting() -> bool:
    """Whether a profiler trace records, and so the counters add up."""
    return jax.profiler.TraceAnnotation.is_enabled()


@functools.partial(jax.jit, static_argnums=(2,))
def _add(acc, steps, per_step: int):
    """``acc`` [len(COUNTERS), 2] uint32 (low word, high word) plus the
    counts of one call's ``steps`` [keys]: exact to 2**64 with no 64-bit
    types on the device."""
    steps = steps.astype(jnp.uint32)
    keys = jnp.uint32(steps.shape[0])
    trips = jnp.max(steps, initial=jnp.uint32(0))
    lane_steps = jnp.sum(steps, dtype=jnp.uint32)
    x = jnp.stack([keys, lane_steps, trips * keys,
                   lane_steps * jnp.uint32(per_step)])
    lo = acc[:, 0] + x
    hi = acc[:, 1] + (lo < acc[:, 0]).astype(jnp.uint32)
    return jnp.stack([lo, hi], axis=1)


class _Counts:
    def __init__(self):
        self.lock = threading.Lock()
        self.acc = None

    def reset(self) -> None:
        with self.lock:
            self.acc = None

    def add(self, steps, per_step: int) -> None:
        if not counting():
            return
        with self.lock:
            if self.acc is None:
                self.acc = jnp.zeros((len(COUNTERS), 2), jnp.uint32)
            self.acc = _add(self.acc, steps, per_step)

    def snapshot(self) -> dict:
        with self.lock:
            acc = self.acc
        if acc is None:
            return dict.fromkeys(COUNTERS, 0)
        words = np.asarray(acc).astype(np.uint64)
        return {name: int(lo) + (int(hi) << 32)
                for name, (lo, hi) in zip(COUNTERS, words)}


_COUNTS = _Counts()


def count_search(steps, *, per_step: int) -> None:
    """Add one traversal's counts, if a profiler trace records: ``steps``
    [keys], each lane's lock-step iterations, at ``per_step`` dependent
    gathers a lane-step (1 with foresight, 2 without)."""
    _COUNTS.add(steps, per_step)


def reset() -> None:
    _COUNTS.reset()


def snapshot() -> dict:
    """{counter: int} over the calls traced since the last ``reset``."""
    return _COUNTS.snapshot()
