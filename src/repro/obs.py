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
are process-wide, like the profiler they sit beside.  ``Read`` holds a
jitted read program beside its counted twin and picks one per call.

A read over a mesh of devices (``count_mesh_read``) adds its devices'
traversals to the same counters, each device's loop running its trips
over every slot it received, bucket fill included, and counts its lanes:
``routed_lanes``, the lanes its calls routed, and ``routed_max``, the most
lanes one device received in each call times the device count, so that
their ratio is 1 for an even split and the device count when every lane
went to one device.
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

COUNTERS = ("keys", "lane_steps", "lane_slots", "gathers")
ROUTED = ("routed_lanes", "routed_max")


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(f"repro.{name}", **attrs)


def counting() -> bool:
    """Whether a profiler trace records, and so the counters add up."""
    return jax.profiler.TraceAnnotation.is_enabled()


class Read:
    """A read program and its counted twin, which also returns each lane's
    steps, last.  Both are jitted, so each is built once per pytree
    structure and shape of its arguments.

    A call runs the plain program, or while a profiler trace records the
    twin, and returns ``(outputs, steps)``, with ``steps`` None when no
    trace records.  An untraced call that builds the plain program
    builds the twin beside it, without running it, so that set-up, which
    warms every shape, leaves a traced window nothing to build."""

    def __init__(self, plain, counted):
        self.plain, self.counted = jax.jit(plain), jax.jit(counted)

    def __call__(self, *args) -> tuple:
        if counting():
            *out, steps = self.counted(*args)
            return tuple(out), steps
        built = self.plain._cache_size()
        out = self.plain(*args)
        if self.plain._cache_size() > built:
            self.counted.lower(*args).compile()
        return out, None


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
    return _wide_add(acc, x)


@functools.partial(jax.jit, static_argnums=(3,))
def _add_mesh(acc, steps, live, per_step: int):
    """``acc`` [len(COUNTERS), 2] plus one mesh call's ``steps`` [devices,
    slots] and ``live`` [devices, slots]: the keys are the live slots, and
    each device's loop runs its slowest slot's steps over all its slots."""
    steps = steps.astype(jnp.uint32)
    lane_steps = jnp.sum(jnp.where(live, steps, 0), dtype=jnp.uint32)
    trips = jnp.max(steps, axis=1, initial=jnp.uint32(0))
    x = jnp.stack([jnp.sum(live, dtype=jnp.uint32), lane_steps,
                   jnp.sum(trips, dtype=jnp.uint32)
                   * jnp.uint32(steps.shape[1]),
                   lane_steps * jnp.uint32(per_step)])
    return _wide_add(acc, x)


@jax.jit
def _add_routed(acc, live):
    """``acc`` [len(ROUTED), 2] plus one call's ``live`` [devices, slots]."""
    lanes = jnp.sum(live, axis=1, dtype=jnp.uint32)
    x = jnp.stack([jnp.sum(lanes, dtype=jnp.uint32),
                   jnp.max(lanes) * jnp.uint32(lanes.shape[0])])
    return _wide_add(acc, x)


def _wide_add(acc, x):
    lo = acc[:, 0] + x
    hi = acc[:, 1] + (lo < acc[:, 0]).astype(jnp.uint32)
    return jnp.stack([lo, hi], axis=1)


class _Counts:
    """One accumulator per group of counters (``COUNTERS``, ``ROUTED``)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.acc: dict = {}

    def reset(self) -> None:
        with self.lock:
            self.acc = {}

    def add(self, names: tuple, add, *args) -> None:
        if not counting():
            return
        with self.lock:
            acc = self.acc.get(names)
            if acc is None:
                acc = jnp.zeros((len(names), 2), jnp.uint32)
            self.acc[names] = add(acc, *args)

    def snapshot(self, names: tuple) -> dict:
        with self.lock:
            acc = self.acc.get(names)
        if acc is None:
            return dict.fromkeys(names, 0)
        words = np.asarray(acc).astype(np.uint64)
        return {name: int(lo) + (int(hi) << 32)
                for name, (lo, hi) in zip(names, words)}


_COUNTS = _Counts()


def count_search(steps, *, per_step: int) -> None:
    """Add one traversal's counts, if a profiler trace records: ``steps``
    [keys], each lane's lock-step iterations, at ``per_step`` dependent
    gathers a lane-step (1 with foresight, 2 without)."""
    _COUNTS.add(COUNTERS, _add, steps, per_step)


def count_mesh_read(steps, live, *, per_step: int) -> None:
    """Add one mesh read's traversals and routing, if a profiler trace
    records: ``steps`` [devices, slots], each device's steps per slot it
    received and searched, ``live`` [devices, slots], the slots that hold
    a lane of the call."""
    _COUNTS.add(COUNTERS, _add_mesh, steps, live, per_step)
    _COUNTS.add(ROUTED, _add_routed, live)


def reset() -> None:
    _COUNTS.reset()


def snapshot(names: tuple = COUNTERS) -> dict:
    """{counter: int} of one group of counters (the traversal's by
    default, or ``ROUTED``) over the calls traced since the last
    ``reset``."""
    return _COUNTS.snapshot(names)
