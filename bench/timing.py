"""Clocks of a run: compile seconds, spans around entry-point calls, and
the measured window.

Every call into the system under test runs inside ``span``: a profiler
``TraceAnnotation`` named ``bench.<call>`` that carries the call's kind
(``read``, ``write`` or ``host``) and its operation count, so a trace
alone gives the device time and the op count of each call.  Outside a
trace an annotation costs a few microseconds.
"""
from __future__ import annotations

import time

import jax


def compile_clock():
    """(seconds, compiles, cache loads) so far in this process: the time
    JAX spent getting executables, the XLA compilations among them, and
    those read back from the persistent cache instead."""
    total = [0.0, 0, 0]

    def duration(event, secs, **_):
        if event.startswith("/jax/core/compile/backend_compile"):
            total[0] += secs
            total[1] += 1

    def hit(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            total[2] += 1

    jax.monitoring.register_event_duration_secs_listener(duration)
    jax.monitoring.register_event_listener(hit)
    return lambda: (total[0], total[1] - total[2], total[2])


def span(name: str, kind: str, ops: int = 0):
    return jax.profiler.TraceAnnotation(f"bench.{name}", kind=kind, ops=ops)


class Window:
    """A measured window of ``seconds``: units run back to back (a closed
    loop), and the window ends with the last unit that started inside it.
    """

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.latencies: list[float] = []
        self.ops = 0
        self.start = self.end = 0.0

    def run(self, unit) -> None:
        """Call ``unit()`` (which returns its op count) until the window
        closes."""
        with span("window", "window"):
            self.start = t0 = time.perf_counter()
            while t0 - self.start < self.seconds:
                self.ops += unit()
                t1 = time.perf_counter()
                self.latencies.append(t1 - t0)
                t0 = t1
            self.end = t0

    @property
    def window_s(self) -> float:
        return self.end - self.start
