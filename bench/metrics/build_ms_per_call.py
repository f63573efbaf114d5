"""``build_ms_per_call``: mean over the entry-point calls of the window of
the time JAX spent building programs inside the call, in milliseconds:
its tracing, lowering and compile spans, and each launch that built its
program from its start to its execution, which takes in the load from
the persistent cache.  The part of ``host_ms_per_call`` that a program
built once would not pay (profiler trace, ``bench.host_trace``)."""
import pathlib

from bench import host_trace

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(run: dict):
    mean = host_trace.per_call(host_trace.of_run(run, ROOT), "build_s")
    return None if mean is None else 1e3 * mean
