"""``dispatches_per_call``: mean over the entry-point calls of the window
of the programs each launched, counted as JAX's outermost
``PjitFunction`` spans inside the call (profiler trace,
``bench.host_trace``)."""
import pathlib

from bench import host_trace

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(run: dict):
    return host_trace.per_call(host_trace.of_run(run, ROOT), "dispatches")
