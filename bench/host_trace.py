"""What the host did inside each entry-point call, from the trace that
``bench.trace`` reads: the program's own spans (``repro.*``, from
``repro.obs``) and a fixed list of JAX's runtime spans, on the same clock
as the device's ops.

``load`` reads an ``.xplane.pb`` into plain lists: the programs each
device ran (its ``XLA Modules`` line: the busy time that an idle gap is
cut from, without the millions of op events ``bench.trace`` reads), the
``bench.*`` call spans as ``bench.trace`` loads them, and the host spans
this module reads, each with the thread it ran on.  ``reduce`` works on
those lists only, so a small recording checks it (``tests/bench``).  Per
``bench.*`` read and write call in the window it gives the programs
launched (the outermost ``PjitFunction`` spans, one per dispatch) and the
time JAX spent building programs: a launch that traced, lowered or
compiled its program is build time from its start to its execution
(``ExecuteReplicated.__call__``), which takes in the read of the compiled
program from the persistent cache, a step with no span of its own.  Per
span name it gives the self time (the span's time less that of the spans
inside it), and it names the device's longest idle gaps by the path of
spans open at their middle.

    python3 -m bench.host_trace <trace dir or .xplane.pb>

prints that summary as JSON.
"""
from __future__ import annotations

import functools
import json
import pathlib
import sys

import numpy as np

from bench import trace as btrace

PROGRAM_PREFIX = "repro."
DISPATCH = "PjitFunction("
# JAX's spans for building a program: tracing to a jaxpr, lowering,
# compiling, and reading a compiled program back from the persistent cache
BUILD = ("trace_to_jaxpr_dynamic", "lower_sharding_computation",
         "backend_compile_and_load", "backend_compile",
         "MeshComputation.compile")
EXECUTE = "ExecuteReplicated.__call__"
RUNTIME = BUILD + (EXECUTE, "shard_args", "np.asarray(jax.Array)",
                   "PythonRefManager::CollectGarbage")


def _kept(name: str) -> bool:
    return (name.startswith((btrace.SPAN_PREFIX, PROGRAM_PREFIX, DISPATCH))
            or name in RUNTIME)


def load(path: pathlib.Path) -> dict:
    """{"devices": {plane: [[program, start_ns, end_ns], ...]},
    "spans": [[name, start_ns, end_ns, kind, ops], ...] (``bench.*``),
    "host": [[name, start_ns, end_ns, thread], ...]}"""
    from jax.profiler import ProfileData
    devices, spans, host = {}, [], []
    for plane in ProfileData.from_file(str(path)).planes:
        if btrace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == btrace.MODULES_LINE:
                    devices[plane.name] = [
                        [btrace.short_module(e.name), float(e.start_ns),
                         float(e.end_ns)] for e in line.events]
            continue
        for t, line in enumerate(plane.lines):
            for e in line.events:
                if not _kept(e.name):
                    continue
                s, end = float(e.start_ns), float(e.end_ns)
                host.append([e.name, s, end, f"{plane.name}/{t}"])
                if e.name.startswith(btrace.SPAN_PREFIX):
                    st = dict(e.stats)
                    spans.append([e.name[len(btrace.SPAN_PREFIX):], s, end,
                                  str(st.get("kind", "")),
                                  int(st.get("ops", 0))])
    return {"devices": {p: d for p, d in devices.items() if d},
            "spans": spans, "host": host}


def _tree(spans: list) -> list:
    """The index of each span's parent (-1 at the top).  Spans on one
    thread nest, so a stack of the open spans finds it."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], -spans[i][2]))
    parent = [-1] * len(spans)
    stack: list = []
    for i in order:
        s, e = spans[i][1], spans[i][2]
        while stack and spans[stack[-1]][2] < e:
            stack.pop()
        parent[i] = stack[-1] if stack else -1
        stack.append(i)
    return parent


def _label(name: str) -> str:
    return name[len(btrace.SPAN_PREFIX):] \
        if name.startswith(btrace.SPAN_PREFIX) else name


def reduce(events: dict, n_gaps: int = 10) -> dict:
    """Per-call build time and dispatches, self time per span and named
    idle gaps, all inside the ``bench.window`` span; ``{}`` without a
    window or a device."""
    windows = [s for s in events["spans"] if s[0] == "window"]
    if not windows or not events["devices"]:
        return {}
    _, w0, w1, _, _ = windows[0]
    threads: dict = {}
    for h in events["host"]:
        if h[2] > w0 and h[1] < w1:
            threads.setdefault(h[3], []).append(h)
    build, dispatch = [], []
    self_s: dict = {}
    paths = []
    for spans in threads.values():
        parent = _tree(spans)
        # children of one span are disjoint: its self time is its own
        # length less theirs
        own = [e - s for _, s, e, _ in spans]
        for i, p in enumerate(parent):
            if p >= 0:
                own[p] -= spans[i][2] - spans[i][1]
        # each span's launch: its outermost PjitFunction ancestor (or self)
        launch = [-1] * len(spans)
        for i in sorted(range(len(spans)),
                        key=lambda i: (spans[i][1], -spans[i][2])):
            p = parent[i]
            launch[i] = launch[p] if p >= 0 and launch[p] >= 0 else (
                i if spans[i][0].startswith(DISPATCH) else -1)
        built, executed = set(), {}
        for i, (name, s, e, _) in enumerate(spans):
            self_s[_label(name)] = (self_s.get(_label(name), 0.0)
                                    + own[i] * 1e-9)
            if name in BUILD:
                build.append([s, e])
                built.add(launch[i])
            if name == EXECUTE and launch[i] >= 0:
                executed[launch[i]] = min(s, executed.get(launch[i], s))
            if launch[i] == i:
                dispatch.append(s)
        for i in built - {-1}:
            build.append([spans[i][1], executed.get(i, spans[i][2])])
        paths.append((spans, parent))
    dispatch = np.sort(np.asarray(dispatch, np.float64))
    build = btrace.Busy(btrace.union(build)) if build else None

    calls = {"calls": 0, "build_s": 0.0, "dispatches": 0}
    per_call: dict = {}
    for name, s, e, kind, _ in events["spans"]:
        if kind not in btrace.CALL_KINDS or s < w0 or e > w1:
            continue
        b = build.covered(s, e) * 1e-9 if build else 0.0
        d = int(np.searchsorted(dispatch, e, side="right")
                - np.searchsorted(dispatch, s, side="left"))
        for k in (calls, per_call.setdefault(
                name, {"calls": 0, "build_s": 0.0, "dispatches": 0})):
            k["calls"] += 1
            k["build_s"] += b
            k["dispatches"] += d

    busy = {plane: btrace.Busy(btrace.union(
        [[max(s, w0), min(e, w1)] for _, s, e in ops if e > w0 and s < w1]))
        for plane, ops in events["devices"].items()}
    gaps = busy[sorted(busy)[0]].gaps(w0, w1)
    gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")][:n_gaps]
    named = [[_path(paths, (g0 + g1) / 2), (g1 - g0) * 1e-9]
             for g0, g1 in gaps.tolist()]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "calls": calls,
        "per_call": per_call,
        "self_s": dict(sorted(self_s.items(), key=lambda x: -x[1])),
        "idle_gaps": named,
    }


def _path(paths: list, t: float) -> str:
    """The spans open at ``t`` on the thread that has a ``bench.*`` span
    open then, outermost first, joined by ``>``."""
    for spans, parent in paths:
        inner = [i for i, sp in enumerate(spans) if sp[1] <= t <= sp[2]]
        if not any(spans[i][0].startswith(btrace.SPAN_PREFIX)
                   and spans[i][0] != btrace.SPAN_PREFIX + "window"
                   for i in inner):
            continue
        deepest = max(inner, key=lambda i: (spans[i][1], -spans[i][2]))
        chain = []
        while deepest >= 0:
            chain.append(_label(spans[deepest][0]))
            deepest = parent[deepest]
        return ">".join(c for c in reversed(chain) if c != "window")
    return "harness"


def per_call(summary: dict, field: str):
    """Mean of ``field`` over the read and write calls, or ``None``."""
    c = (summary or {}).get("calls")
    if not c or not c["calls"]:
        return None
    return c[field] / c["calls"]


@functools.lru_cache(maxsize=4)
def _summary(path: str, mtime_ns: int) -> dict:
    return reduce(load(pathlib.Path(path)))


def of_run(run: dict, root: pathlib.Path) -> dict | None:
    """The summary of a traced run of ``bench.run``: that of the newest
    trace under ``<root>/.bench_trace`` whose window is the run's."""
    window = (run.get("trace") or {}).get("window_s")
    if window is None:
        return None
    found = sorted(pathlib.Path(root, ".bench_trace").glob("*/**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns, reverse=True)
    for p in found:
        s = _summary(str(p), p.stat().st_mtime_ns)
        if s and s["window_s"] == window:
            return s
    return None


def main(argv=None) -> int:
    arg = pathlib.Path((argv or sys.argv[1:])[0])
    path = arg if arg.suffix == ".pb" else btrace.find(arg)
    print(json.dumps(reduce(load(path)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
