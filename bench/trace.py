"""From a profiler trace to the per-layer numbers: device busy time, the
device time inside each of the harness's call spans, idle gaps and the
device ops that took the most time.

``load`` reads an ``.xplane.pb`` into plain lists: the op intervals of
each TPU (its ``XLA Ops`` line; an op is named ``<program>/<op>``, from
the ``XLA Modules`` event it runs in) and the harness's ``bench.*`` spans
from the host, with the kind and op count each span carries.  ``reduce``
works on those lists only, so a trimmed recording of them checks it
(``tests/bench``).  Device and host events share the trace's clock; each
call is run to completion inside its span, so the device work inside a
span is that call's.
"""
from __future__ import annotations

import bisect
import pathlib
import re

import numpy as np

DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
CALL_KINDS = ("read", "write")


def find(trace_dir: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: pathlib.Path) -> dict:
    """{"devices": {plane: [[name, start_ns, end_ns], ...]},
    "spans": [[name, start_ns, end_ns, kind, ops], ...]}"""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.end_ns, short_module(e.name))
                             for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in modules]
            ops = []
            for e in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                inside = i >= 0 and e.start_ns < modules[i][1]
                ops.append([f"{modules[i][2] if inside else '?'}/"
                            f"{short_op(e.name)}",
                            float(e.start_ns), float(e.end_ns)])
            if ops:
                devices[plane.name] = ops
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        st = dict(e.stats)
                        spans.append([e.name[len(SPAN_PREFIX):],
                                      float(e.start_ns), float(e.end_ns),
                                      str(st.get("kind", "")),
                                      int(st.get("ops", 0))])
    return {"devices": devices, "spans": spans}


def short_module(name: str) -> str:
    """``jit_apply_ops(1234...)`` -> ``jit_apply_ops``."""
    return re.sub(r"\(\d+\)$", "", name)


def short_op(name: str) -> str:
    """``%while.40 = (s32[] ...) while(...)`` -> ``while.40``."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> np.ndarray:
    """Merged, sorted intervals as an [n, 2] array."""
    iv = np.asarray(sorted(intervals), np.float64).reshape(-1, 2)
    if not len(iv):
        return iv
    # an interval starts a new run where it begins after every earlier end
    run_end = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > run_end[:-1]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(iv) - 1)
    return np.stack([iv[idx, 0], run_end[last]], axis=1)


class Busy:
    """One device's merged op intervals, with O(log n) coverage queries."""

    def __init__(self, merged: np.ndarray):
        self.start, self.end = merged[:, 0], merged[:, 1]
        self.cum = np.concatenate([[0.0], np.cumsum(self.end - self.start)])

    @property
    def total(self) -> float:
        return float(self.cum[-1])

    def covered(self, lo: float, hi: float) -> float:
        """Length of [lo, hi] that the intervals cover."""
        i = int(np.searchsorted(self.end, lo, side="right"))
        j = int(np.searchsorted(self.start, hi, side="left"))
        if j <= i:
            return 0.0
        total = self.cum[j] - self.cum[i]
        total -= max(0.0, lo - self.start[i])
        total -= max(0.0, self.end[j - 1] - hi)
        return float(total)

    def gaps(self, lo: float, hi: float) -> np.ndarray:
        """The idle [start, end] intervals inside [lo, hi]."""
        edges = np.concatenate([[lo], np.stack([self.start, self.end],
                                               axis=1).ravel(), [hi]])
        g = edges.reshape(-1, 2)
        return g[g[:, 1] > g[:, 0]]


def reduce(events: dict, n_gaps: int = 10) -> dict:
    """Busy and window seconds, per-kind and per-call device time, the
    longest idle gaps and op totals, all inside the ``bench.window``
    span."""
    windows = [s for s in events["spans"] if s[0] == "window"]
    if not windows or not events["devices"]:
        return {}
    _, w0, w1, _, _ = windows[0]
    busy = {}
    op_time: dict = {}
    for plane, ops in events["devices"].items():
        busy[plane] = Busy(union([[max(s, w0), min(e, w1)]
                                  for _, s, e in ops if e > w0 and s < w1]))
        for name, s, e in ops:
            if e > w0 and s < w1:
                op_time[name] = op_time.get(name, 0.0) + (min(e, w1)
                                                          - max(s, w0))
    n_dev = len(busy)
    kinds: dict = {}
    per_call: dict = {}
    for name, s, e, kind, ops in events["spans"]:
        if kind not in CALL_KINDS or s < w0 or e > w1:
            continue
        dev = sum(b.covered(s, e) for b in busy.values()) / n_dev
        for key, d in ((kind, kinds), (name, per_call)):
            k = d.setdefault(key, {"calls": 0, "ops": 0, "wall_s": 0.0,
                                   "device_s": 0.0})
            k["calls"] += 1
            k["ops"] += ops
            k["wall_s"] += (e - s) * 1e-9
            k["device_s"] += dev * 1e-9
    # the longest idle gaps of the first device, each named by the
    # innermost span open at its middle
    gaps = busy[sorted(busy)[0]].gaps(w0, w1)
    gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")][:n_gaps]
    inner = sorted((s for s in events["spans"] if s[0] != "window"),
                   key=lambda s: s[2] - s[1])
    named = []
    for g0, g1 in gaps.tolist():
        mid = (g0 + g1) / 2
        owner = next((s[0] for s in inner if s[1] <= mid <= s[2]), "harness")
        named.append([owner, (g1 - g0) * 1e-9])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(b.total for b in busy.values()) / n_dev * 1e-9,
        "kinds": kinds,
        "calls": per_call,
        "ops": sorted(([n, t * 1e-9 / n_dev] for n, t in op_time.items()),
                      key=lambda x: -x[1]),
        "gaps": named,
    }


def breakdown(summary: dict) -> dict:
    """The result line's ``breakdown``: the ten device ops that took most
    time and the ten longest idle gaps, by what the host was doing."""
    return {"device_ops": summary.get("ops", [])[:10],
            "idle_gaps": summary.get("gaps", [])[:10]}
