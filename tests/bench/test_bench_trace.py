"""The reduction from a profiler trace to the per-layer metrics, checked
against a brute-force count on a synthetic trace and on a trimmed
recording of a chip trace."""
from __future__ import annotations

import gzip
import json
import pathlib

import numpy as np
import pytest

from bench import trace

FIXTURES = pathlib.Path(__file__).parent / "fixtures"

ROOT = pathlib.Path(__file__).resolve().parents[2]


def swept(intervals) -> float:
    """Length of the union of intervals, by a sweep over their ends."""
    ends = sorted([(s, 1) for s, e in intervals if e > s]
                  + [(e, -1) for s, e in intervals if e > s],
                  key=lambda x: (x[0], -x[1]))
    total, depth, since = 0.0, 0, 0.0
    for t, d in ends:
        if depth == 0 and d > 0:
            since = t
        depth += d
        if depth == 0:
            total += t - since
    return total


def brute(events: dict) -> dict:
    """Busy and per-kind device time, each union taken by a sweep of
    every op clipped to the interval asked about."""
    w = next(s for s in events["spans"] if s[0] == "window")
    lo, hi = w[1], w[2]
    devs = [np.asarray([[s, e] for _, s, e in ops], np.float64)
            for ops in events["devices"].values()]

    def busy(a, b):
        return sum(swept(np.clip(d, a, b).tolist()) for d in devs) / len(devs)

    out = {"window_s": (hi - lo) * 1e-9, "busy_s": busy(lo, hi) * 1e-9,
           "kinds": {}}
    for name, s, e, kind, ops in events["spans"]:
        if kind not in ("read", "write") or s < lo or e > hi:
            continue
        k = out["kinds"].setdefault(kind, {"calls": 0, "ops": 0,
                                           "device_s": 0.0, "wall_s": 0.0})
        k["calls"] += 1
        k["ops"] += ops
        k["wall_s"] += (e - s) * 1e-9
        k["device_s"] += busy(s, e) * 1e-9
    return out


SYNTHETIC = {
    "devices": {"/device:TPU:0": [["a", 10, 20], ["b", 15, 30],
                                  ["c", 40, 50], ["d", 90, 120]]},
    "spans": [["window", 0, 100, "window", 0],
              ["get_batch", 5, 35, "read", 10],
              ["ingest", 38, 60, "write", 2],
              ["generate", 60, 95, "host", 0]],
}


def test_reduce_synthetic_by_hand():
    r = trace.reduce(SYNTHETIC)
    want = brute(SYNTHETIC)
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["kinds"]["read"]["device_s"] == pytest.approx(
        want["kinds"]["read"]["device_s"])
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)          # 10-30, 40-50, 90-100
    assert r["kinds"]["read"]["device_s"] == pytest.approx(20e-9)
    assert r["kinds"]["write"]["device_s"] == pytest.approx(10e-9)
    assert r["gaps"][0] == ["generate", pytest.approx(40e-9)]
    assert [g[0] for g in r["gaps"]] == ["generate", "get_batch",
                                         "get_batch"]
    assert r["ops"][0] == ["b", pytest.approx(15e-9)]
    b = trace.breakdown(r)
    assert len(b["device_ops"]) == 4 and len(b["idle_gaps"]) == 3


def test_metric_readers_on_the_synthetic_trace():
    from bench import spec
    run = {"trace": trace.reduce(SYNTHETIC)}
    read = {m: spec.reader(ROOT, m) for m in
            ("device_idle_share", "read_device_us_per_op",
             "write_device_us_per_op", "host_ms_per_call")}
    assert read["device_idle_share"](run) == pytest.approx(60.0)
    assert read["read_device_us_per_op"](run) == pytest.approx(20e-3 / 10)
    assert read["write_device_us_per_op"](run) == pytest.approx(10e-3 / 2)
    # (30 - 20) + (22 - 10) ns of host time over two calls
    assert read["host_ms_per_call"](run) == pytest.approx(11e-6)
    assert read["write_device_us_per_op"]({"trace": {}}) is None


def test_union_merges_nested_and_touching_intervals():
    got = trace.union([[5, 9], [0, 3], [1, 2], [3, 4], [8, 12]])
    assert got.tolist() == [[0, 4], [5, 12]]


def _recorded(name: str) -> dict:
    path = FIXTURES / name
    if name.endswith(".xplane.pb"):
        return trace.load(path)
    return json.loads(gzip.decompress(path.read_bytes()))


@pytest.mark.parametrize("name", sorted(
    p.name for p in FIXTURES.iterdir()
    if p.name.endswith((".events.json.gz", ".xplane.pb"))))
def test_reduce_recorded_chip_trace(name):
    events = _recorded(name)
    assert events["devices"] and any(s[0] == "window"
                                     for s in events["spans"])
    got, want = trace.reduce(events), brute(events)
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert set(got["kinds"]) == set(want["kinds"])
    for kind, k in want["kinds"].items():
        for field, v in k.items():
            assert got["kinds"][kind][field] == pytest.approx(v, rel=1e-9)
    assert 0 < got["busy_s"] <= got["window_s"]
    # every op is named after the program it ran in
    assert all("/" in n and not n.startswith("?/") for n, _ in got["ops"])
