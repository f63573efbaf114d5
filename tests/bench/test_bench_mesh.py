"""The four-chip cell ``dbx1000_full_mesh4.ycsb_c`` at a tiny size on four
host devices, its control, and the readers of the mesh read's counters.

Four host devices exist only when
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` is set before JAX
starts, and the test process may have started it already: the cell runs
in a child process (this file run as a script), which prints the result
objects of its runs as one JSON line.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "dbx1000_full_mesh4.ycsb_c"
DEVICES = 4
TINY = {"config": {"records": 20000, "index_levels": 16},
        "mix": {"batch": 512}}


def _xla_flags() -> str:
    kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    return " ".join(kept + [
        f"--xla_force_host_platform_device_count={DEVICES}"])


@pytest.fixture(scope="module")
def runs():
    env = {**os.environ, "XLA_FLAGS": _xla_flags(), "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-6000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_cell_runs_correct_at_a_tiny_size(runs):
    res = runs["sound"]
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == DEVICES
    assert set(res["metrics"]) == {"ops_per_s", "p95_ms", "setup_s"}
    assert all(c["limit"] == 0 for c in res["compared"].values())
    assert list(res)[-1] == "compared"


def test_mesh_cell_missing_half_a_batch_is_not_correct(runs):
    res = runs["half_batch"]
    assert not res["correct"]
    assert [k for k, c in res["compared"].items()
            if c["value"] > c["limit"]], res["compared"]


def test_mesh_cell_with_keys_compared_at_a_lower_precision_is_not_correct(
        runs):
    res = runs["control"]
    assert not res["correct"]
    assert res["compared"]["row_mismatch"]["value"] > 0, res["compared"]


def test_traced_mesh_cell_reads_its_routed_lane_balance(runs):
    res = runs["traced"]
    assert res["correct"], res["compared"]
    # the CPU has no device trace: only the program's counters read here
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(m) == {"routed_lane_balance", "gathers_per_lookup",
                      "traversal_lane_use"}
    assert 1.0 <= m["routed_lane_balance"] <= DEVICES
    assert m["gathers_per_lookup"] >= 1.0
    # three of every four slots a device searches are bucket fill
    assert 0.0 < m["traversal_lane_use"] <= 100.0 / DEVICES


def _reader(name):
    from bench import spec
    return spec.reader(ROOT, name)


def test_exchange_busy_share_on_a_recording():
    from bench import trace
    events = {
        "devices": {
            "/device:TPU:0": [["jit_run/all_to_all.3", 0, 10],
                              ["jit_run/while.1", 10, 40],
                              ["jit_run/fusion", 50, 60],
                              ["jit_run/all_to_all.5", 95, 120]],
            "/device:TPU:1": [["jit_run/all-to-all.4", 5, 25],
                              ["jit_run/fusion", 30, 50]]},
        "spans": [["window", 0.0, 100.0, "window", 0]]}
    summary = trace.reduce(events)
    # busy 55 and 40 ns; exchange 10 + 5 (clipped at the window) and 20
    assert summary["busy_s"] == pytest.approx(47.5e-9)
    read = _reader("exchange_busy_share")
    assert read({"trace": summary}) == pytest.approx(100 * 17.5 / 47.5)
    no_exchange = {p: [op for op in ops if "to" not in op[0]]
                   for p, ops in events["devices"].items()}
    assert read({"trace": trace.reduce({**events,
                                        "devices": no_exchange})}) is None
    assert read({"trace": {}}) is None


def test_routed_lane_balance_on_counted_calls(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro import obs
    read = _reader("routed_lane_balance")
    obs.reset()
    assert read({}) is None
    # two calls over 4 devices of 4 slots: lanes a device 3,1,0,0 (max 3
    # x 4) then 1,1,1,1 (max 1 x 4); a device's trips are its slowest
    # slot's steps, fill included
    live = jnp.asarray([[[1, 1, 1, 0], [1, 0, 0, 0], [0] * 4, [0] * 4],
                        [[1, 0, 0, 0]] * 4], jnp.bool_)
    steps = jnp.asarray([[[5, 6, 7, 2], [4, 9, 2, 2], [2] * 4, [2] * 4],
                         [[3, 2, 2, 2]] * 4], jnp.int32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for s, lv in zip(steps, live):
            obs.count_mesh_read(s, lv, per_step=2)
    finally:
        jax.profiler.stop_trace()
    assert obs.snapshot(obs.ROUTED) == {"routed_lanes": 8, "routed_max": 16}
    assert obs.snapshot() == {"keys": 8, "lane_steps": 22 + 12,
                              "lane_slots": 4 * (7 + 9 + 2 + 2) + 4 * 12,
                              "gathers": 2 * 34}
    assert read({}) == 2.0
    assert _reader("gathers_per_lookup")({}) == 2 * 34 / 8
    assert _reader("traversal_lane_use")({}) == pytest.approx(100 * 34 / 128)
    obs.reset()
    assert read({}) is None


# ---------------------------------------------------------------------------
# The child: the cell on four host devices
# ---------------------------------------------------------------------------

def _child() -> dict:
    import jax
    import jax.numpy as jnp

    from bench import control, run
    from repro.data.store import IndexedSampleStore

    def go(seed, trace=False, make_system=None):
        return run.run_cell(ROOT, CELL, seed, 1.0, trace, jax.devices(),
                            make_system=make_system, overrides=TINY)

    out = {"sound": go(2**31 + 5), "traced": go(2**31 + 6, trace=True)}

    def reduced_keys(cfg, rows, sorted_keys):
        # ``bench.control``'s store put in the mesh store's place
        return control.ReducedKeyStore({"records": cfg.n_samples}, rows,
                                       sorted_keys, cfg.seed)
    out["control"] = go(2**31 + 8, make_system=reduced_keys)
    get_batch = IndexedSampleStore.get_batch

    def half(self, keys):
        h = keys.shape[0] // 2
        rows, found = get_batch(self, keys[:h])
        rest = keys.shape[0] - h
        return (jnp.concatenate([rows, rows[:rest]]),
                jnp.concatenate([found, found[:rest]]))
    IndexedSampleStore.get_batch = half
    out["half_batch"] = go(2**31 + 7)
    return out


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    print(json.dumps(_child()))
