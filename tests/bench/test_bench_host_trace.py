"""The host side of a trace (``bench.host_trace``) and the program's
counters (``bench.counters``): known answers on a small recording, the
program's spans in a CPU trace of the tiny cells, and ``bench.trace``'s
numbers unchanged on the recorded chip trace."""
from __future__ import annotations

import gzip
import json
import pathlib

import pytest

from bench import counters, host_trace, spec, trace

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
ROOT = pathlib.Path(__file__).resolve().parents[2]

# Device busy 30-40 and 70-90 in a window of 0-100: idle 0-30, 40-70 and
# 90-100.  One read call (5-45) launches two programs (the add inside the
# lowering is not a launch); the first builds its program, from its start
# at 8 to its execution at 23.  One write call (50-97) builds nothing and
# launches one.
H = "/host:CPU/0"
SYNTHETIC = {
    "devices": {"/device:TPU:0": [["jit_while/while.1", 30, 40],
                                  ["jit_apply_ops/copy.10", 70, 90]]},
    "spans": [["window", 0, 100, "window", 0],
              ["get_batch", 5, 45, "read", 8],
              ["ingest", 50, 97, "write", 2]],
    "host": [["bench.window", 0, 100, H],
             ["bench.get_batch", 5, 45, H],
             ["repro.store.get_batch", 6, 44, H],
             ["repro.read.search_fast", 7, 29, H],
             ["PjitFunction(while)", 8, 28, H],
             ["lower_sharding_computation", 9, 17, H],
             ["PjitFunction(add)", 10, 12, H],
             ["backend_compile_and_load", 18, 20, H],
             ["ExecuteReplicated.__call__", 23, 27, H],
             ["shard_args", 23, 24, H],
             ["repro.store.gather_rows", 30, 43, H],
             ["PjitFunction(_take)", 31, 33, H],
             ["bench.ingest", 50, 97, H],
             ["repro.store.ingest", 51, 94, H],
             ["repro.write.apply_ops", 52, 60, H],
             ["PjitFunction(apply_ops)", 53, 59, H],
             ["ExecuteReplicated.__call__", 54, 58, H],
             ["np.asarray(jax.Array)", 61, 93, H],
             # another thread, outside every call
             ["trace_to_jaxpr_dynamic", 1, 4, "/host:CPU/1"]],
}


def test_host_trace_on_a_synthetic_recording():
    s = host_trace.reduce(SYNTHETIC)
    assert s["calls"] == {"calls": 2, "build_s": pytest.approx(15e-9),
                          "dispatches": 3}
    assert s["per_call"]["get_batch"]["dispatches"] == 2
    assert s["per_call"]["ingest"]["build_s"] == 0.0
    assert s["idle_gaps"] == [
        ["get_batch>repro.store.get_batch>repro.read.search_fast>"
         "PjitFunction(while)>lower_sharding_computation",
         pytest.approx(30e-9)],
        ["ingest>repro.store.ingest>repro.write.apply_ops>"
         "PjitFunction(apply_ops)>ExecuteReplicated.__call__",
         pytest.approx(30e-9)],
        ["ingest", pytest.approx(10e-9)]]
    # self time: the span less the spans inside it
    assert s["self_s"]["repro.store.get_batch"] == pytest.approx(3e-9)
    assert s["self_s"]["PjitFunction(while)"] == pytest.approx(6e-9)
    assert s["self_s"]["ExecuteReplicated.__call__"] == pytest.approx(7e-9)
    assert s["self_s"]["window"] == pytest.approx(13e-9)


def test_metric_readers_on_a_synthetic_recording(monkeypatch, tmp_path):
    import jax
    import jax.numpy as jnp
    from repro import obs
    read = {m: spec.reader(ROOT, m) for m in
            ("build_ms_per_call", "dispatches_per_call",
             "gathers_per_lookup", "traversal_lane_use")}
    # nothing to read: no trace, no counted read
    obs.reset()
    assert all(r({"trace": {}}) is None for r in read.values())
    monkeypatch.setattr(host_trace, "of_run",
                        lambda run, root: host_trace.reduce(SYNTHETIC))
    jax.profiler.start_trace(str(tmp_path))
    try:    # 8 keys, 30 lane-steps in 5 trips: 40 lane slots
        obs.count_search(jnp.asarray([5, 5, 5, 5, 5, 5, 0, 0], jnp.int32),
                         per_step=1)
    finally:
        jax.profiler.stop_trace()
    run = {"trace": {}}
    assert read["build_ms_per_call"](run) == pytest.approx(7.5e-6)
    assert read["dispatches_per_call"](run) == pytest.approx(1.5)
    assert read["gathers_per_lookup"](run) == pytest.approx(3.75)
    assert read["traversal_lane_use"](run) == pytest.approx(75.0)
    obs.reset()


def test_window_counters_come_from_the_program(tmp_path):
    from repro import obs
    obs.reset()
    assert counters.of_window() is None
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    try:
        obs.count_search(jnp.asarray([4, 1, 2], jnp.int32), per_step=2)
    finally:
        jax.profiler.stop_trace()
    obs.count_search(jnp.asarray([9, 9], jnp.int32), per_step=1)
    assert counters.of_window() == {"keys": 3, "lane_steps": 7,
                                    "lane_slots": 12, "gathers": 14}
    obs.reset()


@pytest.mark.parametrize("workload", ["ycsb_store_4m.ycsb_c",
                                      "kv_pages_64k.decode_churn"])
def test_program_spans_nest_inside_the_calls(run_tiny, workload):
    from repro import obs
    obs.reset()
    res = run_tiny(workload, trace=True)
    assert res["correct"], res["compared"]
    events = host_trace.load(trace.find(ROOT / ".bench_trace" / workload))
    spans = [h for h in events["host"] if h[3] == events["host"][0][3]]
    parent = host_trace._tree(spans)

    def ancestors(i):
        while parent[i] >= 0:
            i = parent[i]
            yield spans[i][0]

    program = [i for i, h in enumerate(spans)
               if h[0].startswith(host_trace.PROGRAM_PREFIX)]
    assert program
    for i in program:
        assert any(a.startswith("bench.") and a != "bench.window"
                   for a in ancestors(i)), spans[i]
    read = ("repro.read.search_fast" if workload.startswith("ycsb")
            else "repro.read.search_sharded")
    assert any(read in ancestors(i) for i, h in enumerate(spans)
               if h[0] == "lower_sharding_computation")
    # the counters read every lookup of the window
    lane_use = res["metrics"]["traversal_lane_use"]["value"]
    assert 0 < lane_use <= 100
    assert res["metrics"]["gathers_per_lookup"]["value"] > 1


def test_trace_reduce_is_unchanged_on_the_recorded_chip_trace():
    events = json.loads(gzip.decompress(
        (FIXTURES / "ycsb_c_v5e.events.json.gz").read_bytes()))
    got = trace.reduce(events)
    want = (FIXTURES / "ycsb_c_v5e.reduced.json").read_text()
    assert json.dumps({k: got[k] for k in ("window_s", "busy_s", "kinds",
                                            "ops")},
                      sort_keys=True, indent=1) + "\n" == want


def test_host_trace_on_a_recorded_chip_trace():
    """One YCSB D batch on a v5e: a jitted ``ingest`` and an eager
    ``get_batch`` whose while loop is lowered and loaded anew."""
    events = json.loads(gzip.decompress(
        (FIXTURES / "ycsb_d_v5e.host.events.json.gz").read_bytes()))
    s = host_trace.reduce(events)
    assert s["per_call"]["ingest"] == {"calls": 1, "build_s": 0.0,
                                       "dispatches": 5}
    read = s["per_call"]["get_batch"]
    assert read["dispatches"] == 64
    lowering = sum(e - b for n, b, e, _ in events["host"]
                   if n == "lower_sharding_computation") * 1e-9
    wall = sum(e - b for n, b, e, _, _ in events["spans"]
               if n == "get_batch") * 1e-9
    # the launch's load from the cache is build time, beside its lowering
    assert lowering < read["build_s"] < wall
    assert s["idle_gaps"][0][0] == (
        "get_batch>repro.store.get_batch>repro.read.search_fast>"
        "PjitFunction(while)>PjitFunction(while)>lower_sharding_computation")
