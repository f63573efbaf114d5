"""Every cell at a tiny size on the CPU: generators, system, reference
and metric arithmetic; a cell added as files only; the refusal without
a TPU."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]


CELLS = ["ycsb_store_4m.ycsb_c", "kv_pages_64k.decode_churn",
         "ycsb_store_4m.ycsb_d"]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct_at_a_tiny_size(run_tiny, workload):
    res = run_tiny(workload)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])}
    # the CPU has no allocator statistics, so no bytes per key here
    assert set(res["metrics"]) == want - {"hbm_bytes_per_key"}
    assert res["metrics"]["ops_per_s"]["value"] > 0
    assert list(res)[-1] == "compared"
    assert all(c["limit"] == 0 for c in res["compared"].values())


def test_every_benchmark_entry_resolves_to_files():
    from bench import spec
    b = spec.load(ROOT)
    for cell in b["workloads"]:
        _, _, config, mix = spec.resolve(ROOT, cell["name"])
        assert config["system"] == mix["system"]
        for m in (spec.cell_metrics(b, cell["name"], "end_to_end")
                  + spec.cell_metrics(b, cell["name"], "per_layer")):
            assert callable(spec.reader(ROOT, m["name"]))
    for c in b["configs"]:
        assert set(c["reduced"]) <= set(
            json.loads((ROOT / c["file"]).read_text())["reduced"])


def test_a_cell_config_mix_and_metric_added_as_files(run_tiny, tmp_path):
    """A later PR adds a cell with files and entries only."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((ROOT / "bench/configs/ycsb_store_4m.json")
                        .read_text())
    config.update(records=3000, index_levels=14)
    (tmp_path / "bench/configs/tiny_store.json").write_text(
        json.dumps(config))
    mix = json.loads((ROOT / "bench/traffic/ycsb_c.json").read_text())
    mix.update(batch=128, request_distribution="uniform")
    (tmp_path / "bench/traffic/uniform_reads.json").write_text(
        json.dumps(mix))
    for m in spec["end_to_end"]:
        shutil.copy(ROOT / f"bench/metrics/{m['name']}.py",
                    tmp_path / "bench/metrics")
    (tmp_path / "bench/metrics/units_done.py").write_text(
        "def read(run):\n    return float(len(run['latencies_s']))\n")
    spec["configs"].append({"name": "tiny_store", "source": "test",
                            "file": "bench/configs/tiny_store.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny_store.uniform_reads",
                              "config": "tiny_store",
                              "traffic": "uniform_reads", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "units_done", "unit": "units",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["tiny_store.uniform_reads"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    res = run_tiny("tiny_store.uniform_reads", root=tmp_path,
                   overrides={"config": {}})
    assert res["correct"], res["compared"]
    assert res["metrics"]["units_done"]["value"] >= 1
    assert "p95_ms" not in res["metrics"]


def test_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "ycsb_store_4m.ycsb_c", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 3, p.stderr
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_peaks_are_keyed_by_device_kind():
    from bench import spec
    v5e = spec.peaks(ROOT, "TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v4", "source"):
        with pytest.raises(KeyError):
            spec.peaks(ROOT, kind)


def test_unknown_workload_is_refused():
    from bench import run
    assert run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"]
                    ) == 2


def test_paths_hold_only_the_benchmark():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert spec["paths"] == ["bench", "tests/bench"]
    assert spec["command"][:3] == ["python3", "-m", "bench.run"]
    for p in spec["paths"]:
        assert (ROOT / p).is_dir()
    assert all(pathlib.Path(c["file"]).parts[0] == "bench"
               for c in spec["configs"])
