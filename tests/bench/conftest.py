"""Fixtures of the benchmark's CPU rehearsal: every cell at a tiny size.

The benchmark refuses any backend but a TPU; these tests call its
``run_cell`` with the CPU devices directly, which skips only that check.
"""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SMALL_STORE = {"records": 20000, "index_levels": 16}
TINY = {
    "ycsb_store_4m.ycsb_c": {"config": SMALL_STORE, "mix": {"batch": 512}},
    "ycsb_store_4m.ycsb_d": {"config": SMALL_STORE, "mix": {"batch": 64}},
    "kv_pages_64k.decode_churn": {
        "config": {"n_pages": 2048},
        "mix": {"sessions": 8,
                "prompt_tokens": {"dist": "lognormal", "median": 128,
                                  "sigma": 1.0, "min": 16, "max": 512},
                "output_tokens": {"dist": "lognormal", "median": 16,
                                  "sigma": 1.0, "min": 8, "max": 64}}},
}


@pytest.fixture
def run_tiny(monkeypatch):
    """``run_tiny(workload, ...)`` -> the result object of one tiny run."""
    import jax

    from bench import run
    from bench.systems import page_table
    monkeypatch.setattr(page_table, "MAX_CALL", 16)

    def go(workload, *, seed=2**31 + 5, seconds=1.0, trace=False,
           make_system=None, root=ROOT, overrides=None):
        return run.run_cell(root, workload, seed, seconds, trace,
                            jax.devices(), make_system=make_system,
                            overrides=overrides or TINY[workload])
    return go
