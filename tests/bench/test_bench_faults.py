"""The comparison that decides ``correct`` fails what it must: the
controls (the reference with keys compared at a lower precision), and
the timed path broken underneath a whole run."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench import control

STORE_C, STORE_D = "ycsb_store_4m.ycsb_c", "ycsb_store_4m.ycsb_d"
PAGES = "kv_pages_64k.decode_churn"


def wrong(res) -> list:
    return [k for k, c in res["compared"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("workload,system", [
    (STORE_C, control.ReducedKeyStore),
    (STORE_D, control.ReducedKeyStore),
    (PAGES, control.ReducedKeyPageTable)])
def test_control_is_not_correct(run_tiny, workload, system):
    res = run_tiny(workload, make_system=system)
    assert not res["correct"] and wrong(res), res["compared"]


def _store_fault(monkeypatch, kind):
    from repro.data.store import IndexedSampleStore
    get_batch = IndexedSampleStore.get_batch
    if kind == "state_unchanged":
        monkeypatch.setattr(IndexedSampleStore, "_apply",
                            lambda self, ops, keys, vals:
                            jnp.ones(keys.shape, jnp.int32))
    elif kind == "half_batch":
        def half(self, keys):
            h = keys.shape[0] // 2
            rows, found = get_batch(self, keys[:h])
            rest = keys.shape[0] - h
            return (jnp.concatenate([rows, rows[:rest]]),
                    jnp.concatenate([found, found[:rest]]))
        monkeypatch.setattr(IndexedSampleStore, "get_batch", half)
    elif kind == "answer_altered":
        def altered(self, keys):
            rows, found = get_batch(self, keys)
            return rows.at[0, 0].add(1), found
        monkeypatch.setattr(IndexedSampleStore, "get_batch", altered)
    elif kind == "insert_result_altered":
        ingest = IndexedSampleStore.ingest
        monkeypatch.setattr(IndexedSampleStore, "ingest",
                            lambda self, k, r: ingest(self, k, r).at[0].set(0))


@pytest.mark.parametrize("workload,kind", [
    (STORE_D, "state_unchanged"),
    (STORE_C, "half_batch"),
    (STORE_C, "answer_altered"),
    (STORE_D, "insert_result_altered")])
def test_broken_store_is_not_correct(run_tiny, monkeypatch, workload, kind):
    _store_fault(monkeypatch, kind)
    res = run_tiny(workload)
    assert not res["correct"] and wrong(res), res["compared"]


def _page_fault(monkeypatch, kind):
    from repro.serving.kvcache import PageTable
    lookup = PageTable.lookup
    if kind == "state_unchanged":
        monkeypatch.setattr(PageTable, "_apply",
                            lambda self, ops, keys, vals:
                            jnp.ones(keys.shape, jnp.int32))
    elif kind == "half_batch":
        def half(self, seqs, blocks):
            h = len(seqs) // 2
            f, p = lookup(self, seqs[:h], blocks[:h])
            rest = len(seqs) - h
            return (jnp.concatenate([f, f[:rest]]),
                    jnp.concatenate([p, p[:rest]]))
        monkeypatch.setattr(PageTable, "lookup", half)
    elif kind == "answer_altered":
        def altered(self, seqs, blocks):
            f, p = lookup(self, seqs, blocks)
            return f, p.at[0].add(1)
        monkeypatch.setattr(PageTable, "lookup", altered)


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
def test_broken_page_table_is_not_correct(run_tiny, monkeypatch, kind):
    _page_fault(monkeypatch, kind)
    res = run_tiny(PAGES)
    assert not res["correct"] and wrong(res), res["compared"]
