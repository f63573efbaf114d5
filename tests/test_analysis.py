"""Tests for the static-analysis suite (src/repro/analysis).

Fixture modules in tests/fixtures_analysis/ contain known violations (they
are parsed, never imported); each rule must fire on its fixture and stay
quiet on the annotated/compliant variants.  The clean-tree tests assert
the shipped repo passes its own gate: zero unsuppressed lint findings,
zero trace-audit findings on the public entry points, and kernel-budget
findings fully covered by analysis_baseline.json.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = Path(__file__).resolve().parents[1]
FIXTURES = ("tests/fixtures_analysis",)

from repro.analysis.baseline import (apply_baseline, load_baseline,
                                     write_baseline)
from repro.analysis.findings import RULES, Finding
from repro.analysis.kernel_budget import (TOTAL_VMEM_BYTES,
                                          VMEM_BUDGET_BYTES, BlockCapture,
                                          LaunchCapture, check_launch,
                                          max_capacity_under_budget,
                                          tile_bytes)
from repro.analysis.lint import run_lint


def lint_fixtures():
    return run_lint(REPO, src_dirs=FIXTURES, extra_seeds=())


def by_rule(findings, rule, suppressed=False):
    return [f for f in findings
            if f.rule == rule and f.suppressed == suppressed]


# ---------------------------------------------------------------------------
# findings model
# ---------------------------------------------------------------------------

def test_finding_key_is_line_independent():
    a = Finding("HOST-ESCAPE", "p.py", 10, "f", "m1")
    b = Finding("HOST-ESCAPE", "p.py", 99, "f", "m2")
    assert a.key == b.key == "HOST-ESCAPE|p.py|f"


def test_every_emitted_rule_is_registered():
    for f in lint_fixtures():
        assert f.rule in RULES


# ---------------------------------------------------------------------------
# AST lint rules on the fixture tree
# ---------------------------------------------------------------------------

def test_host_escape_fires_on_fixture():
    hits = by_rule(lint_fixtures(), "HOST-ESCAPE")
    syms = {f.symbol for f in hits}
    assert "traced_escape" in syms          # int() + np.asarray under jit
    assert "_helper" in syms                # reachable through the seed
    # eager-only helper is NOT traced-reachable -> not flagged
    assert "eager_only" not in syms


def test_host_escape_messages_name_the_reason():
    hits = by_rule(lint_fixtures(), "HOST-ESCAPE")
    assert any("traced-reachable" in f.message for f in hits)


def test_silent_degrade_fires_and_spares_loud_handlers():
    hits = by_rule(lint_fixtures(), "SILENT-DEGRADE")
    syms = {f.symbol for f in hits}
    assert "quiet_fallback" in syms
    assert "quiet_jax_error" in syms        # jax error class = device ctx
    assert "loud_fallback" not in syms      # warns
    assert "reraising" not in syms          # raises


def test_interpret_plumb_fires_on_missing_and_hardcoded():
    hits = by_rule(lint_fixtures(), "INTERPRET-PLUMB")
    syms = {f.symbol for f in hits}
    assert "launch_missing" in syms
    assert "launch_hardcoded" in syms
    assert "launch_threaded" not in syms    # caller-controlled flag


def test_trace_ok_suppression_line_and_def_level():
    fs = [f for f in lint_fixtures()
          if f.path.endswith("suppressed_ok.py")]
    assert fs, "suppression fixture produced no findings at all"
    assert all(f.suppressed for f in fs)
    assert {f.symbol for f in fs} == {"line_suppressed", "def_suppressed"}
    assert all(f.reason for f in fs)


# ---------------------------------------------------------------------------
# kernel budget checks on synthetic launches
# ---------------------------------------------------------------------------

def _launch(blocks, grid=(4,), nsp=0, aliases=None, name="k"):
    return LaunchCapture(kernel_name=name, grid=grid, blocks=blocks,
                         num_scalar_prefetch=nsp, aliases=aliases or {},
                         interpret=True)


def _blk(shape, imap, oshape=None, out=False, label="in[0]"):
    return BlockCapture(block_shape=shape, index_map=imap,
                        operand_shape=oshape or shape, dtype_bytes=4,
                        is_output=out, label=label)


def test_vmem_budget_fires_on_oversized_tile():
    big = 4 * 1024 * 1024                    # 16 MiB in int32 elements
    cap = _launch([_blk((1, big), lambda i: (i, 0), oshape=(4, big))])
    rules = {f.rule for f in check_launch(cap)}
    assert "VMEM-BUDGET" in rules


def test_vmem_budget_double_buffer_vs_pinned():
    # a tile within the budget and over a quarter of the total: x1
    # (pinned) fits the total; x2 (streamed) with two of them would not —
    # the index_map decides which model applies
    n = min(VMEM_BUDGET_BYTES, TOTAL_VMEM_BYTES * 7 // 16) // 4
    pinned = _launch([_blk((1, n), lambda i: (0, 0), oshape=(4, n)),
                      _blk((1, n), lambda i: (0, 0), oshape=(4, n),
                           out=True, label="out[0]")])
    assert not [f for f in check_launch(pinned) if f.rule == "VMEM-BUDGET"]
    streamed = _launch([_blk((1, n), lambda i: (i, 0), oshape=(4, n)),
                        _blk((1, n), lambda i: (i, 0), oshape=(4, n),
                             out=True, label="out[0]")])
    hits = [f for f in check_launch(streamed) if f.rule == "VMEM-BUDGET"]
    assert hits and "double-buffered" in hits[0].message


def test_grid_rank_fires_on_rank_mismatch():
    cap = _launch([_blk((8, 8), lambda i: (i,), oshape=(32, 8))])
    hits = [f for f in check_launch(cap) if f.rule == "GRID-RANK"]
    assert hits and "rank" in hits[0].message


def test_grid_rank_fires_on_arity_mismatch():
    cap = _launch([_blk((8, 8), lambda i, j: (i, j), oshape=(32, 8))],
                  grid=(4,))
    hits = [f for f in check_launch(cap) if f.rule == "GRID-RANK"]
    assert hits and "arity" in hits[0].message


def test_alias_hazard_fires_on_diverging_index_maps():
    ins = _blk((8, 8), lambda i: (i, 0), oshape=(32, 8))
    outs = _blk((8, 8), lambda i: (3 - i, 0), oshape=(32, 8),
                out=True, label="out[0]")
    cap = _launch([ins, outs], aliases={0: 0})
    hits = [f for f in check_launch(cap) if f.rule == "ALIAS-HAZARD"]
    assert hits and "write-after-read" in hits[0].message
    # identical maps -> in-place update is safe
    ok = _launch([ins, _blk((8, 8), lambda i: (i, 0), oshape=(32, 8),
                            out=True, label="out[0]")], aliases={0: 0})
    assert not [f for f in check_launch(ok) if f.rule == "ALIAS-HAZARD"]


def test_dma_skip_fires_on_non_coalesced_padding_slot():
    import numpy as np
    bs = np.asarray([[0, 1], [1, 0]], np.int32)   # j=1,k=1 padding -> 0
    nd = np.asarray([2, 1], np.int32)
    blk = _blk((1, 8), lambda j, k, bs_, nd_: (bs_[j, k], 0),
               oshape=(2, 8))
    cap = _launch([blk], grid=(2, 2), nsp=2)
    hits = [f for f in check_launch(cap, prefetch=(bs, nd), ndist=nd)
            if f.rule == "DMA-SKIP"]
    assert hits and "resident" in hits[0].message
    # coalesced plan (padding repeats the last shard) is clean
    bs_ok = np.asarray([[0, 1], [1, 1]], np.int32)
    hits_ok = [f for f in check_launch(cap, prefetch=(bs_ok, nd), ndist=nd)
               if f.rule == "DMA-SKIP"]
    assert not hits_ok


def test_capture_spy_records_real_pallas_launch():
    import jax.numpy as jnp
    from repro.analysis.kernel_budget import capture_pallas_calls
    import importlib
    ft = importlib.import_module("repro.kernels.foresight_traverse")
    import jax
    jax.clear_caches()
    caps = []
    fused = jnp.zeros((4, 64, 2), jnp.int32)
    q = jnp.zeros((ft.QBLK,), jnp.int32)
    with capture_pallas_calls(caps, capture_only=True):
        ft.foresight_traverse(fused, q, interpret=True)
    assert len(caps) == 1
    cap = caps[0]
    assert cap.kernel_name == "_foresight_kernel"
    assert cap.interpret is not None        # the wrapper threads the flag
    assert any(b.block_shape for b in cap.blocks)
    assert not check_launch(cap), "tiny launch must be clean"


# ---------------------------------------------------------------------------
# canonical estimator
# ---------------------------------------------------------------------------

def test_tile_bytes_matches_builder_formula():
    import repro.kernels.ops as kops
    for levels, cap, fg in [(16, 1 << 14, True), (16, 1 << 14, False),
                            (4, 64, True), (20, 1 << 16, False)]:
        assert tile_bytes(levels, cap, fg) == \
            kops.shard_vmem_footprint(levels, cap, fg)
    assert kops.VMEM_BUDGET_BYTES == VMEM_BUDGET_BYTES
    assert VMEM_BUDGET_BYTES < TOTAL_VMEM_BYTES


def test_max_capacity_under_budget_is_tight():
    for levels in (4, 16, 20):
        for fg in (True, False):
            cap = max_capacity_under_budget(levels, fg)
            assert tile_bytes(levels, cap, fg) <= VMEM_BUDGET_BYTES
            assert tile_bytes(levels, cap * 2, fg) > VMEM_BUDGET_BYTES


# ---------------------------------------------------------------------------
# baseline ratchet
# ---------------------------------------------------------------------------

def test_baseline_roundtrip(tmp_path):
    f1 = Finding("VMEM-BUDGET", "k", 0, "a", "m")
    f2 = Finding("VMEM-BUDGET", "k", 0, "a", "m again")
    f3 = Finding("GRID-RANK", "k", 0, "b", "m")
    p = tmp_path / "b.json"
    write_baseline(p, [f1, f2])
    base = load_baseline(p)
    assert base[f1.key]["count"] == 2
    # same two match; a third same-key finding and a new rule are NEW
    baselined, new, stale = apply_baseline([f1, f2, f2, f3], base)
    assert len(baselined) == 2
    assert {f.key for f in new} == {f2.key, f3.key}
    assert not stale
    # a fixed finding leaves unconsumed budget -> the key is stale (the
    # baseline over-counts and should be ratcheted down)
    _, _, stale2 = apply_baseline([f1], base)
    assert stale2 == [f1.key]
    _, _, stale3 = apply_baseline([], base)
    assert stale3 == [f1.key]


def test_suppressed_findings_bypass_baseline():
    s = Finding("HOST-ESCAPE", "p", 1, "f", "m", suppressed=True,
                reason="why")
    baselined, new, stale = apply_baseline([s], {})
    assert not baselined and not new and not stale


# ---------------------------------------------------------------------------
# CLI gate
# ---------------------------------------------------------------------------

def _run_cli(root, *extra):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--passes", "lint",
         "--root", str(root), *extra],
        capture_output=True, text=True, env=env)


def test_cli_nonzero_on_violation_zero_after_baseline(tmp_path):
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "import jax\nimport jax.numpy as jnp\n\n"
        "@jax.jit\ndef f(x):\n    return x + int(jnp.max(x))\n")
    r = _run_cli(tmp_path, "--baseline", str(tmp_path / "b.json"))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "HOST-ESCAPE" in r.stdout
    r2 = _run_cli(tmp_path, "--baseline", str(tmp_path / "b.json"),
                  "--update-baseline")
    assert r2.returncode == 0
    r3 = _run_cli(tmp_path, "--baseline", str(tmp_path / "b.json"))
    assert r3.returncode == 0, r3.stdout + r3.stderr


def test_cli_report_schema(tmp_path):
    out = tmp_path / "rep.json"
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--passes", "lint",
         "--baseline", str(REPO / "analysis_baseline.json"),
         "--report", str(out), "-q"],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.loads(out.read_text())
    assert rep["suite"] == "repro.analysis"
    assert set(rep["rules"]) == set(RULES)
    assert rep["totals"]["new"] == 0


# ---------------------------------------------------------------------------
# clean-tree gates
# ---------------------------------------------------------------------------

def test_clean_tree_lint_zero_unsuppressed():
    fs = run_lint(REPO)
    new = [f for f in fs if not f.suppressed]
    assert not new, "\n".join(f.render() for f in new)


@pytest.mark.slow
def test_clean_tree_trace_audit_zero_findings():
    from repro.analysis.trace_audit import run_trace_audit
    fs, audited = run_trace_audit()
    assert not fs, "\n".join(f.render() for f in fs)
    # the ISSUE's acceptance list is covered
    names = " ".join(audited)
    assert "search_kernel_sharded" in names
    assert "watermark_rebalance_traced" in names
    assert "exhaustion_guard_traced" in names
    assert "PageTable._apply" in names


@pytest.mark.slow
def test_clean_tree_kernel_budget_fully_baselined():
    from repro.analysis.kernel_budget import probe_repo_kernels
    fs, checked = probe_repo_kernels()
    base = load_baseline(REPO / "analysis_baseline.json")
    _, new, _ = apply_baseline(fs, base)
    assert not new, "\n".join(f.render() for f in new)
    assert {"_foresight_kernel", "_base_kernel",
            "_foresight_sharded_kernel", "_base_sharded_kernel",
            "_foresight_clustered_kernel", "_base_clustered_kernel",
            "_validated_kernel"} <= set(checked)


# ---------------------------------------------------------------------------
# AUDIT-GAP: the trace-audit entry-point list must cover every public jit
# ---------------------------------------------------------------------------

def test_audit_gap_fires_on_unlisted_public_jit(tmp_path):
    from repro.analysis.trace_audit import audit_coverage
    pkg = tmp_path / "src" / "repro" / "core"
    pkg.mkdir(parents=True)
    (pkg / "newapi.py").write_text(
        "import jax\n\n"
        "@jax.jit\ndef shiny_public_path(x):\n    return x\n\n"
        "@jax.jit\ndef _private_path(x):\n    return x\n")
    fs = audit_coverage(str(tmp_path))
    gaps = [f for f in fs if f.rule == "AUDIT-GAP"]
    assert [f.symbol for f in gaps] == ["shiny_public_path"]
    assert "trace-audit" in gaps[0].message or "entry" in gaps[0].message


def test_audit_gap_clean_tree_and_exemptions_carry_reasons():
    from repro.analysis.trace_audit import AUDIT_EXEMPT, audit_coverage
    fs = audit_coverage(str(REPO))
    assert not fs, "\n".join(f.render() for f in fs)
    assert all(isinstance(r, str) and r for r in AUDIT_EXEMPT.values())


def test_audit_covers_mesh_entry_points():
    from repro.analysis.trace_audit import audited_symbols
    names = audited_symbols()
    assert "search_mesh" in names
    assert "apply_ops_mesh" in names
    assert "search_kernel_mesh" in names
