"""scripts/bench_check.py regression tests (ISSUE 8 bugfix).

The first run of any new benchmark column produces a fresh BENCH_*.json
with headline metrics the committed (``git show HEAD:``) baseline predates.
That used to KeyError inside ``headline_metrics`` (e.g. baseline rows
without ``best_acc``) and exit 2 — the gate must instead report such
metrics as informational NEW rows and keep gating the metrics both sides
share.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "bench_check", os.path.join(REPO_ROOT, "scripts", "bench_check.py")
)
bench_check = importlib.util.module_from_spec(_spec)
sys.modules.setdefault("bench_check", bench_check)
_spec.loader.exec_module(bench_check)


def _algo_row(algo, tta, best_acc=None):
    row = {"algorithm": algo, "tta": tta}
    if best_acc is not None:
        row["best_acc"] = best_acc
    return row


def test_baseline_predating_metric_does_not_crash():
    """Baseline rows without best_acc (written before the metric existed)
    must not KeyError; the fresh-only metrics show up as NEW table rows."""
    base = {"rows": [_algo_row("sync", 3.0)]}
    fresh = {"rows": [_algo_row("sync", 2.9, best_acc=0.81)]}
    failures, table = bench_check.check_file(
        "BENCH_algorithms.json", fresh, base, tolerance=0.25
    )
    assert failures == []
    new_rows = [ln for ln in table if ln.rstrip().endswith("NEW")]
    assert len(new_rows) == 1 and "best_acc/sync" in new_rows[0]


def test_new_benchmark_entry_is_informational():
    """A brand-new speedup key gates nothing but is shown as NEW."""
    base = {"speedup_sparse_over_dense": {"fwd_bwd_nf10000": 2.0}}
    fresh = {"speedup_sparse_over_dense": {"fwd_bwd_nf10000": 2.1,
                                           "fwd_bwd_nf400000": 1.5}}
    failures, table = bench_check.check_file(
        "BENCH_spmm_grad.json", fresh, base, tolerance=0.25
    )
    assert failures == []
    assert any("fwd_bwd_nf400000" in ln and ln.rstrip().endswith("NEW")
               for ln in table)


def test_shared_metrics_still_gated_alongside_new_ones():
    """NEW-row tolerance must not weaken the gate for shared metrics."""
    base = {"speedup_sparse_over_dense": {"fwd_bwd_nf10000": 2.0}}
    fresh = {"speedup_sparse_over_dense": {"fwd_bwd_nf10000": 0.8,
                                           "fwd_bwd_nf400000": 1.5}}
    failures, _ = bench_check.check_file(
        "BENCH_spmm_grad.json", fresh, base, tolerance=0.25
    )
    assert len(failures) == 1 and "fwd_bwd_nf10000" in failures[0]


def test_metric_missing_from_fresh_still_fails():
    """The inverse direction (baseline has it, fresh lost it) stays fatal."""
    base = {"rows": [_algo_row("sync", 3.0, best_acc=0.8)]}
    fresh = {"rows": [_algo_row("sync", 2.9)]}
    failures, _ = bench_check.check_file(
        "BENCH_algorithms.json", fresh, base, tolerance=0.25
    )
    assert any("best_acc/sync" in f for f in failures)


def test_main_with_baseline_dir(tmp_path):
    """End-to-end through main(): old-schema baseline dir + new-schema
    fresh file exits 0 (used to exit 2 via the KeyError handler)."""
    (tmp_path / "baseline").mkdir()
    (tmp_path / "baseline" / "BENCH_algorithms.json").write_text(
        json.dumps({"rows": [_algo_row("sync", 3.0)]})
    )
    fresh_path = tmp_path / "BENCH_algorithms.json"
    fresh_path.write_text(
        json.dumps({"rows": [_algo_row("sync", 2.9, best_acc=0.81)]})
    )
    rc = bench_check.main([
        str(fresh_path), "--baseline-dir", str(tmp_path / "baseline"),
    ])
    assert rc == 0
