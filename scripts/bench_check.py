"""Benchmark regression gate (CI bench job).

Compares freshly produced ``BENCH_*.json`` headline metrics against the
committed baselines and fails on a regression larger than the tolerance —
bench artifacts have been uploaded since PR 1, but nothing ever *read*
them, so a change could silently halve a speedup and still merge green.

Headline metrics per benchmark (higher is better unless noted):

* ``BENCH_spmm_grad.json``   — every entry of ``speedup_sparse_over_dense``
* ``BENCH_algorithms.json``  — per-algorithm ``tta`` (time-to-accuracy,
  LOWER is better; a fresh run that no longer reaches the target where the
  baseline did is an automatic failure), ``best_acc``, and the faults
  scenario's ``recovery_overhead`` (faulty TTA / clean TTA, LOWER is
  better, DESIGN.md §7)

Baselines default to ``git show HEAD:<file>`` so the gate needs no extra
artifact plumbing: the bench job regenerates the jsons in the workspace and
this script diffs them against the committed versions. ``--baseline-dir``
points at saved copies instead (e.g. when comparing two fresh runs).

Whether or not the gate trips, a per-metric drift table (baseline vs fresh
value, signed drift) is printed for every benchmark so CI logs show the
metric trajectories over time, not only the failures.

Exit code 0 = within tolerance, 1 = regression, 2 = usage/data error.

    python scripts/bench_check.py                  # all benchmarks, 25%
    python scripts/bench_check.py --tolerance 0.1 BENCH_spmm_grad.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

DEFAULT_TOLERANCE = 0.25
BENCH_FILES = ("BENCH_spmm_grad.json", "BENCH_algorithms.json")


def headline_metrics(name: str, data: dict) -> dict[str, tuple[float | None, bool]]:
    """{metric: (value, higher_is_better)} for one benchmark file."""
    out: dict[str, tuple[float | None, bool]] = {}
    if name == "BENCH_spmm_grad.json":
        for k, v in data.get("speedup_sparse_over_dense", {}).items():
            out[f"speedup_sparse_over_dense/{k}"] = (float(v), True)
    elif name == "BENCH_algorithms.json":
        if data.get("elastic_schedule"):
            # churn runs are a different experiment: their TTA/accuracy is
            # not comparable to the fixed-membership baseline this gate
            # protects (benchmarks/algorithms.py writes them to
            # BENCH_algorithms_elastic.json by default)
            raise KeyError(
                f"{name} was produced with an elastic schedule "
                f"({data['elastic_schedule']}) — the regression gate only "
                "compares fixed-membership runs; regenerate without "
                "--elastic-schedule"
            )
        for row in data.get("rows", []):
            algo = row["algorithm"]
            # per-metric presence checks: a baseline written before a metric
            # existed (first run of a new benchmark column) simply lacks the
            # key — that is "no baseline yet", not a data error
            if "tta" in row:
                tta = row["tta"]
                out[f"tta/{algo}"] = (
                    None if tta is None else float(tta), False
                )
            if "best_acc" in row:
                out[f"best_acc/{algo}"] = (float(row["best_acc"]), True)
        if data.get("faults"):
            # fault-recovery scenario (DESIGN.md §7): faulty TTA / clean
            # TTA under the seeded fault script — LOWER is better, and a
            # fresh run whose faulty trajectory no longer reaches the
            # target (recovery_overhead null) fails like a lost tta
            ro = data["faults"].get("recovery_overhead")
            out["faults/recovery_overhead"] = (
                None if ro is None else float(ro), False
            )
    else:
        raise KeyError(f"no headline extraction defined for {name}")
    return out


def load_baseline(name: str, baseline_dir: str | None, repo_root: str) -> dict:
    if baseline_dir:
        with open(os.path.join(baseline_dir, name)) as f:
            return json.load(f)
    blob = subprocess.run(
        ["git", "show", f"HEAD:{name}"], capture_output=True, text=True,
        cwd=repo_root, check=True,
    ).stdout
    return json.loads(blob)


def _fmt(v: float | None) -> str:
    return "never" if v is None else f"{v:.4g}"


def check_file(name: str, fresh: dict, base: dict,
               tolerance: float) -> tuple[list[str], list[str]]:
    """Returns ``(regression messages, per-metric drift table lines)``.

    The table covers *every* headline metric — it is printed on pass as
    well as on fail, so CI logs show the metric trajectories instead of
    only surfacing them once a run trips the tolerance. Metrics present in
    the fresh run but absent from the baseline (the first run of a new
    benchmark) are informational NEW rows: they gate nothing now and become
    the baseline once committed.
    """
    fresh_m = headline_metrics(name, fresh)
    base_m = headline_metrics(name, base)
    if not base_m:
        # a renamed/absent headline key must not disable the gate silently
        return ([f"{name}: baseline contains no headline metrics — "
                 "benchmark output schema changed? update headline_metrics()"],
                [])
    failures, table = [], []
    width = max(len(k) for k in (*base_m, *fresh_m))
    for key, (b_val, higher_better) in sorted(base_m.items()):
        f_val = fresh_m[key][0] if key in fresh_m else None
        drift = "n/a"
        if b_val is not None and f_val is not None and b_val != 0:
            rel = (f_val - b_val) / b_val
            drift = f"{rel:+.1%}"
        status = "ok"
        if key not in fresh_m:
            failures.append(f"{name}:{key} missing from the fresh run")
            status = "MISSING"
        elif b_val is None:
            pass                        # baseline never reached the target
        elif f_val is None:
            failures.append(
                f"{name}:{key} baseline={b_val:.4g} but the fresh run never "
                "reached the target"
            )
            status = "REGRESSED"
        elif higher_better:
            floor = b_val * (1.0 - tolerance)
            if f_val < floor:
                failures.append(
                    f"{name}:{key} regressed: {f_val:.4g} < {floor:.4g} "
                    f"(baseline {b_val:.4g}, tolerance {tolerance:.0%})"
                )
                status = "REGRESSED"
        else:
            ceil = b_val * (1.0 + tolerance)
            if f_val > ceil:
                failures.append(
                    f"{name}:{key} regressed: {f_val:.4g} > {ceil:.4g} "
                    f"(baseline {b_val:.4g}, tolerance {tolerance:.0%})"
                )
                status = "REGRESSED"
        arrow = "higher=better" if higher_better else "lower=better"
        table.append(
            f"  {key:<{width}}  baseline={_fmt(b_val):>8}  "
            f"fresh={_fmt(f_val):>8}  drift={drift:>7}  [{arrow}] {status}"
        )
    for key in sorted(set(fresh_m) - set(base_m)):
        f_val, higher_better = fresh_m[key]
        arrow = "higher=better" if higher_better else "lower=better"
        table.append(
            f"  {key:<{width}}  baseline={'--':>8}  "
            f"fresh={_fmt(f_val):>8}  drift={'n/a':>7}  [{arrow}] NEW"
        )
    return failures, table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*", default=[],
                    help=f"benchmark jsons to gate (default: {BENCH_FILES})")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="allowed relative regression (default 0.25 = 25%%)")
    ap.add_argument("--baseline-dir", default=None,
                    help="directory with baseline jsons (default: read the "
                         "committed versions via `git show HEAD:<file>`)")
    args = ap.parse_args(argv)

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = args.files or [os.path.join(repo_root, f) for f in BENCH_FILES]

    failures: list[str] = []
    checked: list[tuple[str, int]] = []  # (family, n metrics) per file
    for path in files:
        name = os.path.basename(path)
        try:
            with open(path) as f:
                fresh = json.load(f)
            base = load_baseline(name, args.baseline_dir, repo_root)
        except (OSError, subprocess.CalledProcessError, json.JSONDecodeError) as e:
            print(f"bench_check: cannot load {name}: {e}", file=sys.stderr)
            return 2
        try:
            msgs, table = check_file(name, fresh, base, args.tolerance)
        except KeyError as e:
            print(f"bench_check: {e.args[0]}", file=sys.stderr)
            return 2
        status = "FAIL" if msgs else "ok"
        n = len(headline_metrics(name, base))
        print(f"[bench_check] {name}: {n} headline metrics — {status}")
        for line in table:      # drift trajectory, printed on pass AND fail
            print(line)
        failures.extend(msgs)
        checked.append((name.removeprefix("BENCH_").removesuffix(".json"), n))

    # one greppable line naming every benchmark family this run gated — a
    # file list that silently shrank must be visible in the log, not lore
    print("[bench_check] families checked: "
          + ", ".join(f"{fam} ({n} metrics)" for fam, n in checked))
    for msg in failures:
        print(f"[bench_check] REGRESSION {msg}", file=sys.stderr)
    if failures:
        print(f"[bench_check] {len(failures)} regression(s) beyond "
              f"{args.tolerance:.0%}", file=sys.stderr)
        return 1
    print("[bench_check] all headline metrics within tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
