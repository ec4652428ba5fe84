"""Benchmark harness: one benchmark per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run              # all, fast settings
  PYTHONPATH=src python -m benchmarks.run --only fig6
  PYTHONPATH=src python -m benchmarks.run --full       # full sweeps
"""
from __future__ import annotations

import argparse
import time
import traceback


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help="fig6..fig12 | all")
    ap.add_argument("--full", action="store_true",
                    help="full sweeps (slower); default is fast settings")
    args = ap.parse_args(argv)

    from .paper_figures import ALL_FIGURES

    jobs = {}
    if args.only == "all":
        jobs.update(ALL_FIGURES)
    else:
        jobs[args.only] = ALL_FIGURES[args.only]

    failures = []
    for name, fn in jobs.items():
        t0 = time.perf_counter()
        print(f"\n{'='*70}\n== {name}\n{'='*70}")
        try:
            fn(fast=not args.full)
        except Exception as e:
            failures.append((name, repr(e)))
            traceback.print_exc()
        print(f"# {name} done in {time.perf_counter()-t0:.1f}s")

    if failures:
        print(f"\n{len(failures)} benchmark(s) FAILED:")
        for n, e in failures:
            print(" ", n, e[:200])
        raise SystemExit(1)
    print("\nAll benchmarks completed.")


if __name__ == "__main__":
    from .envtune import ensure_tuned_env

    ensure_tuned_env()  # allocator/logging tuning; re-execs once if needed
    main()
