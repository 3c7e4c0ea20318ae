"""Benchmark entry: one function per paper table + roofline summary.

Prints ``name,us_per_call,derived`` CSV rows (the harness contract).

  PYTHONPATH=src python -m benchmarks.run [--runs N] [--agents N] [--quick]
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--quick", action="store_true",
                    help="2 runs/mode, smaller table6 sweep")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    runs = 2 if args.quick else args.runs

    from repro.runtime import compile_cache
    compile_cache.enable()

    from benchmarks import tables
    from benchmarks.common import run_suite

    print("name,us_per_call,derived")
    suite = run_suite(runs_per_mode=runs, n_agents=args.agents,
                      force=args.force)
    for row in tables.table3(suite):
        print(row)
    for row in tables.table4(suite):
        print(row)
    for row in tables.table5(suite):
        print(row)
    for row in tables.table6(runs=1 if args.quick else 2,
                             agents=(1, 2, 4) if args.quick
                             else (1, 2, 4, 8)):
        print(row)
    for row in tables.table7(suite):
        print(row)
    for row in tables.rq3_consistency(suite):
        print(row)

    # Roofline summary (reads dry-run artifacts if present).
    try:
        from benchmarks.roofline import summary_rows
        for row in summary_rows():
            print(row)
    except FileNotFoundError:
        print("roofline/skipped,0,run launch/dryrun.py first")


if __name__ == "__main__":
    main()
