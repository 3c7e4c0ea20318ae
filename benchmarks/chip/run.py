#!/usr/bin/env python3
"""Chip benchmark of the served path: one run of one cell.

  python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
      --seconds <s> --trace <0|1>

Cells, metrics and bounds are in BENCHMARK.json at the checkout's root;
each configuration, traffic mix, metric, kernel count and device lives in
a file of its own under benchmarks/chip/ (see harness/spec.py).  The last
line of standard output is one JSON object (correct, attempted, failed,
metrics, device, breakdown with --trace 1, check); the last lines of
standard error are the numbers the correctness check compared, each with
its limit.  Without a TPU, with fewer chips than the cell needs, or
without the program beside it, it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import cell, spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    try:
        result, compared = cell.run(a.workload, a.seed, a.seconds,
                                    bool(a.trace), t_start=T_START)
    except (cell.Refusal, spec.SpecError) as e:
        print(f"run.py: no result: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    for name, value, limit in compared:
        print(f"{name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
