#!/usr/bin/env python3
"""Readings the correctness limit of a cell is set from, on the chip.

  python3 benchmarks/chip/control.py --workload <name> --seconds <s> \\
      --seeds 1 2 3 ... [--control-seeds 1 2 3]

For each seed, in one process: the cell's set-up and traffic, a short
window at the cell's own load, then the same sample of finished requests
that a run checks (harness/correct.py), scored twice against the plain
reference:

  program  the widest gap of a served token below the reference's best
           logit (the number a run compares);
  control  the widest gap of the token that the reference computed with
           float8 e4m3 matmul operands (the precision step below the
           configuration's bfloat16) puts first, at the same positions.

The limit lies above the largest program reading and below the smallest
control reading (see checks/<workload>.json).  One JSON line per seed.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import cell as cell_mod, correct, drive, spec  # noqa: E402
from harness import traffic as traffic_mod  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=None)
    a = ap.parse_args()
    c = spec.Cell(a.workload)
    ref = c.reference().make(c.config)
    control_seeds = set(a.seeds if a.control_seeds is None
                        else a.control_seeds)
    for seed in a.seeds:
        t = time.perf_counter()
        try:
            env = cell_mod.prepare(c, seed)
        except (cell_mod.Refusal, spec.SpecError) as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        tr = traffic_mod.make(c.traffic, seed, env.cfg.vocab_size,
                              a.seconds)
        drv = drive.Loop(env.eng, tr, env.Request)
        drv.run_until(tr.warm)
        t1 = drv.now() + a.seconds
        drv.run_until(lambda now: now >= t1)
        finished = [r for r in drv.recs
                    if r.req.status == "completed" and r.done <= t1]
        drv.eng = env.eng = None
        gc.collect()
        picks = correct.sample(finished, seed, c.check["served_tokens"],
                               c.check["max_requests"])
        vocab = c.config["vocab_size"]
        g = correct.gaps(ref, env.params, picks, vocab)
        row = {"seed": seed, "requests": len(picks), "served": int(g.size),
               "program_gap": float(g.max()),
               "program_not_best": int((g > 0).sum())}
        if seed in control_seeds:
            gc_ = correct.gaps(ref, env.params, picks, vocab, control=True)
            row.update(control_gap=float(gc_.max()),
                       control_not_best=int((gc_ > 0).sum()))
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        env = drv = None
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
