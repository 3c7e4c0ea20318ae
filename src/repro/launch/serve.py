"""Serving launcher: batched multi-agent generation service.

Runs the CodeCRDT serving stack for an arch: N agent streams on one decode
batch, CRDT coordination, convergence report.  This is the CPU-scale entry;
the production mesh path is exercised by launch/dryrun.py (--fused-coord
lowers the decode+coordination step on 256/512 chips).

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b \\
      --task dashboard --mode parallel --agents 4
"""
from __future__ import annotations

import argparse
import json

import jax

import repro.configs as configs
from repro.agents.orchestrator import run_task
from repro.agents.tasks import TASKS
from repro.models import lm
from repro.runtime import compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=sorted(configs.ARCHS))
    ap.add_argument("--task", default="dashboard", choices=sorted(TASKS))
    ap.add_argument("--mode", default="parallel",
                    choices=["sequential", "parallel", "both"])
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=64,
                    help="width of the reduced model this CLI drives (a "
                         "CPU-sized loop check, not a serving "
                         "configuration; chip_smoke.py serves the "
                         "published widths)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = configs.reduced(configs.get(args.arch), d_model=args.d_model,
                          vocab=512)
    params = lm.init(jax.random.PRNGKey(args.seed), cfg)

    modes = (["sequential", "parallel"] if args.mode == "both"
             else [args.mode])
    out = {}
    for mode in modes:
        r = run_task(cfg, params, TASKS[args.task], mode=mode,
                     n_agents=args.agents, seed=args.seed)
        out[mode] = {
            "steps": r.steps, "wall_s": round(r.wall_s, 3),
            "tokens": r.gen_tokens, "invalidations": r.invalidations,
            "claim_collisions": r.claim_collisions,
            "semantic_conflicts": r.semantic_conflicts,
            "converged": r.converged,
        }
        if not args.json:
            print(f"[{cfg.name} × {args.task} × {mode}] "
                  f"steps={r.steps} wall={r.wall_s:.2f}s "
                  f"tokens={r.gen_tokens} conflicts={r.semantic_conflicts} "
                  f"converged={r.converged}")
    if args.json:
        print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
