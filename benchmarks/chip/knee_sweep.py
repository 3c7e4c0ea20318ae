#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell after one set-up, to find
the highest rate the system sustains (the knee).

  python3 benchmarks/chip/knee_sweep.py --workload <open-loop cell> \\
      --seed <n> --seconds <s> --rates 2 4 6 8

Each rate runs the cell's own traffic (its sizes, at that rate) for a
``warmup`` and then ``--seconds`` measured, on the same engine, drained
between rates.  One line per rate: offered and completed requests per
second, requests still waiting at the close, TTFT p50/p95 and inter-token
p95.  A rate is sustained while completions keep up with arrivals and the
backlog at the close stays small.  The cell's rate is then written into
its traffic file by hand, at about four fifths of the knee.
"""
import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import cell as cell_mod, drive, latency, spec  # noqa: E402
from harness import traffic as traffic_mod  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--warmup", type=float, default=4.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    a = ap.parse_args()
    c = spec.Cell(a.workload)
    if c.traffic["loop"] != "open":
        print("knee_sweep: the cell is not open-loop", file=sys.stderr)
        return 2
    try:
        env = cell_mod.prepare(c, a.seed)
    except (cell_mod.Refusal, spec.SpecError) as e:
        print(f"knee_sweep: {e}", file=sys.stderr)
        return 2
    for rate in a.rates:
        p = dict(c.traffic, rate_rps=rate, warmup_s=a.warmup)
        tr = traffic_mod.make(p, a.seed, env.cfg.vocab_size, a.seconds)
        drv = drive.Loop(env.eng, tr, env.Request)
        t0, t1 = a.warmup, a.warmup + a.seconds
        drv.run_until(lambda now: now >= t1)
        lat = latency.summarize(drv.recs, t0, t1)
        done = sum(1 for r in drv.recs
                   if r.done is not None and t0 <= r.done < t1)
        row = {"rate_rps": rate,
               "offered_rps": lat["due"] / a.seconds,
               "completed_rps": done / a.seconds,
               "waiting_at_close": lat["waiting_at_close"],
               "queued_at_close": len(env.eng.queue),
               "ttft_p50_ms": 1e3 * latency.pct(lat["ttft_s"], 50),
               "ttft_p95_ms": 1e3 * latency.pct(lat["ttft_s"], 95),
               "itl_p95_ms": 1e3 * latency.pct(lat["itl_s"], 95)
               if lat["itl_s"] else None,
               "output_tok_s": lat["tokens"] / a.seconds}
        print(json.dumps(row), flush=True)
        # Drain before the next rate: nothing new is due, the rest finishes.
        drv.traffic = SimpleNamespace(due=lambda now: [], next_due=lambda: None,
                                      complete=lambda job, now: None)
        t_drain = time.perf_counter()
        while drv.live and time.perf_counter() - t_drain < 120:
            drv.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
