"""The control — the plain reference with float8 e4m3 matmul operands, the
precision step below the configurations' bfloat16 — fails the check that
the served tokens pass, on the tiny cells at three seeds.  On the chip the
same readings at the cells' own sizes come from ``control.py``."""
import gc

import pytest

import tinybench
from harness import cell, correct, drive, spec
from harness import traffic as traffic_mod


@pytest.mark.parametrize("workload", sorted(tinybench.CELLS))
def test_control_fails_where_the_program_passes(tmp_path, workload):
    c = spec.Cell(workload, tinybench.bench(), tinybench.chip_dir(tmp_path),
                  tinybench.ROOT)
    ref = c.reference().make(c.config)
    limit = c.check["logit_gap_limit"]
    for seed in (1, 2, 2**32 + 3):
        env = cell.prepare(c, seed, root=tinybench.ROOT, require_chip=False)
        tr = traffic_mod.make(c.traffic, seed, env.cfg.vocab_size, 1.5)
        drv = drive.Loop(env.eng, tr, env.Request)
        drv.run_until(tr.warm)
        t1 = drv.now() + 1.5
        drv.run_until(lambda now: now >= t1)
        done = [r for r in drv.recs if r.req.status == "completed"]
        drv.eng = env.eng = None
        gc.collect()
        picks = correct.sample(done, seed, 10**6, 10**6)
        vocab = c.config["vocab_size"]
        program = correct.gaps(ref, env.params, picks, vocab).max()
        control = correct.gaps(ref, env.params, picks, vocab,
                               control=True).max()
        assert program <= limit < control, (seed, program, control)
