"""A run whose timed path is broken underneath comes out not correct.

The harness runs as it does on the chip, with the look for a chip
skipped, on the tiny cells; the mixed step the engine drives is wrapped
with one fault at a time.  (Four-chip faults do not apply: every cell
runs on one chip.)"""
import time

import jax
import jax.numpy as jnp
import pytest

import tinybench
from harness import cell


def stale_state(eng):
    """The step returns the cache it was given: nothing is written."""
    step = eng._mixed

    def f(params, cache, *a):
        keep = jax.tree.map(jnp.copy, cache)
        nxt, _ = step(params, cache, *a)
        return nxt, keep
    eng._mixed = f


def half_batch(eng):
    """The upper half of the rows is left out of the step."""
    step = eng._mixed

    def f(params, cache, toks, start, span, rng):
        return step(params, cache, toks, start,
                    span.at[span.shape[0] // 2:].set(0), rng)
    eng._mixed = f


def token_altered(eng):
    """Row 0's sampled token is altered where it is produced."""
    step = eng._mixed
    vocab = eng.cfg.vocab_size

    def f(*a):
        nxt, cache = step(*a)
        return nxt.at[0].set((nxt[0] + 1) % vocab), cache
    eng._mixed = f


def run(tmp_path, workload, fault):
    res, compared = cell.run(
        workload, 5, 2.0, False, t_start=time.perf_counter(),
        bench=tinybench.bench(), chip_dir=tinybench.chip_dir(tmp_path),
        root=tinybench.ROOT, require_chip=False, fault=fault)
    return res


@pytest.mark.parametrize("workload", sorted(tinybench.CELLS))
def test_sound_run_is_correct(tmp_path, workload):
    res = run(tmp_path, workload, None)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) >= {"setup_s", "ttft_p50_ms",
                                   "itl_p95_ms"}


@pytest.mark.parametrize("fault", [stale_state, half_batch, token_altered])
def test_fault_is_not_correct(tmp_path, fault):
    res = run(tmp_path, "tiny-mha.closed", fault)
    assert not res["correct"], res["check"]
