"""Serving counters are functions of the code and the seed: a run of a
small seeded workload reports the same steps, pages, stalls, drafts, swaps
and recoveries every time, on any host.  Greedy decoding, seeded prompts and
a seeded fault channel make this hold, so each case pins its counters, its
resident-byte peak and its time to first token (in steps) to exact values:
a change in any of them between two commits is a change of behaviour, such
as admission taking more pages, never noise.  Replicated and disaggregated
serving have theirs pinned in ``test_replicated_pages.py`` and
``test_disagg.py``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import lm
from repro.serving import chaos
from repro.serving.scheduler import ContinuousBatchingEngine, Request


@pytest.fixture(scope="module")
def llm():
    cfg = configs.reduced(configs.get("olmo-1b"), d_model=32, vocab=128)
    cfg = cfg.replace(num_layers=2)
    return cfg, jax.tree.map(lambda x: x.astype(jnp.float32),
                             lm.init(jax.random.PRNGKey(0), cfg))


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(2, 128, n)] for n in lens]


# Each case: engine options, prompts, tokens out, and what the case pins:
# engine counters, the peak of resident_cache_bytes(), and each request's
# steps from submission to its first token.
CASES = {
    # ragged prompts, more requests than rows: mid-flight admission
    "ragged": (dict(batch=3, max_len=64), _prompts((5, 17, 9, 30, 3, 12, 21)),
               6, dict(steps=21, peak_pages=10, prefill_chunks=16,
                       grown_pages=13),
               16384, [1, 3, 2, 10, 8, 10, 16]),
    # a budget below the batch: the third admission waits for a free row
    "token_budget": (dict(batch=3, max_len=64, token_budget=2),
                     _prompts((3, 3, 3)), 8,
                     dict(steps=19, prefill_chunks=6), 10240, [4, 4, 12]),
    # four agents forked from one prompt with a partial boundary page
    "prefix_share": (dict(batch=4, max_len=64, prefix_sharing=True),
                     _prompts((21,)) * 4, 4,
                     dict(steps=6, shared_pages=9, cow_copies=3,
                          peak_pages=6), 12288, [3, 3, 3, 3]),
    # motif-repeating prompts, so prompt lookup drafts
    "speculation": (dict(batch=3, max_len=64, spec_decode="ngram",
                         spec_k=4),
                    [(p * 5)[:20] for p in _prompts((5, 5, 5))], 10,
                    dict(steps=10, draft_tokens=10, accepted_tokens=9,
                         rollback_tokens=1, spec_rollbacks=1),
                    24576, [3, 3, 3]),
    "int8": (dict(batch=2, max_len=32, kv_quant="int8"),
             _prompts((6, 11, 4)), 8,
             dict(steps=16, peak_pages=5, prefill_chunks=4), 6144, [1, 2, 9]),
    # a pool too small for both horizons: the long victim swaps out
    "swap": (dict(batch=2, max_len=64, num_pages=6, swap_tier_pages=8,
                  swap_min_tokens=16), _prompts((24, 6)), 16,
             dict(steps=23, swap_outs=2, swap_ins=2, preempt_swap=1),
             12288, [3, 1]),
}


def _serve(llm, case):
    cfg, params = llm
    kw, prompts, new = CASES[case][:3]
    eng = ContinuousBatchingEngine(cfg, params, page_size=8, chunk_size=8,
                                   **kw)
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    peak_bytes = 0
    while eng.step():
        peak_bytes = max(peak_bytes, eng.resident_cache_bytes())
    assert eng.stats["completed"] == len(reqs)
    ttft = [r.first_token_step - r.submitted_step for r in reqs]
    return eng.stats, peak_bytes, ttft, [r.tokens for r in reqs]


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_counters_repeat_exactly(llm, case):
    first, second = _serve(llm, case), _serve(llm, case)
    assert first[0] == second[0]              # every engine counter
    assert first[1:] == second[1:]            # resident peak, TTFT, tokens
    counters, peak_bytes, ttft = CASES[case][3:]
    assert {k: first[0][k] for k in counters} == counters
    assert first[1] == peak_bytes
    assert first[2] == ttft


def test_crash_failover_counters_repeat_exactly():
    cfg, params = chaos.tiny_model()

    def counters():
        trace = chaos.run_chaos(cfg, params, schedule="lossy", seed=0,
                                crash_at=4)
        srv = trace["server"]
        return (trace["steps"], srv["recovered_requests"], srv["retried"],
                srv["shed"], srv["lost_requests"], srv["failed_requests"],
                srv["completed"], srv["gen_tokens"])

    first = counters()
    assert first == counters()
    # steps, recovered, retried, shed, lost, failed, completed, tokens
    assert first == (27, 3, 3, 0, 0, 0, 10, 60)
