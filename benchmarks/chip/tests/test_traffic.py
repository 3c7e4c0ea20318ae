"""The traffic generator: deterministic per seed, lengths as the file says,
and every seed the same work in another order."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from harness import traffic

CHIP = Path(__file__).resolve().parents[1]


def params(name):
    return json.loads((CHIP / "traffic" / f"{name}.json").read_text())


def test_size_set_is_clipped_lognormal_quantiles():
    d = {"median": 100, "sigma": 1.0, "min": 30, "max": 500}
    s = traffic.size_set(d, 101)
    assert s.min() >= 30 and s.max() <= 500
    assert s[50] == 100                        # the middle quantile
    assert (np.diff(s) >= 0).all()
    assert (s == 30).sum() > 1 and (s == 500).sum() > 1   # both clips bind


def _closed_turns(seed, n_turns=6):
    p = params("agent-loop")
    g = traffic.ClosedLoop(p, seed, 50304)
    jobs = []
    now = 0.0
    for _ in range(n_turns):
        for j in g.due(now):
            jobs.append(j)
            j.tokens = list(range(j.max_new))   # stands for the served tokens
            g.complete(j, now)
        now += 1.0
    return p, g, jobs


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_closed_loop_deterministic_and_shaped(seed):
    p, g, jobs = _closed_turns(seed)
    _, _, again = _closed_turns(seed)
    assert [(j.prompt, j.max_new) for j in jobs] == \
        [(j.prompt, j.max_new) for j in again]
    pre = p["shared_prefix_tokens"]
    for j in jobs:
        assert j.prompt[:pre] == g.prefix
        assert len(j.prompt) + j.max_new <= p["session_max_tokens"]
        o = p["output_tokens"]
        assert o["min"] <= j.max_new <= o["max"]
    # A turn's prompt holds the agent's previous prompt and its tokens.
    by_agent = {}
    for j in jobs:
        by_agent.setdefault(j.client, []).append(j)
    a0 = by_agent[0]
    for prev, nxt in zip(a0, a0[1:]):
        if len(nxt.prompt) > len(prev.prompt):
            assert nxt.prompt[:len(prev.prompt)] == prev.prompt
            assert nxt.prompt[len(prev.prompt):len(prev.prompt)
                              + prev.max_new] == prev.tokens


def test_closed_loop_seeds_share_the_schedule():
    """Every seed: the same sizes in the same order; other token ids."""
    _, a, ja = _closed_turns(1)
    _, b, jb = _closed_turns(2**31 + 11)
    assert [(len(j.prompt), j.max_new, j.client) for j in ja] == \
        [(len(j.prompt), j.max_new, j.client) for j in jb]
    assert a.prefix != b.prefix
    assert sorted(a.obs[0].tolist()) == sorted(a.obs[5].tolist())
    assert a.obs[0].tolist() != a.obs[5].tolist()   # agents differ


def test_open_loop_deterministic_and_same_work_per_seed():
    p = params("code-completion")
    secs = 30.0
    a = traffic.OpenLoop(p, 11, 49152, secs)
    a2 = traffic.OpenLoop(p, 11, 49152, secs)
    b = traffic.OpenLoop(p, 2**33 + 1, 49152, secs)
    assert [(j.due, j.prompt) for j in a.jobs] == \
        [(j.due, j.prompt) for j in a2.jobs]

    def window(g):
        w = p["warmup_s"]
        return [j for j in g.jobs if w <= j.due < w + secs]

    wa, wb = window(a), window(b)
    n = int(np.ceil(p["rate_rps"] * secs))
    assert abs(len(wa) - n) <= 2
    # Every seed: the same arrivals and sizes in the same order.
    assert [(j.due, len(j.prompt), j.max_new) for j in a.jobs] == \
        [(j.due, len(j.prompt), j.max_new) for j in b.jobs]
    assert [j.prompt for j in wa] != [j.prompt for j in wb]
    want = Counter(traffic.size_set(p["prompt_tokens"], n).tolist())
    assert Counter(len(j.prompt) for j in wa) == want
    for j in a.jobs:
        pr, o = p["prompt_tokens"], p["output_tokens"]
        assert pr["min"] <= len(j.prompt) <= pr["max"]
        assert o["min"] <= j.max_new <= o["max"]
    due = [j.due for j in a.jobs]
    assert due == sorted(due)
