"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the engine is freed, a sample of the
requests the window finished — the one with the most served tokens, then
others in an order drawn from the seed, until ``served_tokens`` tokens or
``max_requests`` requests — is run through the configuration's reference
once per request (prompt plus served tokens).  At every served position
the gap is how far the served token's reference logit lies below the
reference's best; the number compared is the widest gap of the sample
(greedy serving: a sound token is the best or within rounding of it).
The limit, and the readings it was set from, are in
``checks/<workload>.json``.
"""
from __future__ import annotations

import time

import numpy as np


def sample(finished, seed: int, served_tokens: int, max_requests: int):
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.req.tokens),
                                           len(r.req.prompt)))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([seed, 0x63])
    out, n = [longest], len(longest.req.tokens)
    for i in rng.permutation(len(rest)):
        if n >= served_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].req.tokens)
    return out


def gaps(ref, params, picks, vocab: int, *, control=False) -> np.ndarray:
    out = []
    for r in picks:
        toks = list(r.req.tokens)
        if not toks or min(toks) < 0 or max(toks) >= vocab:
            out.append(np.array([np.inf]))
            continue
        out.append(ref.gaps(params, r.req.prompt, toks, control=control))
    return np.concatenate(out) if out else np.zeros(0)


def check(cell, params, finished, seed: int):
    t0 = time.perf_counter()
    chk = cell.check
    picks = sample(finished, seed, chk["served_tokens"], chk["max_requests"])
    ref = cell.reference().make(cell.config)
    g = gaps(ref, params, picks, cell.config["vocab_size"])
    worst = float(g.max()) if g.size else float("inf")
    detail = {
        "requests": len(picks),
        "served_tokens_compared": int(g.size),
        "longest_context": max((len(r.req.prompt) + len(r.req.tokens)
                                for r in picks), default=0),
        "positions_not_reference_best": int((g > 0).sum()),
        "reference_s": time.perf_counter() - t0,
    }
    return [("logit_gap", worst, float(chk["logit_gap_limit"]))], detail
