"""The one traffic generator.  A traffic file holds parameters only; this
module turns them into requests from ``--seed``.

Two loops:

* ``closed`` — ``clients`` agents, one request each in flight.  A turn's
  prompt is the shared prefix, the agent's session so far (earlier
  observations and its own generated tokens) and a new observation; the
  next turn is due when the previous one completes (plus ``think_s``).  A
  session restarts from the prefix when the next turn would pass
  ``session_max_tokens``.
* ``open`` — Poisson arrivals at ``rate_rps``, each with its own prompt;
  ``warmup_s`` seconds of arrivals precede the measured window.

Lengths are lognormal (median, sigma) clipped to [min, max], taken from a
fixed set — the quantiles of the distribution — and Poisson gaps likewise.
Every seed runs the same schedule: the same sizes in the same order and the
same arrivals, shuffled once by ``SCHEDULE_SEED``.  ``--seed`` draws the
token ids (and, elsewhere, the weights), so seeds change what is computed,
not how much.  (With the order drawn from the seed too, the open loop's
TTFT median on one TPU v5e moved by 6% between seeds and by under 1%
between two runs of one seed.)  Outputs are a fixed ``max_new_tokens`` with no EOS: lengths
never depend on the numerics.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np

SCHEDULE_SEED = 0x5eed


def size_set(dist: dict, n: int) -> np.ndarray:
    """The n lengths every seed shares: lognormal quantiles at (i + 0.5)/n,
    rounded and clipped to [min, max]."""
    nd = NormalDist()
    mu = math.log(dist["median"])
    out = [math.exp(mu + dist["sigma"] * nd.inv_cdf((i + 0.5) / n))
           for i in range(n)]
    return np.clip(np.rint(out), dist["min"], dist["max"]).astype(np.int64)


def gap_set(rate: float, n: int) -> np.ndarray:
    """The n inter-arrival gaps every seed shares: exponential quantiles."""
    return np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                     for i in range(n)])


@dataclass
class Job:
    """One request to submit: prompt, output length, and when it is due
    (seconds from the traffic's origin)."""
    prompt: list[int]
    max_new: int
    due: float
    client: int = -1
    tokens: list[int] = field(default_factory=list)


class ClosedLoop:
    def __init__(self, p: dict, seed: int, vocab: int):
        self.p = p
        self.rng = np.random.default_rng([seed, 0x61])     # token ids
        order = np.random.default_rng([SCHEDULE_SEED, 0x61])
        self.vocab = vocab
        self.n = p["clients"]
        self.prefix = self.rng.integers(0, vocab, p["shared_prefix_tokens"]
                                        ).tolist()
        k = p["size_set"]
        obs, out = size_set(p["observation_tokens"], k), \
            size_set(p["output_tokens"], k)
        self.obs = [obs[order.permutation(k)] for _ in range(self.n)]
        self.out = [out[order.permutation(k)] for _ in range(self.n)]
        self.turn = [0] * self.n
        self.history: list[list[int]] = [[] for _ in range(self.n)]
        self.ready = [0.0] * self.n          # when each agent's turn is due
        self.busy = [False] * self.n
        self.finished_turns = [0] * self.n

    def _next(self, a: int, due: float) -> Job:
        t = self.turn[a] % len(self.obs[a])
        n_obs, n_out = int(self.obs[a][t]), int(self.out[a][t])
        self.turn[a] += 1
        if (len(self.prefix) + len(self.history[a]) + n_obs + n_out
                > self.p["session_max_tokens"]):
            self.history[a] = []                 # the session restarts
        observation = self.rng.integers(0, self.vocab, n_obs).tolist()
        prompt = self.prefix + self.history[a] + observation
        self.history[a] = self.history[a] + observation
        return Job(prompt=prompt, max_new=n_out, due=due, client=a)

    def due(self, now: float) -> list[Job]:
        jobs = []
        for a in range(self.n):
            if not self.busy[a] and self.ready[a] <= now:
                self.busy[a] = True
                jobs.append(self._next(a, max(self.ready[a], 0.0)))
        return jobs

    def complete(self, job: Job, now: float) -> None:
        a = job.client
        self.history[a] = self.history[a] + list(job.tokens)
        self.busy[a] = False
        self.ready[a] = now + self.p["think_s"]
        self.finished_turns[a] += 1

    def next_due(self) -> float | None:
        waiting = [self.ready[a] for a in range(self.n) if not self.busy[a]]
        return min(waiting) if waiting else None

    def warm(self, now: float) -> bool:
        """Warm-up is over once every agent has finished one turn."""
        return min(self.finished_turns) >= 1


class OpenLoop:
    def __init__(self, p: dict, seed: int, vocab: int, seconds: float):
        self.p = p
        rng = np.random.default_rng([seed, 0x6f])           # token ids
        order = np.random.default_rng([SCHEDULE_SEED, 0x6f])
        rate = p["rate_rps"]
        self.warmup_s = float(p["warmup_s"])
        # Three phases, each with its own fixed set of sizes and gaps: the
        # warm-up, the measured window, and a tail that keeps the load on
        # past the window's end.
        phases = [(0.0, self.warmup_s),
                  (self.warmup_s, float(seconds)),
                  (self.warmup_s + seconds, 5.0)]
        self.jobs: list[Job] = []
        for start, span in phases:
            n = max(1, math.ceil(rate * span))
            gaps = gap_set(rate, n)[order.permutation(n)]
            prompts = size_set(p["prompt_tokens"], n)[order.permutation(n)]
            outs = size_set(p["output_tokens"], n)[order.permutation(n)]
            t = start + np.cumsum(gaps) - gaps[0] * 0.5
            for i in range(n):
                self.jobs.append(Job(
                    prompt=rng.integers(0, vocab, int(prompts[i])).tolist(),
                    max_new=int(outs[i]), due=float(t[i])))
        self.jobs.sort(key=lambda j: j.due)
        self.cursor = 0

    def due(self, now: float) -> list[Job]:
        out = []
        while self.cursor < len(self.jobs) and \
                self.jobs[self.cursor].due <= now:
            out.append(self.jobs[self.cursor])
            self.cursor += 1
        return out

    def complete(self, job: Job, now: float) -> None:
        pass

    def next_due(self) -> float | None:
        return (self.jobs[self.cursor].due
                if self.cursor < len(self.jobs) else None)

    def warm(self, now: float) -> bool:
        return now >= self.warmup_s


def make(p: dict, seed: int, vocab: int, seconds: float):
    if p["loop"] == "closed":
        return ClosedLoop(p, seed, vocab)
    if p["loop"] == "open":
        return OpenLoop(p, seed, vocab, seconds)
    raise ValueError(f"unknown loop {p['loop']!r}")
