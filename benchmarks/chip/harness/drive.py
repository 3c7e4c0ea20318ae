"""Drive ``ContinuousBatchingEngine.step()`` from the traffic generator and
stamp what the host sees.

Each ``eng.step()`` ends in a host sync (it reads the sampled tokens), so
the time after it returns is when the host saw that step's tokens.  A
record per request keeps its due time, when its first token was seen and
when each later token was seen.  A record per measured step keeps, for
every request it advanced, the first context position it processed, how
many it processed and whether it sampled a token — what the FLOP and byte
counts of the per-layer metrics are computed from.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

from harness.traffic import Job


@dataclass
class Rec:
    job: Job
    req: object
    due: float                       # seconds on the loop's clock
    submitted: float
    first: float | None = None
    stamps: list[float] = field(default_factory=list)   # one per token
    done: float | None = None


WAITING = ("queued", "preempted")      # in the engine's queue, no row


def position(req) -> int:
    """Context positions the engine has processed for ``req``: the prompt
    cursor while admitting, then prompt + generated - 1 (the newest token
    is written by the next step)."""
    if req.status in WAITING:
        return 0
    ntok = len(req.tokens)
    if req.filled < req.admit_len or ntok == 0:
        return int(req.filled)
    return int(req.admit_len) + ntok - 1


class Loop:
    def __init__(self, eng, traffic, request_cls, clock=time.perf_counter):
        self.eng = eng
        self.traffic = traffic
        self.request_cls = request_cls
        self.clock = clock
        self.origin = clock()
        self.recs: list[Rec] = []
        self.live: list[Rec] = []
        self.steps: list[dict] = []
        self.record_steps = False
        self.late: list[float] = []      # generator lateness, seconds
        self.annotate = None             # (name, step_num) -> context
        self._rid = 0

    def now(self) -> float:
        return self.clock() - self.origin

    def submit_due(self) -> None:
        now = self.now()
        for job in self.traffic.due(now):
            req = self.request_cls(rid=self._rid, prompt=job.prompt,
                                   max_new_tokens=job.max_new)
            self._rid += 1
            self.eng.submit(req)
            t = self.now()
            self.late.append(t - job.due)
            rec = Rec(job=job, req=req, due=job.due, submitted=t)
            self.recs.append(rec)
            self.live.append(rec)

    def step(self) -> bool:
        """One engine step (or a wait for the next arrival when idle)."""
        eng = self.eng
        if not self.live:
            nxt = self.traffic.next_due()
            if nxt is not None:
                time.sleep(max(0.0, min(nxt - self.now(), 0.05)))
            return False
        before = [(r, position(r.req), int(r.req.filled),
                   r.req.status) for r in self.live]
        p0_tok = eng.stats["prefill_tokens"]
        t0 = self.now()
        if self.annotate is not None:
            with self.annotate("serve_step", len(self.steps)):
                eng.step()
        else:
            eng.step()
        t1 = self.now()
        rows = []
        filled_adv = 0
        still = []
        chunk = eng.chunk_size
        for rec, p0, f0, st in before:
            req = rec.req
            p1 = position(req)
            if st in WAITING and p1 > 0:
                p0 = max(0, p1 - chunk)
            n_new = len(req.tokens) - len(rec.stamps)
            if p1 > p0:
                rows.append((p0, p1 - p0, n_new > 0))
            if req.status not in WAITING:
                filled_adv += int(req.filled) - (0 if st in WAITING else f0)
            if n_new > 0:
                if rec.first is None:
                    rec.first = t1
                rec.stamps.extend([t1] * n_new)
            if req.status in ("completed", "shed", "expired", "failed"):
                rec.done = t1
                rec.job.tokens = list(req.tokens)
                self.traffic.complete(rec.job, t1)
            else:
                still.append(rec)
        self.live = still
        if self.record_steps:
            self.steps.append({
                "t0": t0, "t1": t1, "rows": rows,
                "prefill_tokens": eng.stats["prefill_tokens"] - p0_tok,
                "filled_adv": filled_adv})
        return True

    def run_until(self, stop) -> None:
        """Submit and step until ``stop(now)`` holds."""
        while True:
            if self.annotate is not None:
                with self.annotate("generator", None):
                    self.submit_due()
            else:
                self.submit_due()
            if stop(self.now()):
                return
            self.step()
