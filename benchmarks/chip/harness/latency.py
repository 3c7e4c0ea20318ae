"""Host-clock latency statistics of one measured window.

TTFT: from a request's due time (open loop: its scheduled arrival; closed
loop: the agent's submission) to the host seeing its first token, over
every request due in the window; one still without a token when the
window closes enters at the time it has waited.  Inter-token gaps: every
gap between consecutive tokens of one request with both tokens seen in the
window.  Output rate: tokens seen in the window over the window's length.
"""
from __future__ import annotations

import numpy as np


def pct(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def summarize(recs, t0: float, t1: float) -> dict:
    due = [r for r in recs if t0 <= r.due < t1]
    ttft = [((r.first if r.first is not None and r.first <= t1 else t1)
             - r.due) for r in due]
    itl = []
    tokens = 0
    for r in recs:
        s = [t for t in r.stamps if t0 <= t <= t1]
        tokens += len(s)
        itl.extend(np.diff(s).tolist())
    return {
        "due": len(due),
        "first_tokens": sum(1 for r in due
                            if r.first is not None and r.first <= t1),
        "waiting_at_close": sum(1 for r in due
                                if r.first is None or r.first > t1),
        "failed": sum(1 for r in due
                      if r.req.status in ("shed", "expired", "failed")),
        "ttft_s": ttft,
        "itl_s": itl,
        "tokens": tokens,
        "window_s": t1 - t0,
    }
