"""itl_p95_ms: 95th percentile of every gap between consecutive tokens of
one request, both tokens seen in the window."""
from harness.latency import pct


def read(ctx):
    xs = ctx.latency["itl_s"]
    return 1e3 * pct(xs, 95) if xs else None
