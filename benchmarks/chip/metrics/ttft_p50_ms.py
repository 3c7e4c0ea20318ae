"""ttft_p50_ms: median time to first token over the requests due in the
window (see harness/latency.py for what counts)."""
from harness.latency import pct


def read(ctx):
    xs = ctx.latency["ttft_s"]
    return 1e3 * pct(xs, 50) if xs else None
