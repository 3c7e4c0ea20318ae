"""ttft_p90_ms: 90th percentile of the time to first token over the
requests due in the window — the highest percentile with ten or more
samples beyond it at the open-loop cell's rate and window."""
from harness.latency import pct


def read(ctx):
    xs = ctx.latency["ttft_s"]
    return 1e3 * pct(xs, 90) if xs else None
