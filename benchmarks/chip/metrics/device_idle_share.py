"""device_idle_share: share of the traced window (first traced step's
start to the last one's end) in which no operation ran on the device."""
from harness import trace as trace_mod


def read(ctx):
    if ctx.trace is None:
        return None
    w = trace_mod.window_s(ctx.trace)
    if w <= 0:
        return None
    return 100.0 * (1.0 - trace_mod.busy_s(ctx.trace) / w)
