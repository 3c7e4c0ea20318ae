"""step_mfu: the whole mixed step's share of the chip's peak bf16 FLOP/s
over the traced steps' device busy time.

Model FLOPs count real tokens only: per position processed, 2 x the
non-embedding parameters plus causal attention at that position
(4 * layers * Hq * D * (p + 1)); per row that sampled a token, the head
once (2 * d * V).  Padding positions (the step computes B x width) do not
count.  The positions come from each request's cursor before and after
every step (harness/drive.py)."""
from harness import trace as trace_mod


def layer_params(s):
    attn = 2 * s["d"] * s["h"] * s["hd"] + 2 * s["d"] * s["hkv"] * s["hd"]
    ffn = (3 if s["glu"] else 2) * s["d"] * s["ff"]
    return attn + ffn


def step_flops(s, rows):
    dense = 2.0 * s["layers"] * layer_params(s)
    att = 4.0 * s["layers"] * s["h"] * s["hd"]
    head = 2.0 * s["d"] * s["v"]
    return sum(n * dense + att * (n * p + n * (n + 1) / 2)
               + (head if sampled else 0.0) for p, n, sampled in rows)


def read(ctx):
    if ctx.trace is None or not ctx.traced_steps:
        return None
    busy = trace_mod.busy_s(ctx.trace)
    flops = sum(step_flops(ctx.shapes, st["rows"]) for st in ctx.traced_steps)
    if busy <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (busy * ctx.device["peak_bf16_flops_per_s"])
