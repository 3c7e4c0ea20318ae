"""paged_chunk_attention_roofline: the chunk-attention kernel's least time
over its device time, summed over the traced steps.

Least time per call = max(FLOPs / peak FLOP/s, bytes / HBM bytes/s), with
FLOPs and bytes from kernels/paged_chunk_attention.py; one call per layer
per step.  Which of the two bounds the sum is noted on an earlier line.
No kernel event in the trace: no value."""

KERNEL = "paged_chunk_attention"


def read(ctx):
    if ctx.trace is None or not ctx.traced_steps:
        return None
    k = ctx.kernel(KERNEL)
    events = [o for dev in ctx.trace.ops
              for o in ctx.trace.ops_in_window(dev) if k.matches(o[0], o[3])]
    if not events:
        return None
    dev_s = sum(o[2] for o in events) * 1e-9
    peak = ctx.device["peak_bf16_flops_per_s"]
    bw = ctx.device["hbm_bytes_per_s"]
    layers = ctx.shapes["layers"]
    t_flop = t_byte = least = 0.0
    for st in ctx.traced_steps:
        f = k.flops(ctx.shapes, st["rows"]) / peak
        b = k.bytes_moved(ctx.shapes, st["rows"]) / bw
        t_flop += layers * f
        t_byte += layers * b
        least += layers * max(f, b)
    ctx.notes.append(f"{KERNEL}: {len(events)} events over "
                     f"{len(ctx.traced_steps)} steps x {layers} layers; "
                     f"device {dev_s} s, flop-bound {t_flop} s, "
                     f"byte-bound {t_byte} s "
                     f"({'memory' if t_byte >= t_flop else 'compute'}-bound)")
    return 100.0 * least / dev_s if dev_s > 0 else None
