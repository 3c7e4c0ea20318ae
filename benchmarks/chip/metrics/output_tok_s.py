"""output_tok_s: tokens the host saw in the window over the window's
length (all the work and all the time of the window)."""


def read(ctx):
    lat = ctx.latency
    return lat["tokens"] / lat["window_s"] if lat["tokens"] else None
