"""prompt_cached_share: share of admitted prompt positions that the
scheduler did not compute, over the measured window.

Each step the serving loop sums how far the admitting requests' prompt cursors
(``Request.filled``) moved, and reads how many prompt tokens the step
computed (``stats["prefill_tokens"]``, a program counter).  Positions a
prefix hit skips move the cursor without being computed:

    share = 100 * (1 - computed / cursor advance)

It reads 0 while local prefix hits still recompute the whole prompt."""


def read(ctx):
    adv = sum(s["filled_adv"] for s in ctx.window_steps)
    done = sum(s["prefill_tokens"] for s in ctx.window_steps)
    if adv <= 0:
        return None
    return 100.0 * (1.0 - done / adv)
