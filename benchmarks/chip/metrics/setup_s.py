"""setup_s: seconds from process start to the first measured step —
weights, engine, compile (or compile-cache loads) and traffic warm-up."""


def read(ctx):
    return ctx.setup_s
