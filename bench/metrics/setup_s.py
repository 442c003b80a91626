"""setup_s: seconds from the start of the benchmark's process code to the
first request due: loading, weights, warm-up and any compilation."""


def read(ctx):
    return ctx.setup_s
