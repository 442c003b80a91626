"""step_batch.lm: mean number of live slots (``n_live``) over the
continuous engine's ``decode`` spans in the window."""


def read(ctx):
    n = [s["args"]["n_live"] for s in ctx.spans if s["name"] == "decode"]
    return sum(n) / len(n) if n else None
