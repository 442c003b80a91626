"""lm_tokens_per_s: output tokens emitted inside the window per window
second; the tokens of an engine call (a prefill, a decode step) are
spread evenly over the call, so a call cut by the window's edge counts
the share of it inside."""
from benchlib import stats


def read(ctx):
    return stats.rate(ctx.system.tokens.tokens_in(ctx.t0, ctx.t1),
                      ctx.t0, ctx.t1)
