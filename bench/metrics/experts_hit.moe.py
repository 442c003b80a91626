"""experts_hit.moe: mean, over the continuous engine's ``decode`` spans
in the window, of their ``experts_hit`` arg: the distinct routed experts
that the step's live slots chose, the mean over the expert layers (of
64 for DeepSeek-V2-Lite): how much of the expert weights a decode step
needs.  A model without routed experts puts no such arg on its spans."""


def read(ctx):
    v = [s["args"]["experts_hit"] for s in ctx.spans
         if s["name"] == "decode" and "experts_hit" in s["args"]]
    return sum(v) / len(v) if v else None
