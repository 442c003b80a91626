"""expert_load_max.moe: median, over the continuous engine's
``prefill_call`` spans in the window, of their ``expert_load_max`` arg:
the busiest routed expert's assignments in the prefill over the mean per
expert (tokens x experts per token / routed experts), the largest over
the expert layers.  1 is an even load; a model without routed experts
puts no such arg on its spans."""
from benchlib import stats


def read(ctx):
    v = [s["args"]["expert_load_max"] for s in ctx.spans
         if s["name"] == "prefill_call" and "expert_load_max" in s["args"]]
    return stats.median(v) if v else None
