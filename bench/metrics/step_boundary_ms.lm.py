"""step_boundary_ms.lm: median of the continuous engine's
``engine_boundary`` spans in the window, in ms: the host work between
two decode steps (outputs collected, rows evicted and finished, the
next join chosen)."""
from benchlib import readers


def read(ctx):
    return readers.span_median_ms(ctx, "engine_boundary")
