"""decode_step_ms.lm: median of the continuous engine's ``engine_step``
spans (one slot-batched decode step, lock wait included) in the window,
in ms."""
from benchlib import readers


def read(ctx):
    return readers.span_median_ms(ctx, "engine_step")
