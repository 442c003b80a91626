"""join_insert_ms.lm: median of the continuous engine's ``engine_insert``
spans in the window, in ms: one joined row's state copied to the decode
lane and written into its slot, waited for."""
from benchlib import readers


def read(ctx):
    return readers.span_median_ms(ctx, "engine_insert")
