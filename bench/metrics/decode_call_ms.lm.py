"""decode_call_ms.lm: median of the continuous engine's ``decode`` spans
in the window, in ms: one batched slot-step call on the decode lane,
through the tokens' copy to the host, without the lane's lock wait or
the joined rows' inserts."""
from benchlib import readers


def read(ctx):
    return readers.span_median_ms(ctx, "decode")
