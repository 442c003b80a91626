"""host_lane_busy.lm: busy share (%) of the host lane over the window:
the union of the program's spans on the ``lane:host`` track (each holds
that lane's lock), clipped to the window, over the window.

The spans are on the host clock, which is right for the host lane only;
the TPU lane's share is ``tpu_idle.lm``'s, from the device trace.  Only
spans that end inside the window are in ``ctx.spans``, so a call cut by
the window's close is lost: at most one decode step (about 1.1 s of 51
s on the host lane)."""
from benchlib import stats

TRACK = "lane:host"


def read(ctx):
    iv = [(s["t0"], s["t1"]) for s in ctx.spans if s["track"] == TRACK]
    if not iv:
        return None
    busy = stats.union_length(iv, ctx.t0, ctx.t1)
    return 100.0 * busy / (ctx.t1 - ctx.t0)
