"""Idle share (%) of the TPU over the traced window: 1 - the union of
device-op intervals in the profiler trace over the window."""
from benchlib import readers


def read(ctx):
    return readers.tpu_idle_pct(ctx)
