"""Arithmetic shared by the metric readers under ``bench/metrics``.

A reader takes the run's context and returns a number, or ``None`` when
the run holds nothing for it to read."""
from __future__ import annotations

from typing import Optional

from . import stats


def tpu_idle_pct(ctx) -> Optional[float]:
    """100 * (1 - busy / window) over the traced window, from the device
    trace."""
    red = ctx.trace
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def span_median_ms(ctx, name: str, track_prefix: str = "") -> Optional[float]:
    """Median duration (ms) of the program's ``name`` spans that ended in
    the window."""
    d = [s["t1"] - s["t0"] for s in ctx.spans
         if s["name"] == name and s["track"].startswith(track_prefix)]
    return 1e3 * stats.median(d) if d else None

