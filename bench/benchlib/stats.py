"""Latency and rate arithmetic over one measured window.

A request is a record ``(due, done, ok)`` on the benchmark's clock in
seconds: ``due`` when the traffic schedule says it is sent, ``done`` when
its result came back (``None`` if it never did), ``ok`` whether it came
back as an answer rather than an error or a refusal.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple

INF = float("inf")


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank ``p``-th percentile (0 < p <= 100): the smallest value
    with at least ``p`` percent of the values at or below it.  Infinite
    values (requests that failed or never finished) sort last."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    rank = max(int(math.ceil(p / 100.0 * len(xs))), 1)
    return xs[rank - 1]


def beyond(n: int, p: float) -> int:
    """How many of ``n`` samples lie above the ``p``-th percentile."""
    return n - max(int(math.ceil(p / 100.0 * n)), 1)


def latencies(records: Iterable[Tuple[float, Optional[float], bool]],
              t0: float, t1: float) -> List[float]:
    """Due-to-result latency of every request due in ``[t0, t1)``; a
    request that failed, was refused or never returned counts as
    infinitely late."""
    out = []
    for due, done, ok in records:
        if t0 <= due < t1:
            out.append(done - due if ok and done is not None else INF)
    return out


def rate(count: float, t0: float, t1: float) -> float:
    """Count per second over the window ``[t0, t1)``."""
    if t1 <= t0:
        raise ValueError("empty window")
    return count / (t1 - t0)


def union_length(intervals: Iterable[Tuple[float, float]],
                 lo: float = -INF, hi: float = INF) -> float:
    """Length of the union of ``[a, b)`` intervals, clipped to
    ``[lo, hi)``."""
    spans = sorted((max(a, lo), min(b, hi)) for a, b in intervals)
    total, cur_a, cur_b = 0.0, None, None
    for a, b in spans:
        if b <= a:
            continue
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def gaps(intervals: Iterable[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi)`` that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if b <= cur:
            continue
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)
