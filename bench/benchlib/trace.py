"""Reduction of a ``jax.profiler`` trace to device busy time, idle gaps
and per-operation device time.

A trace is first flattened to plain event records ``{"plane", "line",
"name", "start_ns", "dur_ns"}`` (``load``), so the reduction runs on a
small recorded list in the tests.  Device operations are the events on
the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane.  A host marker
(``SYNC``, written through ``jax.profiler.TraceAnnotation`` at a known
``time.monotonic()``) puts the trace on the benchmark's clock.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

from . import stats

SYNC = "bench_sync"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find(trace_dir: str) -> str:
    """The one ``.xplane.pb`` a trace directory holds."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {trace_dir}")
    return paths[0]


def load(path: str) -> List[dict]:
    """Device-op events and sync markers of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        on_device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if on_device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if on_device or ev.name == SYNC:
                    out.append({"plane": plane.name, "line": line.name,
                                "name": ev.name,
                                "start_ns": float(ev.start_ns),
                                "dur_ns": float(ev.duration_ns)})
    return out


def device_ops(events: Iterable[dict]) -> List[dict]:
    return [e for e in events if e["plane"].startswith(DEVICE_PREFIX)
            and e["line"] == OPS_LINE]


def offset_ns(events: Iterable[dict], sync_mono_s: float) -> float:
    """Trace time minus benchmark time (ns), from the sync marker."""
    marks = [e for e in events if e["name"] == SYNC]
    if not marks:
        raise ValueError("no sync marker in the trace")
    return marks[0]["start_ns"] - sync_mono_s * 1e9


def reduce(events: List[dict], t0_s: float, t1_s: float, sync_mono_s: float,
           n_devices: int = 1) -> dict:
    """Busy and idle time of the ``n_devices`` chips between benchmark
    times ``t0_s`` and ``t1_s`` (seconds on ``time.monotonic()``),
    averaged over the chips, with per-op device time and the idle gaps
    (benchmark clock).  A chip that ran nothing has no plane in the trace
    and counts as idle throughout.  Ops that straddle the window count
    only inside it."""
    off = offset_ns(events, sync_mono_s)
    ops = device_ops(events)
    planes = [f"{DEVICE_PREFIX}{i}" for i in range(n_devices)]
    extra = sorted({e["plane"] for e in ops} - set(planes))
    if extra:
        raise ValueError(f"trace holds planes of other chips: {extra}")
    lo, hi = t0_s, t1_s
    busy, per_op = [], defaultdict(float)
    all_gaps: List[Tuple[float, float]] = []
    for plane in planes:
        iv = []
        for e in ops:
            if e["plane"] != plane:
                continue
            a = (e["start_ns"] - off) / 1e9
            b = a + e["dur_ns"] / 1e9
            a2, b2 = max(a, lo), min(b, hi)
            if b2 <= a2:
                continue
            iv.append((a2, b2))
            per_op[e["name"]] += b2 - a2
        busy.append(stats.union_length(iv, lo, hi))
        all_gaps += stats.gaps(iv, lo, hi)
    return {"window_s": hi - lo, "busy_s": sum(busy) / n_devices,
            "n_devices": n_devices,
            "op_s": dict(per_op), "gaps": all_gaps}


def top_ops(op_s: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(op_s.items(), key=lambda kv: -kv[1])[:n]]


def label_gaps(gaps: List[Tuple[float, float]], spans: List[dict],
               n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps, each named by the host span that
    covers most of it (``name@track``), or ``"no host span"``."""
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, cover = "no host span", 0.0
        for s in spans:
            c = min(b, s["t1"]) - max(a, s["t0"])
            if c > cover:
                best, cover = f"{s['name']}@{s['track']}", c
        out.append([best, b - a])
    return out
