"""The reduction from a profiler trace to device busy time, idle gaps
and per-op device time: on a hand-made trace with known answers, and on
a small trace recorded on a TPU v5e during a t1mix-saturate window."""
import json
import os

import benchpaths  # noqa: F401
import pytest

from benchlib import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tpu_trace_t1mix.json")
DEV = "/device:TPU:0"


def ev(name, start_ms, dur_ms, plane=DEV, line=trace.OPS_LINE):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_ms * 1e6, "dur_ns": dur_ms * 1e6}


def test_hand_made_trace():
    # trace clock = benchmark clock (s) * 1e9 + 5e9
    events = [ev(trace.SYNC, 5000.0 + 1000.0, 0.01, "/host:CPU", "python"),
              ev("a", 6000.0 - 5.0, 10.0),       # straddles the start
              ev("b", 6010.0, 20.0),
              ev("a", 6020.0, 20.0),             # overlaps b
              ev("c", 6100.0, 50.0),             # straddles the end
              ev("x", 6050.0, 10.0, line="XLA Modules")]
    red = trace.reduce(events, 1.0, 1.12, 1.0)
    assert red["window_s"] == pytest.approx(0.12)
    # busy: [1.000, 1.005) + [1.010, 1.040) + [1.100, 1.120)
    assert red["busy_s"] == pytest.approx(0.055)
    assert red["op_s"]["a"] == pytest.approx(0.025)
    assert red["op_s"]["b"] == pytest.approx(0.020)
    assert red["op_s"]["c"] == pytest.approx(0.020)
    assert "x" not in red["op_s"]
    gaps = red["gaps"]
    assert gaps[0] == pytest.approx((1.005, 1.010))
    assert gaps[1] == pytest.approx((1.040, 1.100))
    assert trace.top_ops(red["op_s"], 2)[0][0] == "a"
    spans = [{"name": "lane_exec", "track": "lane:host", "t0": 1.03,
              "t1": 1.2}]
    labelled = trace.label_gaps(gaps, spans, 1)
    assert labelled[0][0] == "lane_exec@lane:host"
    assert labelled[0][1] == pytest.approx(0.06)


def test_a_chip_with_no_plane_is_idle():
    events = [ev(trace.SYNC, 0.0, 0.01, "/host:CPU", "python")]
    red = trace.reduce(events, 0.0, 2.0, 0.0)
    assert red["busy_s"] == 0.0 and red["gaps"] == [(0.0, 2.0)]
    with pytest.raises(ValueError):
        trace.reduce([ev("a", 0.0, 1.0)], 0.0, 1.0, 0.0)
    with pytest.raises(ValueError):
        trace.reduce(events + [ev("a", 0.0, 1.0, "/device:TPU:1")],
                     0.0, 1.0, 0.0)


def test_recorded_tpu_trace():
    rec = json.load(open(DATA))
    events = [dict(zip(("plane", "line", "name", "start_ns", "dur_ns"), e))
              for e in rec["events"]]
    red = trace.reduce(events, rec["t0"], rec["t1"], rec["t_sync"])
    # the same union, counted on a 1 us grid from the raw events
    off = trace.offset_ns(events, rec["t_sync"])
    lo, hi = rec["t0"] * 1e6, rec["t1"] * 1e6
    grid = set()
    for e in trace.device_ops(events):
        a = max(int((e["start_ns"] - off) / 1e3), int(lo))
        b = min(int((e["start_ns"] + e["dur_ns"] - off) / 1e3), int(hi))
        grid.update(range(a, b))
    assert red["busy_s"] == pytest.approx(len(grid) / 1e6, rel=1e-2, abs=5e-5)
    assert 0.0 < red["busy_s"] < red["window_s"]
    idle = sum(b - a for a, b in red["gaps"])
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-6)
    assert sum(red["op_s"].values()) >= red["busy_s"] - 1e-9
    assert red["busy_s"] == pytest.approx(rec["busy_s"], rel=1e-9)
