"""The readers of the continuous engine's spans and counter, on a
hand-made span list with known answers, and all six in one traced run
of a cell on the CPU at a tiny size."""
import json
import math
import os
import subprocess
import sys
from types import SimpleNamespace

import benchpaths  # noqa: F401
import pytest

from benchlib import files
from cpu_run import TINY_LM

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("decode_call_ms.lm", "step_batch.lm", "join_insert_ms.lm",
           "step_boundary_ms.lm", "host_lane_busy.lm", "engine_init_s.lm")


def span(name, t0, t1, track="engine:lm", **args):
    return {"name": name, "track": track, "t0": t0, "t1": t1, "args": args}


def ctx(spans, counters=None):
    return SimpleNamespace(spans=spans, counters=counters or {},
                           t0=10.0, t1=20.0)


SPANS = [
    span("engine_step", 10.0, 11.2, step=0, n_live=2),
    span("lane_wait", 10.0, 10.1, step=0, phase="step"),
    span("engine_insert", 10.1, 10.3, "lane:host", step=0, slot=0),
    span("engine_insert", 10.3, 10.4, "lane:host", step=0, slot=1),
    span("decode", 10.4, 11.2, "lane:host", step=0, n_live=2),
    span("engine_boundary", 11.2, 11.203, step=0),
    span("engine_step", 11.203, 12.2, step=1, n_live=3),
    span("engine_insert", 11.203, 11.5, "lane:host", step=1, slot=2),
    span("decode", 11.5, 12.2, "lane:host", step=1, n_live=3),
    span("engine_boundary", 12.2, 12.201, step=1),
    span("decode", 12.201, 13.2, "lane:host", step=2, n_live=3),
    span("prefill_call", 9.0, 13.5, "lane:accel", rows=1),
    # overlaps the host lane's start: the union counts it once
    span("lane_exec", 9.5, 10.2, "lane:host"),
]


@pytest.mark.parametrize("name,want", [
    ("decode_call_ms.lm", 800.0),
    ("step_batch.lm", 8 / 3),
    ("join_insert_ms.lm", 200.0),
    ("step_boundary_ms.lm", 1.0),       # nearest rank: the lower of two
    # [10, 11.2) + [11.203, 12.2) + [12.201, 13.2): 3.196 s of 10 s
    ("host_lane_busy.lm", 31.96),
    ("engine_init_s.lm", 12.5),
])
def test_reader_on_hand_made_spans(name, want):
    got = files.module("metrics", name).read(
        ctx(SPANS, {"engine_init_s": 12.5, "engine_steps": 3}))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_its_spans(name):
    """What the parent program gives: ``engine_step`` and ``prefill``
    spans only, and no ``engine_init_s`` counter."""
    old = [span("engine_step", 10.0, 11.0, n_live=2, joins=0),
           span("prefill", 9.0, 10.5)]
    assert files.module("metrics", name).read(
        ctx(old, {"engine_steps": 3})) is None


RUN = r"""
import json, sys, time
T = time.monotonic()
import cpu_run
from benchlib import files, harness
bench = files.benchmark()
# lm_mfu needs a chip's published peaks: this run is on the CPU
bench["per_layer"] = [m for m in bench["per_layer"] if m["name"] != "lm_mfu"]
res = harness.run_cell(
    "xlstm-batch", 2**31 + 78, 3.0, True, T, require_chip=False,
    config_overrides=cpu_run.TINY_LM,
    traffic_overrides={"prompt_len": 32, "new_tokens": 8, "clients": 4},
    state_dir=sys.argv[1], bench=bench)
print(json.dumps(res), flush=True)
"""


def test_traced_cpu_run_reports_engine_metrics(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_AUTOTUNE="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    res = subprocess.run([sys.executable, "-c", RUN, str(tmp_path)],
                         capture_output=True, text=True, timeout=280,
                         env=env, cwd=HERE)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["correct"], out["compared"]
    for name in READERS + ("decode_step_ms.lm", "tpu_idle.lm"):
        assert math.isfinite(out["metrics"][name]["value"]), name
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 1 <= m["step_batch.lm"] <= TINY_LM["n_slots"]
    assert 0 < m["host_lane_busy.lm"] <= 100
    assert m["decode_call_ms.lm"] <= m["decode_step_ms.lm"]
