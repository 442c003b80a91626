"""Observability tier (PR 9): trace recorder, propagation, audit.

Covers the tentpole guarantees: a disabled recorder is a no-op (the
REPRO_TRACE=0 contract the bench's overhead row quantifies); the ring
buffer bounds memory; drained worker batches re-ingest onto prefixed
tracks; the Chrome export validates structurally (required keys,
non-negative durations, one named thread row per track); a trace_id
survives the wire-message pickle round-trip, a router failover
resubmit, and a continuous-engine preemption; and the placement
audit's projected-vs-actual error math and utilization figures are
exact on known inputs.
"""
import io
import pickle
import threading
import time

import pytest

from repro.core.metrics import Percentile, ServeStats
from repro.obs import PlacementAudit, TraceRecorder, get_recorder
from repro.serve.router import Router, default_bucket
from repro.serve.transport import (HeartbeatMsg, ResultMsg, SubmitMsg,
                                   _recv_frame, _send_frame)


def _wait(cond, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


@pytest.fixture
def live_recorder():
    """The process-wide recorder, cleared and force-enabled for the
    test, with the prior enabled state restored after."""
    rec = get_recorder()
    was = rec.enabled
    rec.enabled = True
    rec.clear()
    yield rec
    rec.enabled = was
    rec.clear()


# ---------------------------------------------------------------------------
# recorder core
# ---------------------------------------------------------------------------
def test_disabled_recorder_records_nothing():
    rec = TraceRecorder(enabled=False)
    t = rec.now()
    rec.complete("x", "exec", t, t + 1.0, "lane:a", "tid-1", k=1)
    rec.instant("y", "fault", "lane:a")
    with rec.span("z", "exec", "lane:a"):
        pass
    assert len(rec) == 0 and rec.events() == []


def test_ring_buffer_bounds_memory():
    rec = TraceRecorder(maxlen=16, enabled=True)
    for i in range(40):
        rec.instant("e", "exec", "t", i=i)
    assert len(rec) == 16
    # oldest dropped first: the survivors are the most recent 24..39
    assert [e["args"]["i"] for e in rec.events()] == list(range(24, 40))


def test_drain_ingest_retags_tracks():
    src = TraceRecorder(enabled=True)
    t = src.now()
    src.complete("exec", "exec", t, t + 0.01, "lane:accel", "tid-7")
    src.instant("steal", "exec", "lane:host")
    batch = src.drain()
    assert len(batch) == 2 and len(src) == 0

    dst = TraceRecorder(enabled=True)
    dst.ingest(batch, track_prefix="fw1/")
    tracks = {e["track"] for e in dst.events()}
    assert tracks == {"fw1/lane:accel", "fw1/lane:host"}
    # payload untouched: trace_id still stitches across the hop
    assert dst.events()[0]["args"]["trace_id"] == "tid-7"


def test_export_chrome_validates(tmp_path):
    rec = TraceRecorder(enabled=True)
    t = rec.now()
    rec.complete("a", "exec", t, t + 0.002, "lane:accel", "tid-1")
    rec.complete("b", "exec", t + 0.001, t + 0.004, "lane:host", "tid-1")
    rec.instant("watchdog_kill", "fault", "lane:host")
    rec.ingest([{"name": "c", "cat": "exec", "ph": "X",
                 "ts": (rec._anchor + t) * 1e6, "dur": 5.0,
                 "track": "lane:accel", "args": {}}],
               track_prefix="fw0/")
    path = tmp_path / "trace.json"
    n = rec.export_chrome(str(path))
    assert n == 4

    import json
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    data = [e for e in evs if e["ph"] != "M"]
    # every data event carries the required keys; durations and
    # rebased timestamps are non-negative
    for e in data:
        assert {"name", "cat", "ph", "ts", "pid", "tid"} <= set(e)
        assert e["ts"] >= 0.0
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
        if e["ph"] == "i":
            assert e["s"] == "t"
    # one named thread row per distinct track, and the ingest prefix
    # became its own named process
    thread_names = [e for e in meta if e["name"] == "thread_name"]
    assert len(thread_names) == 3       # lane:accel, lane:host, fw0/…
    proc_names = {e["args"]["name"] for e in meta
                  if e["name"] == "process_name"}
    assert proc_names == {"serve", "fw0"}
    # the two processes must not share a pid
    assert len({e["pid"] for e in meta
                if e["name"] == "process_name"}) == 2


def test_recorder_is_thread_safe_under_concurrent_writers():
    rec = TraceRecorder(maxlen=100_000, enabled=True)

    def writer(k):
        for i in range(500):
            rec.instant("e", "exec", f"t{k}", i=i)

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(rec) == 2000


def test_span_reaches_the_profiler_trace(tmp_path):
    """While the recorder is on, a span is also a host annotation in the
    profiler's ``.xplane.pb``, under its own name."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    rec = TraceRecorder(enabled=True)
    with jax.profiler.trace(str(tmp_path)):
        with rec.span("obs_probe_span", "exec", "lane:host") as args:
            jnp.arange(8.0).sum().block_until_ready()
            args["n"] = 8
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "obs_probe_span" in names
    (ev,) = rec.events()
    assert ev["name"] == "obs_probe_span" and ev["args"] == {"n": 8}


def test_disabled_recorder_serves_without_events_or_annotations(
        monkeypatch):
    """With the recorder off (``REPRO_TRACE=0``), serving an engine
    request and a lane request records no event and constructs no
    profiler annotation."""
    import jax

    from repro.core.hybrid_executor import DeviceGroup
    from repro.serve.scheduler import Scheduler

    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    rec = get_recorder()
    monkeypatch.setattr(rec, "enabled", False)
    rec.clear()
    sched = Scheduler(groups=[DeviceGroup("accel", [], "accel"),
                              DeviceGroup("host", [], "host")])
    sched.submit("lbm", {"d": 8, "n_steps": 10, "seed": 5,
                         "continuous": True}).result(timeout=300)
    sched.submit("hist", {"n": 1 << 10, "n_bins": 16}).result(timeout=120)
    sched.shutdown()
    assert rec.events() == [] and made == []
    # the count is live: the same class, entered by an enabled span
    with TraceRecorder(enabled=True).span("probe", "exec", "t"):
        pass
    assert made == [("probe",)]


# ---------------------------------------------------------------------------
# propagation: wire pickle, router failover, engine preemption
# ---------------------------------------------------------------------------
def test_trace_id_survives_wire_frame_roundtrip():
    """The exact framing path ProcWorker uses (length-prefixed pickle)
    must carry trace_id out and span batches back."""
    buf = io.BytesIO()
    _send_frame(buf, SubmitMsg(req_id=3, workload="wl",
                               payload={"i": 1}, trace_id="123-9"))
    spans = ({"name": "resolve", "cat": "request", "ph": "i",
              "ts": 1.0, "track": "sched", "s": "t",
              "args": {"trace_id": "123-9"}},)
    _send_frame(buf, HeartbeatMsg(t=0.0, load=1.0,
                                  stats={"completed": 1}, spans=spans))
    buf.seek(0)
    sub = _recv_frame(buf)
    hb = _recv_frame(buf)
    assert sub.trace_id == "123-9"
    assert hb.spans[0]["args"]["trace_id"] == "123-9"
    # defaults stay wire-compatible with writers that omit the fields
    assert pickle.loads(pickle.dumps(SubmitMsg(1, "wl"))).trace_id is None
    assert pickle.loads(pickle.dumps(HeartbeatMsg(0.0))).spans == ()


class _HoldWorker:
    """Scripted transport: holds submits until answered (test_fleet's
    ToyWorker, reduced to what the trace assertions need)."""

    def __init__(self, name, auto=True):
        self.name = name
        self.auto = auto
        self.held = []
        self.transport_alive = True
        self._on_result = None

    def start(self, on_result, on_heartbeat):
        self._on_result = on_result

    def submit(self, msg):
        if not self.transport_alive:
            return False
        if self.auto:
            self._on_result(self.name, ResultMsg(msg.req_id, ok=True,
                                                 value=("ok", self.name)))
        else:
            self.held.append(msg)
        return True

    def kill(self):
        self.transport_alive = False

    def shutdown(self, timeout=10.0):
        pass


def test_failover_resubmit_keeps_trace_id(live_recorder):
    """A worker death re-sends the pending request under a FRESH wire
    req_id but the SAME trace_id, and the router marks the hop with a
    failover_resubmit instant carrying that id."""
    a, b = _HoldWorker("wa", auto=False), _HoldWorker("wb", auto=False)
    with Router([a, b], hb_timeout_s=60.0, max_retries=2) as r:
        # a payload whose affinity owner is wa (md5 ring is stable)
        payload = next(
            {"i": i} for i in range(256)
            if r._ring.lookup(f"wl|{default_bucket({'i': i})}") == "wa")
        fut = r.submit("wl", payload)
        assert _wait(lambda: len(a.held) == 1)
        orig = a.held[0]
        assert orig.trace_id is not None
        a.kill()
        assert _wait(lambda: len(b.held) == 1)
        resub = b.held[0]
        assert resub.req_id != orig.req_id
        assert resub.trace_id == orig.trace_id
        b._on_result("wb", ResultMsg(resub.req_id, ok=True, value="v"))
        assert fut.result(timeout=10) == "v"
    hops = [e for e in live_recorder.events()
            if e["name"] == "failover_resubmit"]
    assert len(hops) == 1
    assert hops[0]["args"]["trace_id"] == orig.trace_id
    assert hops[0]["args"]["from_worker"] == "wa"


def test_engine_preemption_cancel_carries_trace_id(live_recorder):
    """Resolving a live continuous request's future externally (the
    hedge-winner/preemption path) frees its slot at a step boundary
    and emits an engine_cancel instant with the request's trace_id."""
    from repro.core.hybrid_executor import DeviceGroup
    from repro.serve.scheduler import Scheduler

    groups = [DeviceGroup("accel", [], "accel"),
              DeviceGroup("host", [], "host")]
    sched = Scheduler(groups=groups)
    fut = sched.submit("lbm", {"d": 8, "n_steps": 120, "seed": 5,
                               "continuous": True},
                       trace_id="tid-preempt")
    assert _wait(lambda: sched._engines, timeout=60)
    eng = next(iter(sched._engines.values()))
    assert _wait(lambda: eng.steps >= 3, timeout=60)
    fut._resolve("preempted")          # external resolve mid-decode
    assert _wait(lambda: any(
        e["name"] == "engine_cancel"
        and e["args"].get("trace_id") == "tid-preempt"
        for e in live_recorder.events()), timeout=30)
    sched.shutdown()


def test_scheduler_spans_share_one_trace_id(live_recorder):
    """One real request leaves a stitched lifecycle: submit instant,
    queue_wait + placement + lane_exec spans and a resolve instant, all
    under the caller's trace_id."""
    from repro.serve.scheduler import Scheduler

    sched = Scheduler(batch_window_s=0.0)
    sched.submit("hist", {"n": 1 << 10, "n_bins": 16},
                 trace_id="tid-life").result(timeout=120)
    sched.shutdown()
    mine = [e for e in live_recorder.events()
            if e["args"].get("trace_id") == "tid-life"]
    names = {e["name"] for e in mine}
    assert {"submit", "queue_wait", "placement", "lane_exec",
            "resolve"} <= names
    # spans are well-formed: non-negative durations, lane_exec on a
    # lane track
    for e in mine:
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
    lane_tracks = {e["track"] for e in mine if e["name"] == "lane_exec"}
    assert all(t.startswith("lane:") for t in lane_tracks)


# ---------------------------------------------------------------------------
# placement audit
# ---------------------------------------------------------------------------
def test_placement_audit_error_math_and_utilization():
    clock = {"t": 100.0}
    audit = PlacementAudit(clock=lambda: clock["t"])
    audit.record(1, "conv", "dedicated", projected_s=0.010,
                 alternatives={"shared": 0.02})
    audit.record(2, "conv", "dedicated", projected_s=0.020)
    audit.record(3, "hist", "shared", projected_s=0.005)
    audit.stamp(1, actual_s=0.012)     # abs err 2 ms, rel 1/6
    audit.stamp(2, actual_s=0.010)     # abs err 10 ms, rel 1.0
    audit.stamp(99, actual_s=1.0)      # never recorded: no-op
    audit.lane_busy("accel", 5.0)
    audit.lane_busy("accel", 1.0)
    audit.lane_busy("host", 3.0)
    clock["t"] = 110.0                 # 10 s window

    s = audit.summary()
    conv = s["placements"]["conv:dedicated"]
    assert conv["n"] == 2
    assert conv["mean_abs_err_s"] == pytest.approx((0.002 + 0.010) / 2)
    assert conv["mean_rel_err"] == pytest.approx(
        (0.002 / 0.012 + 0.010 / 0.010) / 2)
    assert conv["max_rel_err"] == pytest.approx(1.0)
    assert s["open_decisions"] == 1    # req 3 never resolved
    assert s["lane_utilization"] == pytest.approx(
        {"accel": 0.6, "host": 0.3})
    assert s["resource_efficiency"] == pytest.approx(0.45)
    assert s["window_s"] == pytest.approx(10.0)

    # duplicate stamp is a no-op (resolve-exactly-once upstream)
    audit.stamp(1, actual_s=9.9)
    assert audit.summary()["placements"]["conv:dedicated"]["n"] == 2


# ---------------------------------------------------------------------------
# satellites: stats locking + percentile window knob
# ---------------------------------------------------------------------------
def test_serve_stats_inc_is_atomic_under_contention():
    st = ServeStats()

    def bump():
        for _ in range(2000):
            st.inc(submitted=1, completed=1)

    threads = [threading.Thread(target=bump) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    snap = st.snapshot()
    assert st.submitted == st.completed == 16_000
    assert snap["submitted"] == snap["completed"] == 16_000
    assert st.in_flight == 0


def test_percentile_window_from_env(monkeypatch):
    monkeypatch.setenv("REPRO_SERVE_PCTL_WINDOW", "32")
    p = Percentile()
    for i in range(100):
        p.observe(float(i))
    assert p.n == 32                   # env-sized ring
    assert p.quantile(0.0) == 68.0     # oldest samples dropped
    assert Percentile(maxlen=8)._buf.maxlen == 8     # explicit wins
    monkeypatch.setenv("REPRO_SERVE_PCTL_WINDOW", "junk")
    assert Percentile()._buf.maxlen == 256           # bad value: default
