"""Continuous batching: iteration-level scheduling engine (PR 6).

Covers the tentpole guarantees: join-at-step-boundary and eviction
demux are bit-identical to solo decode; iteration-boundary yield
points make the sequential adapters preemptible and cross-request
stackable; accounting stays exact under step-quantum dispatch; a
fresh process places the engine's lanes with zero probe runs; and
the hist/conv merge hooks stack same-bucket requests exactly.
"""
import collections
import threading
import time

import numpy as np
import pytest

from repro.configs import registry
from repro.core.hybrid_executor import DeviceGroup
from repro.models import model_zoo, param
from repro.serve.scheduler import Scheduler
from repro.serve.serve_step import generate
from repro.workloads import requests as adapters

PROMPT_LEN, NEW_TOKENS = 8, 6


@pytest.fixture(scope="module")
def lm():
    """One reduced arch + registered continuous adapter per module:
    the stepper is shared state (that is the point — every request of
    the workload stacks into one engine)."""
    import jax

    cfg = registry.get("minicpm3-4b").reduced()
    params = param.values(model_zoo.init(cfg, jax.random.key(0)))
    wl = adapters.make_continuous_lm_adapter(
        cfg, params, prompt_len=PROMPT_LEN, new_tokens=NEW_TOKENS,
        name="serve-lm-cb/test")
    assert adapters.wait_precompiled(timeout=300)
    return cfg, params, wl


def _solo(cfg, params, prompt):
    out = generate(cfg, params, prompt, NEW_TOKENS,
                   cache_len=PROMPT_LEN + NEW_TOKENS + 1)
    return np.asarray(out)


def _two_groups():
    return [DeviceGroup("accel", [], "accel"),
            DeviceGroup("host", [], "host")]


# ---------------------------------------------------------------------------
# tentpole: join / evict bit-identity vs solo decode
# ---------------------------------------------------------------------------
def test_lm_engine_join_evict_bit_identical(lm):
    """A burst of same-bucket LM requests stacks into one slot-batched
    step loop; every demuxed output must equal its solo generate()
    bit-for-bit, and the step count must show actual stacking (fewer
    batched steps than total row-steps)."""
    cfg, params, wl = lm
    sched = Scheduler(groups=_two_groups())
    futs = [sched.submit(wl, {"batch": 1, "seed": s}) for s in range(5)]
    outs = [np.asarray(f.result(timeout=300)) for f in futs]
    snap = sched.stats.snapshot()
    sched.shutdown()
    for s, out in enumerate(outs):
        spec = adapters.make_request(wl, {"batch": 1, "seed": s})
        np.testing.assert_array_equal(out, _solo(cfg, params,
                                                 spec.arrays[0]))
    assert snap["engine_joins"] == 5
    assert snap["engine_evictions"] == 5
    # 5 rows x 6 steps = 30 row-steps; stacking must beat one-at-a-time
    assert 0 < snap["engine_steps"] < 5 * NEW_TOKENS


def test_lm_engine_multirow_request_demux(lm):
    """A batch-3 request spreads over three slots; assemble must
    restore row order exactly."""
    cfg, params, wl = lm
    sched = Scheduler(groups=_two_groups())
    out = np.asarray(sched.submit(wl, {"batch": 3, "seed": 9})
                     .result(timeout=300))
    sched.shutdown()
    spec = adapters.make_request(wl, {"batch": 3, "seed": 9})
    np.testing.assert_array_equal(out, _solo(cfg, params, spec.arrays[0]))


def test_lm_engine_disabled_falls_back_to_monolithic(lm, monkeypatch):
    """REPRO_SERVE_CONTINUOUS=0 must route the same workload through
    the monolithic run_one path — same results, no engine."""
    monkeypatch.setenv("REPRO_SERVE_CONTINUOUS", "0")
    cfg, params, wl = lm
    sched = Scheduler(groups=_two_groups())
    out = np.asarray(sched.submit(wl, {"batch": 1, "seed": 4})
                     .result(timeout=300))
    snap = sched.stats.snapshot()
    sched.shutdown()
    spec = adapters.make_request(wl, {"batch": 1, "seed": 4})
    np.testing.assert_array_equal(out, _solo(cfg, params, spec.arrays[0]))
    assert snap["engine_steps"] == 0 and not sched.engine_placements


# ---------------------------------------------------------------------------
# tentpole: disaggregated cold-start placement, zero probes
# ---------------------------------------------------------------------------
def test_cold_start_places_engine_with_zero_probes(lm):
    """A fresh scheduler must pick the prefill and decode lanes purely
    from the CostTerms priors — no probe may run."""
    _, _, wl = lm
    sched = Scheduler(groups=_two_groups())
    sched.submit(wl, {"batch": 1, "seed": 2}).result(timeout=300)
    snap = sched.stats.snapshot()
    plan = sched.engine_placements.get(wl)
    sched.shutdown()
    assert snap["probe_runs"] == 0
    assert plan is not None
    assert plan.prefill_group in ("accel", "host")
    assert plan.decode_group in ("accel", "host")
    assert plan.est_prefill_s > 0 and plan.est_decode_s > 0


# ---------------------------------------------------------------------------
# tentpole: iterative adapters become preemptible + stackable
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("wl,payload,solo", [
    ("listrank", {"n": 1 << 10, "seed": 3, "continuous": True},
     lambda: __import__("repro.workloads.listrank", fromlist=["x"])
     .pointer_jump_rank(adapters._listrank_inputs(1 << 10, 3))),
    ("lbm", {"d": 8, "n_steps": 3, "seed": 1, "continuous": True},
     lambda: _lbm_solo(8, 3, 1)),
    ("dither", {"h": 32, "w": 32, "seed": 2, "continuous": True},
     lambda: __import__("repro.workloads.dither", fromlist=["x"])
     .fsd_dither(adapters._dither_inputs(32, 32, 2))),
])
def test_iterative_engine_bit_identical(wl, payload, solo):
    sched = Scheduler(groups=_two_groups())
    out = np.asarray(sched.submit(wl, payload).result(timeout=300))
    sched.shutdown()
    if wl == "lbm":
        np.testing.assert_allclose(out, np.asarray(solo()), **LBM_TOL)
    else:
        np.testing.assert_array_equal(out, np.asarray(solo()))


# lbm's BGK step (workloads/lbm.py) forms its moments with float
# einsums, which XLA may reassociate once the step is vmapped over the
# engine's slots: the slot-stacked state then drifts from the solo state
# by about one ulp per step (max abs diff 8.9e-8 after 3 steps on
# XLA:CPU, JAX 0.9.0).  Integer and thresholded steppers (listrank,
# dither) stay bit-identical and are compared exactly.
LBM_TOL = dict(rtol=1e-5, atol=1e-6)


def _lbm_solo(d, n_steps, seed):
    from repro.workloads import lbm

    cur = adapters._lbm_state(d, seed)
    for _ in range(n_steps):
        cur = lbm.step_all(cur)
    return cur


def test_iterative_requests_stack_cross_request():
    """Two live lbm requests must share the vmapped slot state
    (max_live == 2) and still both match the sequential solo run."""
    sched = Scheduler(groups=_two_groups())
    n_steps = 48
    futs = [sched.submit("lbm", {"d": 8, "n_steps": n_steps, "seed": s,
                                 "continuous": True})
            for s in (1, 2)]
    outs = [np.asarray(f.result(timeout=300)) for f in futs]
    eng = next(iter(sched._engines.values()))
    snap = eng.snapshot()
    sched.shutdown()
    for s, out in zip((1, 2), outs):
        np.testing.assert_allclose(out, np.asarray(_lbm_solo(8, n_steps, s)),
                                   **LBM_TOL)
    assert snap["max_live"] == 2
    assert snap["evictions"] == 2
    # stacked: strictly fewer batched steps than sequential row-steps
    assert snap["steps"] < 2 * n_steps


def test_step_loop_preempts_at_iteration_boundaries():
    """The step loop releases its lane locks between steps; holding
    those locks from outside must stall it mid-request (at a step
    boundary, not mid-kernel) and releasing must let it finish."""
    sched = Scheduler(groups=_two_groups())
    fut = sched.submit("lbm", {"d": 8, "n_steps": 120, "seed": 5,
                               "continuous": True})
    deadline = time.monotonic() + 60
    while not sched._engines and time.monotonic() < deadline:
        time.sleep(0.005)
    eng = next(iter(sched._engines.values()))
    while eng.steps < 3 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert eng.steps >= 3, "engine never started stepping"

    for lk in eng.step_locks:           # preempt: take the decode lane
        lk.acquire()
    try:
        s0 = eng.steps
        time.sleep(0.2)
        # at most one in-flight step finishes; the loop then blocks
        assert eng.steps <= s0 + 1
        assert not fut.done()
    finally:
        for lk in reversed(eng.step_locks):
            lk.release()

    out = np.asarray(fut.result(timeout=300))
    sched.shutdown()
    np.testing.assert_allclose(out, np.asarray(_lbm_solo(8, 120, 5)),
                               **LBM_TOL)


# ---------------------------------------------------------------------------
# accounting under step-quantum dispatch
# ---------------------------------------------------------------------------
def test_accounting_invariant_under_step_quantum(lm):
    """submitted == completed + failed + rejected + shed + in-flight at
    every observation point, and in-flight drains to zero."""
    _, _, wl = lm
    sched = Scheduler(groups=_two_groups())
    futs = [sched.submit(wl, {"batch": 1, "seed": s}) for s in range(4)]
    futs.append(sched.submit("listrank", {"n": 1 << 10, "seed": 0,
                                          "continuous": True}))
    futs.append(sched.submit("dither", {"h": 32, "w": 32, "seed": 1,
                                        "continuous": True}))
    st = sched.stats
    assert st.submitted == (st.completed + st.failed + st.rejected_full
                            + st.rejected_shutdown + st.shed_deadline
                            + st.in_flight)
    for f in futs:
        f.result(timeout=300)
    deadline = time.monotonic() + 30
    while st.in_flight and time.monotonic() < deadline:
        time.sleep(0.01)
    sched.shutdown()
    assert st.submitted == 6 == st.completed
    assert st.in_flight == 0


def test_engine_shutdown_finishes_in_flight(lm):
    """shutdown() must resolve every submitted future (finished or
    structured-rejected), never orphan one."""
    from repro.serve.request_queue import RequestRejected

    _, _, wl = lm
    sched = Scheduler(groups=_two_groups())
    futs = [sched.submit(wl, {"batch": 1, "seed": s}) for s in range(3)]
    sched.shutdown()                     # immediately, mid-decode
    for f in futs:
        try:
            f.result(timeout=300)        # resolved, not hung
        except RequestRejected:
            pass                         # structured shutdown rejection
    assert sched.stats.in_flight == 0


# ---------------------------------------------------------------------------
# tracing: the engine's phases on the recorder's timeline
# ---------------------------------------------------------------------------
@pytest.fixture
def live_recorder():
    """The process-wide recorder, cleared and on for the test."""
    from repro.obs import get_recorder
    rec = get_recorder()
    was = rec.enabled
    rec.enabled = True
    rec.clear()
    yield rec
    rec.enabled = was
    rec.clear()


class _SleepStepper:
    """Engine protocol with sleeps for work: each row echoes its spec."""

    workload = "sleepy"
    n_slots = 4

    def __init__(self, n_steps=4, dt=0.004):
        self.n_steps, self.dt = n_steps, dt

    def init_slots(self):
        time.sleep(self.dt)
        return [None] * self.n_slots

    def prefill(self, spec):
        time.sleep(self.dt)
        return [(spec, spec, self.n_steps)]

    def insert(self, state, slot, row_state):
        time.sleep(self.dt / 2)
        state = list(state)
        state[slot] = row_state
        return state

    def step(self, state):
        time.sleep(self.dt)
        return state, list(state)

    def finish(self, state, slot, first_out, collected):
        return [first_out] + collected

    def assemble(self, row_values):
        return row_values[0]


class _Req:
    def __init__(self, trace_id):
        import concurrent.futures
        self.trace_id = trace_id
        self.future = concurrent.futures.Future()
        self.future.meta = {}


def _serve_sleepy(n_requests=6, hooks=None):
    """Requests through a ContinuousEngine over two lanes (prefill on
    ``accel``, decode on ``host``), arriving over several steps."""
    from repro.serve.continuous import ContinuousEngine

    eng = ContinuousEngine(
        _SleepStepper(),
        resolve=lambda req, value, t: req.future.set_result(value),
        reject=lambda req, exc: req.future.set_exception(exc),
        prefill_locks=[threading.Lock()], step_locks=[threading.Lock()],
        prefill_group="accel", decode_group="host", hooks=hooks)
    reqs = [_Req(f"tid-{i}") for i in range(n_requests)]
    try:
        for i, r in enumerate(reqs):
            assert eng.submit(r, i, time.monotonic())
            if i % 2:
                time.sleep(0.006)
        for i, r in enumerate(reqs):
            assert r.future.result(timeout=30)[0] == i
    finally:
        eng.shutdown()
    return eng, reqs


def test_engine_children_tile_their_phases(live_recorder):
    """``lane_wait`` + ``engine_insert`` + ``decode`` tile each
    ``engine_step`` and ``lane_wait`` + ``prefill_call`` each
    ``prefill``, to within 1 ms; every child names its parent (the
    step's index, the request's trace id); spans on one lane track never
    overlap."""
    eng, reqs = _serve_sleepy()
    evs = [e for e in live_recorder.events() if e["ph"] == "X"]
    by = collections.defaultdict(list)
    for e in evs:
        by[e["name"]].append(e)
    steps = by["engine_step"]
    assert len(steps) == eng.steps > 0
    assert sum(e["args"]["joins"] for e in steps) == len(reqs)

    def inside(child, parent):
        return (child["ts"] >= parent["ts"] - 1.0 and child["ts"]
                + child["dur"] <= parent["ts"] + parent["dur"] + 1.0)

    for st in steps:
        k = st["args"]["step"]
        kids = [e for n in ("lane_wait", "engine_insert", "decode")
                for e in by[n] if e["args"].get("step") == k]
        assert all(inside(c, st) for c in kids)
        names = sorted(c["name"] for c in kids)
        assert names.count("decode") == names.count("lane_wait") == 1
        assert names.count("engine_insert") == st["args"]["joins"]
        assert st["dur"] - sum(c["dur"] for c in kids) <= 1000.0
    for pre in by["prefill"]:
        tid = pre["args"]["trace_id"]
        kids = [e for n in ("lane_wait", "prefill_call") for e in by[n]
                if e["args"].get("trace_id") == tid]
        assert len(kids) == 2 and all(inside(c, pre) for c in kids)
        assert pre["dur"] - sum(c["dur"] for c in kids) <= 1000.0
    assert {e["args"]["step"] for e in by["engine_boundary"]} <= {
        st["args"]["step"] for st in steps}
    assert sum(e["args"]["evicted"] for e in by["engine_boundary"]) == len(
        reqs)
    for track in ("lane:accel", "lane:host"):
        on = sorted((e for e in evs if e["track"] == track),
                    key=lambda e: e["ts"])
        assert on
        for a, b in zip(on, on[1:]):
            assert b["ts"] >= a["ts"] + a["dur"] - 1e-3, (a, b)
    init = by["engine_init"]
    assert len(init) == 1 and init[0]["track"] == "lane:host"
    assert eng.snapshot()["init_s"] > 0


def test_engine_insert_carries_trace_id(live_recorder):
    """Each joined row's ``engine_insert`` names its request and slot;
    the old ``engine_join`` instant is gone."""
    _, reqs = _serve_sleepy(n_requests=3)
    evs = live_recorder.events()
    inserts = [e for e in evs if e["name"] == "engine_insert"]
    assert sorted(e["args"]["trace_id"] for e in inserts) == sorted(
        r.trace_id for r in reqs)
    assert all(e["track"] == "lane:host" and "slot" in e["args"]
               and "bytes" in e["args"] for e in inserts)
    assert not any(e["name"] == "engine_join" for e in evs)


def test_engine_lane_time_reaches_the_audit():
    """The engine's prefill, insert and decode calls accrue to the
    scheduler's placement audit, so ``resource_efficiency`` counts both
    of the engine's lanes."""
    sched = Scheduler(groups=_two_groups())
    sched.submit("lbm", {"d": 8, "n_steps": 40, "seed": 5,
                         "continuous": True}).result(timeout=300)
    (plan,) = sched.engine_placements.values()
    util = sched.audit.summary()["lane_utilization"]
    sched.shutdown()
    # whichever lanes the plan chose, each of them accrued its work
    for group in {plan.prefill_group, plan.decode_group}:
        assert util[group] > 0, (group, util)


# ---------------------------------------------------------------------------
# one lane for both phases: a prefill only for a free slot, and the step
# loop yields the lane to it at the boundary
# ---------------------------------------------------------------------------
def _burst(stepper, specs, shared, hooks=None):
    """Every spec submitted at once to an engine whose two phases share
    one lane and one lock (``shared``) or run on two lanes.  Returns the
    engine, the requests, and the excess of ready rows over free slots
    seen as each prefill starts and after each step."""
    from repro.serve.continuous import ContinuousEngine

    excess = []
    eng = None

    def watch(*_):
        with eng._cv:
            excess.append(len(eng._ready) - len(eng._free))

    prefill = stepper.prefill

    def watched_prefill(spec):
        watch()
        return prefill(spec)

    stepper.prefill = watched_prefill
    lock = threading.Lock()
    eng = ContinuousEngine(
        stepper,
        resolve=lambda req, value, t: req.future.set_result(value),
        reject=lambda req, exc: req.future.set_exception(exc),
        prefill_locks=[lock],
        step_locks=[lock] if shared else [threading.Lock()],
        prefill_group="accel", decode_group="accel" if shared else "host",
        hooks=dict(hooks or {}, on_step=watch))
    reqs = [_Req(f"burst-{i}") for i in range(len(specs))]
    try:
        for r, sp in zip(reqs, specs):
            assert eng.submit(r, sp, time.monotonic())
        for r in reqs:
            r.future.result(timeout=300)
    finally:
        eng.shutdown()
    return eng, reqs, excess


def test_shared_lane_burst_fills_free_slots_only(live_recorder):
    """A burst of 10 requests on 4 slots of one lane: every request
    finishes with its own rows; no more rows are ever ready than slots
    are free; the step loop yields to its prefills, and
    ``prefill_yields`` counts the gaps between steps that hold
    prefills; and between two steps no more prefills run than slots
    were free after the evictions at the boundary before.  The interpreter switches threads
    every microsecond meanwhile, so that a check and its update that
    are not under one lock would be caught apart."""
    import sys

    st = _SleepStepper()
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        eng, reqs, excess = _burst(st, list(range(10)), shared=True)
    finally:
        sys.setswitchinterval(switch)
    for i, r in enumerate(reqs):
        assert r.future.result() == [i] * (st.n_steps + 1)
    assert max(excess) <= 0
    snap = eng.snapshot()
    assert snap["prefill_yields"] > 0 and snap["shared_prefills"] == 10
    evs = [e for e in live_recorder.events() if e["ph"] == "X"]
    decodes = sorted((e for e in evs if e["name"] == "decode"),
                     key=lambda e: e["ts"])
    evicted = {e["args"]["step"]: e["args"]["evicted"] for e in evs
               if e["name"] == "engine_boundary"}
    yielded = [e["args"]["yielded_to_prefill"] for e in evs
               if e["name"] == "engine_boundary"]
    assert sum(yielded) > 0
    starts = [e["ts"] for e in evs if e["name"] == "prefill_call"]
    between = 0
    # every stop of the step loop for prefills lies before its first
    # step or between two steps, at a boundary or with no row live
    stops = int(any(t < decodes[0]["ts"] for t in starts))
    for a, b in zip(decodes, decodes[1:]):
        k = a["args"]["step"]
        free = st.n_slots - a["args"]["n_live"] + evicted[k]
        n = sum(1 for t in starts if a["ts"] + a["dur"] <= t < b["ts"])
        assert n <= free, (k, n, free)
        between += n
        stops += n > 0
    assert between > 0
    assert stops == snap["prefill_yields"]


def test_shared_lane_closed_loop_waves_count_their_prefills():
    """Closed-loop clients, one per slot, each sending its next request
    as the last one resolves: rows of equal length join together and
    all finish at one step, so each wave's prefills run with no row
    live.  The step loop still stops for them between its steps, and
    ``prefill_yields`` counts one stop per wave."""
    from repro.serve.continuous import ContinuousEngine

    st = _SleepStepper(n_steps=3)
    lock = threading.Lock()
    eng = ContinuousEngine(
        st, resolve=lambda req, value, t: req.future.set_result(value),
        reject=lambda req, exc: req.future.set_exception(exc),
        prefill_locks=[lock], step_locks=[lock],
        prefill_group="accel", decode_group="accel")
    waves = 5

    def client(c):
        for w in range(waves):
            r = _Req(f"c{c}-w{w}")
            assert eng.submit(r, c * 100 + w, time.monotonic())
            assert r.future.result(timeout=60) == [c * 100 + w] * 4

    clients = [threading.Thread(target=client, args=(c,))
               for c in range(st.n_slots)]
    try:
        for t in clients:
            t.start()
        for t in clients:
            t.join(120)
    finally:
        eng.shutdown()
    snap = eng.snapshot()
    assert snap["shared_prefills"] == waves * st.n_slots
    assert snap["joins"] == waves * st.n_slots
    assert waves <= snap["prefill_yields"] <= snap["shared_prefills"]


def test_shared_lane_lm_burst_matches_solo(lm):
    """Five LM requests on 2 slots of one lane: each request's tokens
    equal its solo ``generate``, and the ready rows never outnumber the
    free slots."""
    from repro.serve.continuous import LMStepper

    cfg, params, wl = lm
    stepper = LMStepper(cfg, params, prompt_len=PROMPT_LEN,
                        new_tokens=NEW_TOKENS, n_slots=2)
    specs = [adapters.make_request(wl, {"batch": 1, "seed": s})
             for s in range(20, 25)]
    eng, reqs, excess = _burst(stepper, specs, shared=True)
    for sp, r in zip(specs, reqs):
        np.testing.assert_array_equal(np.asarray(r.future.result()),
                                      _solo(cfg, params, sp.arrays[0]))
    assert max(excess) <= 0
    snap = eng.snapshot()
    assert snap["prefill_yields"] > 0 and snap["shared_prefills"] == 5
    assert snap["joins"] == 5 and snap["max_live"] == 2


def test_two_lanes_prefill_ahead_unchanged():
    """On two lanes prefills run ahead of free slots, as before: rows
    wait ready while every slot is taken, and the step loop never
    yields to a prefill."""
    st = _SleepStepper(n_steps=16)
    eng, reqs, excess = _burst(st, list(range(10)), shared=False)
    for i, r in enumerate(reqs):
        assert r.future.result() == [i] * (st.n_steps + 1)
    assert max(excess) > 0
    snap = eng.snapshot()
    assert snap["prefill_yields"] == 0 and snap["shared_prefills"] == 0


def test_scheduler_one_lane_serves_both_phases(lm):
    """A scheduler with one lane places both of the engine's phases on
    it, and a burst of more requests than slots through it matches
    solo decode."""
    cfg, params, wl = lm
    sched = Scheduler(groups=[DeviceGroup("accel", [], "accel")])
    futs = [sched.submit(wl, {"batch": 1, "seed": s}) for s in range(30, 36)]
    outs = [np.asarray(f.result(timeout=300)) for f in futs]
    plan = sched.engine_placements[wl]
    (eng,) = sched._engines.values()
    snap = eng.snapshot()
    sched.shutdown()
    for s, out in zip(range(30, 36), outs):
        spec = adapters.make_request(wl, {"batch": 1, "seed": s})
        np.testing.assert_array_equal(out, _solo(cfg, params,
                                                 spec.arrays[0]))
    assert (plan.prefill_group, plan.decode_group) == ("accel", "accel")
    assert snap["shared_prefills"] == 6 and snap["prefill_yields"] > 0


@pytest.mark.parametrize("n_lanes", [1, 2],
                         ids=["one_lane", "simulated_two_lanes"])
def test_table1_work_beside_a_shared_lane_engine(n_lanes):
    """Table-1 work on a lane whose lock an engine takes for both phases
    (one lane, or two lanes on one device, where every execution takes
    every lock) is served between the engine's steps: a hist request
    submitted while a long lbm request steps there finishes, exact,
    while the lbm request still runs, and the engine steps on."""
    import jax

    from repro.workloads import hist

    dev = jax.devices()[0]
    groups = [DeviceGroup("accel", [dev], "accel"),
              DeviceGroup("host", [dev], "host", slowdown=4.0)][:n_lanes]
    sched = Scheduler(groups=groups)
    try:
        # about 1.3 ms a step here: several seconds of stepping
        lbm = sched.submit("lbm", {"d": 8, "n_steps": 6000, "seed": 5,
                                   "continuous": True})
        deadline = time.monotonic() + 120
        while not sched._engines and time.monotonic() < deadline:
            time.sleep(0.005)
        (eng,) = sched._engines.values()
        while eng.steps < 3 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng._shared and eng.steps >= 3
        out = np.asarray(sched.submit("hist", {"n": 1 << 12, "n_bins": 64,
                                               "seed": 0})
                         .result(timeout=60))
        steps = eng.steps
        assert not lbm.done(), "hist waited for the whole lbm request"
        x = np.asarray(hist.make_inputs(1 << 12, 64, 0))
        np.testing.assert_array_equal(out, np.bincount(x, minlength=64))
        lbm.result(timeout=300)
        assert eng.steps > steps
    finally:
        sched.shutdown()


@pytest.mark.parametrize("wl,payload,solo", [
    ("listrank", {"n": 1 << 10, "seed": 4, "continuous": True},
     lambda: __import__("repro.workloads.listrank", fromlist=["x"])
     .pointer_jump_rank(adapters._listrank_inputs(1 << 10, 4))),
    ("dither", {"h": 32, "w": 32, "seed": 3, "continuous": True},
     lambda: __import__("repro.workloads.dither", fromlist=["x"])
     .fsd_dither(adapters._dither_inputs(32, 32, 3))),
])
def test_iterative_engine_on_one_lane_bit_identical(wl, payload, solo):
    """The ``IterStepper`` workloads give their solo result when both
    phases share the one lane."""
    sched = Scheduler(groups=[DeviceGroup("accel", [], "accel")])
    futs = [sched.submit(wl, payload) for _ in range(3)]
    outs = [np.asarray(f.result(timeout=300)) for f in futs]
    (plan,) = sched.engine_placements.values()
    sched.shutdown()
    assert not plan.disaggregated
    for out in outs:
        np.testing.assert_array_equal(out, np.asarray(solo()))


# ---------------------------------------------------------------------------
# joins write their row into the slot state in place
# ---------------------------------------------------------------------------
INSERT_SLOTS = 8


@pytest.fixture(scope="module", params=["xlstm-350m",
                                        "deepseek-v2-lite-16b"])
def insert_stepper(request):
    """A tiny LMStepper of 8 slots: xlstm-350m carries recurrent state
    ("groups" leaves, batch axis 1), deepseek-v2-lite also a dense
    prefix layer's KV cache ("prefix" leaves, batch axis 0)."""
    import jax

    from repro.serve.continuous import LMStepper

    cfg = registry.get(request.param).reduced()
    params = param.values(model_zoo.init(cfg, jax.random.key(0)))
    return LMStepper(cfg, params, prompt_len=PROMPT_LEN,
                     new_tokens=NEW_TOKENS, n_slots=INSERT_SLOTS)


def _prompts(cfg, n, seed):
    import jax
    return jax.random.randint(jax.random.key(seed), (n, PROMPT_LEN), 0,
                              cfg.vocab_size)


def _row(stepper, seed):
    """One prefilled row state, as the engine hands it to ``insert``."""
    import types
    spec = types.SimpleNamespace(arrays=(_prompts(stepper.cfg, 1, seed),))
    ((row_state, _, _),) = stepper.prefill(spec)
    return row_state


def _slot_state(stepper):
    """A slot state whose every slot holds a different row."""
    import jax
    import jax.numpy as jnp

    first, caches, _ = stepper._prefill(
        stepper._lane_params(), _prompts(stepper.cfg, INSERT_SLOTS, 1))
    pos = jnp.arange(INSERT_SLOTS, dtype=jnp.int32,
                     device=jax.devices()[0])
    return {"caches": caches, "tokens": first, "pos": pos}


def _np_tree(tree):
    import jax
    return jax.tree.map(lambda a: np.array(a), tree)


@pytest.mark.parametrize("slot", [0, 3, INSERT_SLOTS - 1])
def test_lm_insert_writes_one_slot(insert_stepper, slot):
    """``insert`` gives, bit for bit, the state of a plain NumPy write
    of the row at ``slot`` (batch axis 1 of "groups" leaves, 0 of
    "prefix" leaves, ``tokens`` and ``pos`` at the slot): the target
    slot holds the row, every other slot is unchanged."""
    import jax

    st = insert_stepper
    state = _slot_state(st)
    row_cache, first = _row(st, 100 + slot)
    want = _np_tree(state)
    row = _np_tree(row_cache)
    for full, r in zip(jax.tree.leaves(want["caches"]["groups"]),
                       jax.tree.leaves(row["groups"])):
        full[:, slot] = r
    for full, r in zip(jax.tree.leaves(want["caches"].get("prefix", [])),
                       jax.tree.leaves(row.get("prefix", []))):
        full[slot] = r
    want["tokens"][slot] = int(first)
    want["pos"][slot] = PROMPT_LEN

    got = _np_tree(st.insert(state, slot, (row_cache, first)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


def test_lm_init_slots_built_from_shapes(insert_stepper):
    """``init_slots`` runs no prefill: its state has the shapes and
    dtypes of a size-8 prefill's caches, holds zeros, and is committed
    to the lane's device, as the states that later steps return are."""
    import jax

    st = insert_stepper
    compiled = st._prefill._cache_size()
    state = st.init_slots()
    assert st._prefill._cache_size() == compiled
    want = _slot_state(st)
    assert jax.tree.structure(state) == jax.tree.structure(want)
    for got, w in zip(jax.tree.leaves(state), jax.tree.leaves(want)):
        assert got.shape == w.shape and got.dtype == w.dtype
        assert got.committed and not np.any(np.asarray(got))


def test_lm_insert_donates_and_compiles_once(insert_stepper):
    """``insert`` consumes the state it is given: every array of the old
    state is deleted after the call, its buffers donated to the new
    state; joins at every slot, from the fresh ``init_slots`` state on,
    share one compiled call."""
    import jax

    st = insert_stepper
    state = st.init_slots()
    for slot in range(INSERT_SLOTS):
        old = jax.tree.leaves(state)
        state = st.insert(state, slot, _row(st, 200 + slot))
        assert all(x.is_deleted() for x in old)
        assert not any(x.is_deleted() for x in jax.tree.leaves(state))
    state, _ = st.step(state)
    st.insert(state, 0, _row(st, 300))
    assert st._insert._cache_size() == 1


def test_lm_engine_join_mid_decode_matches_solo(lm, live_recorder):
    """Rows that join while another row is mid-decode write into the
    running slot state in place; every request's tokens still equal its
    solo ``generate``, and every ``engine_insert`` reads donated."""
    from repro.serve.continuous import ContinuousEngine, LMStepper

    cfg, params, wl = lm
    stepper = LMStepper(cfg, params, prompt_len=PROMPT_LEN,
                        new_tokens=NEW_TOKENS, n_slots=4)
    specs = [adapters.make_request(wl, {"batch": 1, "seed": s})
             for s in (11, 12, 13)]
    reqs = [_Req(f"mid-{i}") for i in range(3)]
    eng = None

    def on_step(n_live):
        # after the first row's first step, the other two arrive; the
        # boundary waits for their prefill so both join mid-decode
        if eng.steps != 1:
            return
        for r, sp in zip(reqs[1:], specs[1:]):
            assert eng.submit(r, sp, time.monotonic())
        with eng._cv:
            assert eng._cv.wait_for(lambda: len(eng._ready) == 2,
                                    timeout=120)

    eng = ContinuousEngine(
        stepper,
        resolve=lambda req, value, t: req.future.set_result(value),
        reject=lambda req, exc: req.future.set_exception(exc),
        prefill_locks=[threading.Lock()], step_locks=[threading.Lock()],
        prefill_group="accel", decode_group="host",
        hooks={"on_step": on_step})
    try:
        assert eng.submit(reqs[0], specs[0], time.monotonic())
        outs = [np.asarray(r.future.result(timeout=300)) for r in reqs]
    finally:
        eng.shutdown()
    for sp, out in zip(specs, outs):
        np.testing.assert_array_equal(out, _solo(cfg, params, sp.arrays[0]))
    snap = eng.snapshot()
    assert snap["joins"] == 3 and snap["max_live"] == 3
    inserts = [e for e in live_recorder.events()
               if e["name"] == "engine_insert"]
    assert len(inserts) == 3
    assert [e["args"]["step"] for e in inserts] == [0, 1, 1]
    assert all(e["args"]["donated"] is True for e in inserts)


# ---------------------------------------------------------------------------
# satellite: hist / conv merge hooks (array-level batching)
# ---------------------------------------------------------------------------
def test_hist_merge_demux_bit_identical():
    specs = [adapters.make_request("hist", {"n": 1 << 12, "n_bins": 64,
                                            "seed": s}) for s in range(3)]
    merged = specs[0].merge(specs)
    assert merged is not None
    assert merged.spec.total_units == 3          # real rows, not pads
    assert merged.spec.workload.endswith("@stack")
    batched = merged.spec.run_one()
    for i, s in enumerate(specs):
        np.testing.assert_array_equal(np.asarray(merged.demux(batched, i)),
                                      np.asarray(s.run_one()))


def test_hist_merge_refuses_unequal_lengths():
    a = adapters.make_request("hist", {"n": 1 << 12, "seed": 0})
    b = adapters.make_request("hist", {"n": (1 << 12) - 8, "seed": 1})
    assert a.merge([a, b]) is None


def test_conv_merge_demux_bit_identical():
    # REPRO_AUTOTUNE=0 in conftest -> tuned config is xla_conv -> the
    # merge hook engages (it declines for vmap-unsafe impls)
    specs = [adapters.make_request("conv", {"size": 64, "ksize": 5,
                                            "seed": s}) for s in range(3)]
    merged = specs[0].merge(specs)
    assert merged is not None
    assert merged.spec.total_units == 3
    batched = merged.spec.run_one()
    for i, s in enumerate(specs):
        np.testing.assert_array_equal(np.asarray(merged.demux(batched, i)),
                                      np.asarray(s.run_one()))


def test_scheduler_coalesces_hist_burst_exactly():
    """Same-bucket hist burst through the scheduler: merged execution,
    per-request results identical to solo."""
    sched = Scheduler(groups=_two_groups(), max_batch=8,
                      batch_window_s=0.05, split_overhead_s=100.0,
                      shared_span_factor=1.0)
    payloads = [{"n": 1 << 12, "n_bins": 64, "seed": s} for s in range(4)]
    futs = [sched.submit("hist", p) for p in payloads]
    vals = [np.asarray(f.result(timeout=120)) for f in futs]
    merged = sched.stats.merged_batches
    sched.shutdown()
    for p, v in zip(payloads, vals):
        solo = adapters.make_request("hist", p)
        np.testing.assert_array_equal(v, np.asarray(solo.run_one()))
    assert merged >= 1


# ---------------------------------------------------------------------------
# satellite: registry-level background precompile
# ---------------------------------------------------------------------------
def test_precompile_merged_runs_in_background():
    mix = [("hist", {"n": 1 << 12, "n_bins": 64, "seed": 0}),
           ("conv", {"size": 64, "ksize": 5, "seed": 0})]
    adapters.precompile_merged(mix, max_batch=4, background=True)
    assert adapters.wait_precompiled(timeout=300)
    # precompile threads are named precompile-* (teardown asserts no
    # serve-* thread survives; these must not trip that)
    for t in threading.enumerate():
        assert not (t.name.startswith("serve-")
                    and "precompile" in t.name)
