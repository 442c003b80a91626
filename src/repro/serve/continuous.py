"""Iteration-level scheduling engine: the decode step is the quantum.

PR-4/5 serve LM requests as monolithic unpreemptible units, so a
64-token decode occupies its lane end-to-end while same-shape arrivals
queue behind it — head-of-line blocking the paper's own lens diagnoses
as using the wrong scheduling granularity.  This engine makes one
*decode step* the scheduling quantum instead:

* live requests' rows live in fixed slots of a pow2-sized state pytree,
  and every step is ONE batched kernel call over all S slots
  (``serve_step.make_slot_step``'s vmap), so shapes stay jit-stable no
  matter how many rows are live — dead slots compute garbage that
  nothing reads, which is what keeps join/evict bit-identical to solo
  decode (vmap rows are independent);
* new same-bucket arrivals join the running batch at the next step
  boundary (their prefill runs beside the step loop, see below) instead
  of waiting for the batch to drain;
* finished rows are evicted at the boundary and their outputs demuxed
  exactly per request.

**Prefill and decode placed by cost** (paper §5.4.3 suitability
split): compute-bound prefill runs as a dedicated unit on the
projected-fastest lane, and the bandwidth-bound step loop runs on the
lane where a step costs least — the prefill lane itself when one decode
plus one prefill there beats every other lane's decode
(``placement.plan_disaggregation``).  The Scheduler prices both from
``CostTerms`` priors (``cost_model.lm_prefill_terms``/
``lm_decode_terms``), so a fresh process places with zero probe runs.
When the two phases take a common lane lock, the step loop runs the
prefills itself, at its boundaries and only for free slots
(``ContinuousEngine``).

The same mechanism generalizes past LMs: any sequential workload whose
unit of progress is "one iteration over carried state" (listrank
pointer-jump rounds, LBM BGK steps, dither rows) gets iteration-
boundary yield points for free — the step loop releases its lane locks
between steps, so other lane work interleaves and same-shape requests
stack into the vmapped state (``IterStepper``).

Steppers are duck-typed; the engine needs::

    workload      str, registry name this engine serves
    n_slots       int, fixed slot count (pow2 keeps shapes stable)
    prefill_cost  CostTerms for one request's join work
    decode_cost   CostTerms for one batched step
    init_slots()            -> state
    prefill(spec)           -> [(row_state, first_out, n_steps), ...]
    insert(state, slot, row_state) -> state   # consumes ``state``: its
    #                                            arrays may be donated to
    #                                            the result, so the caller
    #                                            never reads them again
    step(state)             -> (state, outs)   # outs indexable by slot
    #                                            or None (state carries)
    finish(state, slot, first_out, collected) -> row value
    assemble(row_values)    -> request value (solo-identical order)

and may add, for the recorder's spans::

    route_args(phase, slots) -> {arg: value}  # the routing of the last
    #   ``prefill`` (phase "prefill") or ``step`` ("decode"; ``slots``
    #   the live ones); read inside that call's span, on the thread and
    #   under the lane locks that made the call.  Without it: no args.
"""
from __future__ import annotations

import collections
import sys
import threading
import time
import weakref
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional

from repro.obs import get_recorder

_LIVE: "weakref.WeakSet[ContinuousEngine]" = weakref.WeakSet()


def shutdown_all(timeout: float = 10.0) -> None:
    """Stop every live engine (test teardown safety net)."""
    for eng in list(_LIVE):
        eng.shutdown(timeout=timeout)


class _Pending:
    """One submitted request in flight through the engine."""

    __slots__ = ("req", "spec", "t_start", "n_rows", "row_values")

    def __init__(self, req, spec, t_start: float):
        self.req = req
        self.spec = spec
        self.t_start = t_start
        self.n_rows = 0                      # set once prefill ran
        self.row_values: Dict[int, object] = {}


class _Row:
    """One live slot-resident row."""

    __slots__ = ("pending", "row_index", "first_out", "remaining",
                 "collected", "slot")

    def __init__(self, pending: _Pending, row_index: int, first_out,
                 remaining: int):
        self.pending = pending
        self.row_index = row_index
        self.first_out = first_out
        self.remaining = int(remaining)
        self.collected: List[object] = []
        self.slot = -1


class ContinuousEngine:
    """Step-quantum engine for one (stepper, lane-assignment) pair.

    Two threads: ``serve-cb-<wl>-prefill`` turns submissions into slot
    rows on the prefill lane; ``serve-cb-<wl>-step`` runs the batched
    step loop on the decode lane, joining ready rows and evicting
    finished ones at every step boundary; where the two phases' lane
    locks overlap, the step thread does both (below).  Lane locks are
    acquired per-phase and *released between steps* — that release IS
    the preemption point: any dedicated/shared work the Scheduler placed
    on the same lane interleaves at iteration boundaries instead of
    waiting for a whole request.

    When ``prefill_locks`` and ``step_locks`` share a lock (one lane
    for both phases, or a simulated platform whose lanes all take
    every lock), two threads would race for it at every boundary.
    There the step loop runs the prefills itself: at each boundary,
    after its evictions, it prefills queued requests one at a time
    while fewer rows are ready than slots are free, and their rows
    join at once.  The batch stalls for at most the prefills that
    refill its free slots, and a burst of arrivals cannot put more
    prefills between two steps.  ``prefill_yields`` counts the times
    the step loop stopped between two of its steps to run prefills,
    at a boundary with rows live or with none live (a batch whose rows
    all finished at one step, or an idle engine); ``shared_prefills``
    counts the prefills run so.  On separate locks prefills run ahead
    of free slots on their own thread, as they cost the batch nothing
    there.

    ``resolve(req, value, t_start)`` is the Scheduler's ``_resolve``
    (keeps the accounting invariant: every submitted request is
    completed/failed exactly once); ``hooks`` may carry ``on_step``,
    ``on_join``, ``on_evict``, ``on_cancel``, ``on_preempt`` counters
    (called outside locks) and ``on_busy(group, seconds)``, which is
    given the time of each prefill, insert and decode call on its lane.

    Spans (``repro.obs``): ``prefill`` and ``engine_step`` on the
    ``engine:<workload>`` track cover a phase from before its lane
    locks are taken until after they are released; their children
    tile them: ``lane_wait`` (the locks), then ``prefill_call`` or each
    joined row's ``engine_insert`` and the ``decode`` call, on the
    ``lane:<group>`` track of the lane that ran them.  Every child of
    step ``k`` carries ``step=k``; ``engine_boundary`` covers the host
    work between two steps and, where its ``yielded_to_prefill`` arg is
    true, the prefills the step loop ran there.  For a model with
    routed experts, ``prefill_call`` also carries ``expert_load_max``
    and ``decode`` ``experts_hit`` (``LMStepper.route_args``).  While
    the recorder is on, ``init_slots``, each insert and each step wait
    for their outputs before their spans close, so the spans time the
    work and not its dispatch.

    ``should_yield()`` (optional) is polled at every step boundary:
    while it returns True — the Scheduler dispatched latency-class
    deadline work at this engine's lane — the step loop pauses
    (bounded) instead of re-grabbing the lane lock, so the urgent work
    wins the lock handoff.  A batch whose own live rows include a
    latency-class request never yields: pausing it would starve
    exactly the class being prioritized.

    A row whose request future is already resolved — a hedge duplicate
    won the race, or the scheduler rejected it at shutdown — is dropped
    at the next step boundary without finishing: joins skip it, live
    slots free it.  That is the PR-6 preemption point doing cancellation
    duty; at most one extra step is ever spent on a loser.
    """

    def __init__(self, stepper, *,
                 resolve: Callable[[object, object, float], None],
                 reject: Callable[[object, BaseException], None],
                 prefill_locks: Optional[List[threading.Lock]] = None,
                 step_locks: Optional[List[threading.Lock]] = None,
                 prefill_group: str = "", decode_group: str = "",
                 prefill_ctx: Optional[Callable] = None,
                 step_ctx: Optional[Callable] = None,
                 should_yield: Optional[Callable[[], bool]] = None,
                 yield_max_s: float = 0.1,
                 hooks: Optional[Dict[str, Callable]] = None,
                 clock: Optional[Callable[[], float]] = None):
        import time as _time
        from contextlib import nullcontext
        self.stepper = stepper
        self.workload = stepper.workload
        self.n_slots = int(stepper.n_slots)
        self.prefill_group = prefill_group
        self.decode_group = decode_group
        self.prefill_locks = list(prefill_locks or [])
        self.step_locks = list(step_locks or [])
        self._resolve = resolve
        self._reject = reject
        self._prefill_ctx = prefill_ctx or (lambda: nullcontext())
        self._step_ctx = step_ctx or (lambda: nullcontext())
        self._should_yield = should_yield
        self._yield_max_s = max(float(yield_max_s), 0.0)
        self._hooks = dict(hooks or {})
        self._clock = clock or _time.monotonic
        self._rec = get_recorder()
        self._track = f"engine:{_safe(self.workload)}"
        self._cv = threading.Condition()
        self._inbox: collections.deque = collections.deque()
        self._ready: collections.deque = collections.deque()
        self._free: List[int] = list(range(self.n_slots))[::-1]
        self._live: Dict[int, _Row] = {}
        self._stop = False
        # the phases take a common lock: the step loop prefills too
        self._shared = bool(set(self.prefill_locks) & set(self.step_locks))
        self._prefilling = False        # a request taken, rows not ready
        self.steps = 0
        self.joins = 0
        self.evictions = 0
        self.cancellations = 0
        self.preemptions = 0
        self.prefill_yields = 0
        self.shared_prefills = 0
        self.max_live = 0
        self.init_s: Optional[float] = None
        traced = self._rec.enabled
        for lk in self.step_locks:
            lk.acquire()
        try:
            t0 = time.monotonic()
            with self._rec.span("engine_init", "engine",
                                f"lane:{decode_group}", group=decode_group):
                with self._step_ctx():
                    self._state = stepper.init_slots()
                    if traced:
                        _sync(self._state)
            if traced:
                # untraced, this would time the dispatch alone
                self.init_s = time.monotonic() - t0
        finally:
            for lk in reversed(self.step_locks):
                lk.release()
        loops = {"step": self._step_loop}
        if not self._shared:
            loops["prefill"] = self._prefill_loop
        self._threads = [
            threading.Thread(target=fn, daemon=True,
                             name=f"serve-cb-{_safe(self.workload)}-{name}")
            for name, fn in sorted(loops.items())]
        for t in self._threads:
            t.start()
        _LIVE.add(self)

    # ---- submission ------------------------------------------------------
    def submit(self, req, spec, t_start: float) -> bool:
        """Hand one request to the engine (False after shutdown)."""
        with self._cv:
            if self._stop:
                return False
            self._inbox.append(_Pending(req, spec, t_start))
            self._cv.notify_all()
        return True

    # ---- prefill lane ----------------------------------------------------
    def _prefill_loop(self) -> None:
        """Separate locks: each request is prefilled as it arrives."""
        while True:
            with self._cv:
                while not self._inbox:
                    if self._stop:
                        return
                    self._cv.wait()
                pending = self._take()
            self._prefill_rows(pending)

    def _prefill_for_free_slots(self) -> int:
        """A common lock: the step loop's own prefills, one request at
        a time while fewer rows are ready than slots are free.  Returns
        how many ran."""
        n = 0
        while True:
            with self._cv:
                if not self._inbox or len(self._ready) >= len(self._free):
                    return n
                pending = self._take()
                self.shared_prefills += 1
            self._prefill_rows(pending)
            n += 1

    def _take(self) -> _Pending:
        """The next request for prefill (under ``_cv``)."""
        self._prefilling = True
        return self._inbox.popleft()

    def _prefill_rows(self, pending: _Pending) -> None:
        """Prefill a taken request and queue its rows as ready."""
        try:
            rows = self._prefill(pending)
        except BaseException as exc:              # noqa: BLE001
            rows = []
            self._reject(pending.req, exc)
        with self._cv:
            for i, (row_state, first_out, n_steps) in enumerate(rows):
                row = _Row(pending, i, first_out, n_steps)
                self._ready.append((row, row_state))
            self._prefilling = False
            self._cv.notify_all()

    def _prefill(self, pending: _Pending) -> list:
        """One request's prefill on the prefill lane; its rows."""
        rec = self._rec
        trace_id = getattr(pending.req, "trace_id", None)
        with rec.span("prefill", "engine", self._track, trace_id,
                      workload=self.workload, group=self.prefill_group):
            with rec.span("lane_wait", "engine", self._track, trace_id,
                          phase="prefill", group=self.prefill_group):
                for lk in self.prefill_locks:
                    lk.acquire()
            try:
                with self._prefill_ctx(), self._lane_span(
                        "prefill_call", self.prefill_group,
                        trace_id) as args:
                    rows = self.stepper.prefill(pending.spec)
                    args["rows"] = len(rows)
                    if rec.enabled:
                        args.update(self._route_args("prefill"))
            finally:
                for lk in reversed(self.prefill_locks):
                    lk.release()
        pending.req.future.meta.setdefault("t_first_token", self._clock())
        pending.req.future.meta.setdefault("engine", {
            "prefill_group": self.prefill_group,
            "decode_group": self.decode_group})
        pending.n_rows = len(rows)
        return rows

    @contextmanager
    def _lane_span(self, name: str, group: str,
                   trace_id: Optional[str] = None, **attrs):
        """A span of work on ``group``'s lane, on its ``lane:<group>``
        track; its time also goes to the ``on_busy`` hook."""
        t0 = time.monotonic()
        with self._rec.span(name, "engine", f"lane:{group}", trace_id,
                            **attrs) as args:
            yield args
        busy = self._hooks.get("on_busy")
        if busy is not None:
            busy(group, time.monotonic() - t0)

    # ---- decode lane -----------------------------------------------------
    def _step_loop(self) -> None:
        live_now: Dict[int, _Row] = {}
        joined: List[tuple] = []
        while True:
            if not live_now:
                # nothing live: wait for arrivals, outside any span; a
                # stopped engine still serves what it was given
                with self._cv:
                    while not (self._ready or self._live
                               or (self._shared and self._inbox)):
                        if (self._stop and not self._inbox
                                and not self._prefilling):
                            return
                        self._cv.wait()
                if self._shared and self._prefill_for_free_slots():
                    self.prefill_yields += 1
                live_now, joined = self._join()
                if not live_now:
                    continue
            self._maybe_yield(live_now)
            k = self.steps
            outs = self._run_step(k, live_now, joined)
            with self._rec.span("engine_boundary", "engine", self._track,
                                step=k) as args:
                if joined and "on_join" in self._hooks:
                    self._hooks["on_join"](len(joined))
                if "on_step" in self._hooks:
                    self._hooks["on_step"](len(live_now))
                args["evicted"] = self._retire(live_now, outs)
                yielded = self._shared and self._prefill_for_free_slots() > 0
                if yielded:
                    self.prefill_yields += 1
                args["yielded_to_prefill"] = yielded
                live_now, joined = self._join()
                args["joined_next"] = len(joined)

    def _join(self):
        """The step boundary's join: fill free slots from ready rows.
        Returns the rows live for the next step and those that joined."""
        joined, cancelled = [], []
        with self._cv:
            while self._ready and self._free:
                row, row_state = self._ready.popleft()
                if row.pending.req.future.done():
                    # already resolved elsewhere (hedge winner,
                    # shutdown rejection): never takes a slot
                    self.cancellations += 1
                    cancelled.append(row)
                    continue
                row.slot = self._free.pop()
                self._live[row.slot] = row
                joined.append((row, row_state))
            live_now = dict(self._live)
            self.max_live = max(self.max_live, len(live_now))
            if cancelled:
                self._cv.notify_all()
        if cancelled:
            if self._rec.enabled:
                for row in cancelled:
                    self._rec.instant(
                        "engine_cancel", "engine", self._track,
                        getattr(row.pending.req, "trace_id", None),
                        at="join")          # preempted before a slot
            if "on_cancel" in self._hooks:
                self._hooks["on_cancel"](len(cancelled))
        return live_now, joined

    def _run_step(self, k: int, live_now: Dict[int, _Row],
                  joined: List[tuple]):
        """Step ``k``: the joined rows' inserts and one batched decode
        call on the decode lane; returns the step's outputs."""
        rec = self._rec
        group = self.decode_group
        # the span covers lock wait too: lane contention is exactly
        # what a step timeline should show
        with rec.span("engine_step", "engine", self._track, step=k,
                      n_live=len(live_now), joins=len(joined), group=group):
            with rec.span("lane_wait", "engine", self._track, step=k,
                          phase="step", group=group):
                for lk in self.step_locks:
                    lk.acquire()
            try:
                with self._step_ctx():
                    for row, row_state in joined:
                        with self._lane_span(
                                "engine_insert", group,
                                getattr(row.pending.req, "trace_id", None),
                                slot=row.slot, step=k) as args:
                            old = _leaves(self._state) if rec.enabled else ()
                            self._state = self.stepper.insert(
                                self._state, row.slot, row_state)
                            if rec.enabled:
                                _sync(self._state)
                                args["bytes"] = _nbytes(row_state)
                                args["donated"] = _all_deleted(old)
                        self.joins += 1
                    with self._lane_span("decode", group, step=k,
                                         n_live=len(live_now)) as args:
                        self._state, outs = self.stepper.step(self._state)
                        if rec.enabled:
                            _sync(self._state)
                            args.update(self._route_args("decode",
                                                         sorted(live_now)))
                self.steps += 1
            finally:
                for lk in reversed(self.step_locks):
                    lk.release()
        return outs

    def _route_args(self, phase: str, slots=()) -> Dict[str, float]:
        """The stepper's optional ``route_args`` for a ``phase`` span;
        {} for a stepper without it."""
        fn = getattr(self.stepper, "route_args", None)
        return fn(phase, slots) if fn is not None else {}

    def _retire(self, live_now: Dict[int, _Row], outs) -> int:
        """Collect a step's outputs; evict and finish the rows that are
        done and free the slots of cancelled ones.  Returns the number
        evicted."""
        evicted, cancelled = [], []
        for slot, row in live_now.items():
            if row.pending.req.future.done():
                # hedge loser / cancelled mid-decode: free the slot
                # at this boundary, skip finish (resolve-exactly-
                # once makes the duplicate's value the only value)
                cancelled.append(row)
                continue
            if outs is not None:
                row.collected.append(outs[slot])
            row.remaining -= 1
            if row.remaining <= 0:
                evicted.append(row)
        if not evicted and not cancelled:
            return 0
        with self._cv:
            for row in evicted:
                del self._live[row.slot]
                self._free.append(row.slot)
                self.evictions += 1
            for row in cancelled:
                del self._live[row.slot]
                self._free.append(row.slot)
                self.cancellations += 1
            self._cv.notify_all()
        if self._rec.enabled:
            for row in evicted:
                self._rec.instant(
                    "engine_evict", "engine", self._track,
                    getattr(row.pending.req, "trace_id", None),
                    slot=row.slot)
            for row in cancelled:
                self._rec.instant(
                    "engine_cancel", "engine", self._track,
                    getattr(row.pending.req, "trace_id", None),
                    at="mid_decode")        # preempted from a slot
        if evicted and "on_evict" in self._hooks:
            self._hooks["on_evict"](len(evicted))
        if cancelled and "on_cancel" in self._hooks:
            self._hooks["on_cancel"](len(cancelled))
        for row in evicted:
            self._finish_row(row)
        return len(evicted)

    def _maybe_yield(self, live_now: Dict[int, _Row]) -> None:
        """Iteration-boundary preemption: pause (bounded) while the
        Scheduler has latency-class deadline work waiting for this
        engine's lane — the waiting lane worker wins the lock handoff
        instead of racing the step loop for it.  Skipped when a live
        row is itself latency-class."""
        check = self._should_yield
        if check is None or not check():
            return
        if any(getattr(row.pending.req, "slo_class", "") == "latency"
               for row in live_now.values()):
            return
        self.preemptions += 1
        if self._rec.enabled:
            self._rec.instant("engine_preempt", "engine", self._track,
                              n_live=len(live_now))
        if "on_preempt" in self._hooks:
            self._hooks["on_preempt"](1)
        deadline = time.monotonic() + self._yield_max_s
        while check() and time.monotonic() < deadline:
            with self._cv:
                if self._stop:
                    return
            # urgent work clears once its lane worker HOLDS the locks
            # (scheduler._lane_run) — a short sleep is the handoff; the
            # deadline bounds livelock if the urgent lane died instead
            time.sleep(0.001)

    def _finish_row(self, row: _Row) -> None:
        pending = row.pending
        try:
            value = self.stepper.finish(self._state, row.slot,
                                        row.first_out, row.collected)
            pending.row_values[row.row_index] = value
            if len(pending.row_values) < pending.n_rows:
                return
            out = self.stepper.assemble(
                [pending.row_values[i] for i in range(pending.n_rows)])
            pending.req.future.meta.setdefault("t_last_token", self._clock())
            self._resolve(pending.req, out, pending.t_start)
        except BaseException as exc:              # noqa: BLE001
            self._reject(pending.req, exc)

    # ---- lifecycle -------------------------------------------------------
    @property
    def live_rows(self) -> int:
        with self._cv:
            return len(self._live)

    def wait_idle(self, timeout: float = 30.0) -> bool:
        """Block until no work is queued or live (tests/benchmarks)."""
        deadline = self._clock() + timeout
        with self._cv:
            while (self._inbox or self._prefilling or self._ready
                   or self._live):
                remaining = deadline - self._clock()
                if remaining <= 0:
                    return False
                self._cv.wait(remaining)
        return True

    def shutdown(self, timeout: float = 10.0) -> None:
        """Finish in-flight rows, then stop both threads."""
        with self._cv:
            if self._stop:
                self._cv.notify_all()
            self._stop = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout)

    def snapshot(self) -> Dict[str, object]:
        """Counters; ``init_s`` (the ``engine_init`` span's seconds) only
        when the recorder was on at construction."""
        with self._cv:
            snap = {"workload": self.workload, "steps": self.steps,
                    "joins": self.joins, "evictions": self.evictions,
                    "cancellations": self.cancellations,
                    "preemptions": self.preemptions,
                    "prefill_yields": self.prefill_yields,
                    "shared_prefills": self.shared_prefills,
                    "max_live": self.max_live, "live": len(self._live),
                    "prefill_group": self.prefill_group,
                    "decode_group": self.decode_group}
        if self.init_s is not None:
            snap["init_s"] = self.init_s
        return snap


def _safe(name: str) -> str:
    return name.replace("/", "-").replace("@", "-")


def _sync(tree) -> None:
    """Wait until the arrays of ``tree`` are computed (dispatch is
    asynchronous, on the host's XLA too)."""
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.block_until_ready(tree)


def _leaves(tree) -> list:
    jax = sys.modules.get("jax")
    return [] if jax is None else jax.tree.leaves(tree)


def _nbytes(tree) -> int:
    return int(sum(getattr(x, "nbytes", 0) for x in _leaves(tree)))


def _all_deleted(leaves) -> bool:
    """Whether every array of ``leaves`` gave up its buffer, as the
    arguments a call was donated do."""
    arrays = [x for x in leaves if hasattr(x, "is_deleted")]
    return bool(arrays) and all(x.is_deleted() for x in arrays)


# ---------------------------------------------------------------------------
# Steppers
# ---------------------------------------------------------------------------
class LMStepper:
    """Slot-batched LM decode over ``serve_step.make_slot_step``.

    One row == one prompt row of a request; the slot state is exactly
    the cache pytree a size-S prefill produces (layer-group axis 0 /
    batch axis 1 on ``"groups"`` leaves, batch axis 0 on ``"prefix"``),
    so insert/step are pure index updates and every slot decodes the
    same math it would decode alone.  ``finish`` rebuilds the solo
    ``generate`` output: first prefill token + one token per step.
    """

    def __init__(self, cfg, params, *, prompt_len: int, new_tokens: int,
                 cache_len: Optional[int] = None, n_slots: int = 4,
                 tp: int = 1, workload: str = ""):
        import jax
        import jax.numpy as jnp

        from repro.core import cost_model
        from repro.models import model_zoo
        from repro.serve.serve_step import make_slot_step

        self._jax, self._jnp = jax, jnp
        self.cfg = cfg
        self.params = params
        self._params_on: Dict[object, object] = {}
        self._params_lock = threading.Lock()
        self.prompt_len = int(prompt_len)
        self.new_tokens = int(new_tokens)
        self.cache_len = int(cache_len or (prompt_len + new_tokens + 1))
        self.n_slots = int(n_slots)
        self.workload = workload or f"serve-lm-cb/{cfg.name}"
        n_params = float(sum(
            x.size for x in jax.tree.leaves(params)
            if hasattr(x, "size")))
        self.n_params = n_params
        self.prefill_cost = cost_model.lm_prefill_terms(
            n_params, self.prompt_len)
        self.decode_cost = cost_model.lm_decode_terms(n_params)
        self._slot_step = make_slot_step(cfg, tp=tp)
        L, tp_ = self.cache_len, tp

        @jax.jit
        @jax.named_scope("prefill")
        def _prefill(params, prompt):
            logits, caches, route = model_zoo.prefill(
                cfg, params, {"tokens": prompt}, cache_len=L, tp=tp_,
                with_route=True)
            first = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
            return first.astype(jnp.int32), caches, route

        self._prefill = _prefill
        # the expert counts of the last prefill and of the last step
        # (None for a model without experts), for ``route_args``: each
        # phase is called and read by one engine thread
        self._route = {"prefill": None, "decode": None}

        def _insert(state, slot, row_cache, first):
            return {"caches": self._cache_update(state["caches"], row_cache,
                                                 slot),
                    "tokens": state["tokens"].at[slot].set(
                        first.astype(jnp.int32)),
                    "pos": state["pos"].at[slot].set(self.prompt_len)}

        # the slot state is donated: a join writes one row in place
        # instead of copying every slot; ``slot`` is traced, so one
        # compile serves every slot
        self._insert = jax.jit(_insert, donate_argnums=0)

    def _lane_params(self):
        """``params`` on the device this call runs on, copied there once
        per device: prefill and decode may run on different lanes (the
        TPU and the host CPU), and params left on one device would
        either pull the other lane's calls onto it or cross the host
        link at every step."""
        from repro.core.device import current_device
        dev = current_device()
        with self._params_lock:
            p = self._params_on.get(dev)
            if p is None:
                p = self._params_on[dev] = self._jax.device_put(
                    self.params, dev)
        return p

    # -- protocol ----------------------------------------------------------
    def init_slots(self):
        """The empty slot state, built from the shapes of a size-S prefill
        and filled with zeros: every slot is overwritten by its first
        join, so nothing is computed."""
        from repro.core.device import current_device
        jax, jnp = self._jax, self._jnp
        prompt = jax.ShapeDtypeStruct((self.n_slots, self.prompt_len),
                                      jnp.int32)
        _, caches, _ = jax.eval_shape(self._prefill, self.params, prompt)
        # committed to the lane's device, as every later state is: a
        # state of the other kind would compile ``insert`` again at the
        # first join after it
        dev = current_device()

        def zeros(shape, dtype):
            return jnp.zeros(shape, dtype, device=dev)

        return {"caches": jax.tree.map(lambda a: zeros(a.shape, a.dtype),
                                       caches),
                "tokens": zeros((self.n_slots,), jnp.int32),
                "pos": zeros((self.n_slots,), jnp.int32)}

    def prefill(self, spec):
        jax = self._jax
        prompt = self._jnp.asarray(spec.arrays[0])
        first, caches, self._route["prefill"] = self._prefill(
            self._lane_params(), prompt)
        first_host = [int(t) for t in jax.device_get(first)]
        rows = []
        for b in range(prompt.shape[0]):
            row_cache = self._slice_cache(caches, b)
            rows.append(((row_cache, first[b]), first_host[b],
                         self.new_tokens))
        return rows

    def insert(self, state, slot, row_state):
        import numpy as np

        from repro.core.device import current_device
        # the row was prefilled on the prefill lane's device
        row_cache, first = self._jax.device_put(row_state, current_device())
        return self._insert(state, np.int32(slot), row_cache, first)

    def step(self, state):
        toks, caches, self._route["decode"] = self._slot_step(
            self._lane_params(), state["tokens"], state["caches"],
            state["pos"])
        new = {"caches": caches, "tokens": toks,
               "pos": state["pos"] + 1}
        import numpy as np
        # a copy: a host view of ``toks`` would keep the next insert
        # from taking the tokens' buffer over
        return new, np.array(self._jax.device_get(toks))

    def finish(self, state, slot, first_out, collected):
        import numpy as np
        return np.asarray([first_out] + [int(t) for t in collected],
                          dtype=np.int32)[None, :]

    def route_args(self, phase: str, slots=()) -> Dict[str, float]:
        """Span args of the routing of the last ``phase`` call; {} for a
        model without experts.  ``"prefill"``: ``expert_load_max``, the
        busiest routed expert's assignments over the mean per expert,
        the largest over the expert layers.  ``"decode"``:
        ``experts_hit``, the distinct routed experts that the live
        ``slots`` chose, the mean over the expert layers."""
        import numpy as np
        route = self._route[phase]
        if route is None:
            return {}
        leaves = [np.asarray(a) for a in self._jax.tree.leaves(route)]
        if phase == "prefill":
            # (n_layers, rows, E): the call's load per expert and layer
            load = np.concatenate(leaves, axis=0).sum(axis=1)
            mean = load.sum(axis=1) / load.shape[1]
            return {"expert_load_max":
                    float((load.max(axis=1) / mean).max())}
        # (S, n_layers, E): the experts any live slot chose, per layer
        hit = np.concatenate(leaves, axis=1)[list(slots)] > 0
        return {"experts_hit": float(hit.any(axis=0).sum(axis=1).mean())}

    def assemble(self, row_values):
        import numpy as np
        return np.concatenate(row_values, axis=0)

    def warm(self, batch_sizes=(1, 2)) -> None:
        """Compile the fixed slot shapes (size-S prefill, per-request
        prefill batches, insert, slot step) ahead of traffic."""
        jnp = self._jnp
        state = self.init_slots()
        for b in batch_sizes:
            first, caches, _ = self._prefill(
                self._lane_params(),
                jnp.zeros((int(b), self.prompt_len), jnp.int32))
            row = self._slice_cache(caches, 0)
            state = self.insert(state, 0, (row, first[0]))
        state, _ = self.step(state)
        self._jax.block_until_ready(state)

    # -- cache pytree plumbing --------------------------------------------
    def _slice_cache(self, caches, b):
        jax = self._jax
        out = {"groups": jax.tree.map(lambda a: a[:, b],
                                      caches["groups"])}
        if "prefix" in caches:
            out["prefix"] = [jax.tree.map(lambda a: a[b], c)
                             for c in caches["prefix"]]
        return out

    def _cache_update(self, caches, row, slot):
        jax = self._jax
        lax = self._jax.lax
        out = {"groups": jax.tree.map(
            lambda full, r: lax.dynamic_update_index_in_dim(
                full, r, slot, 1),
            caches["groups"], row["groups"])}
        if "prefix" in caches:
            out["prefix"] = [
                jax.tree.map(lambda full, r: lax.dynamic_update_index_in_dim(
                    full, r, slot, 0), c, rc)
                for c, rc in zip(caches["prefix"], row["prefix"])]
        return out


class IterStepper:
    """Slot-batched iteration for sequential single-unit workloads.

    Wraps one jitted per-row iteration (a pointer-jump round, a BGK
    step, a dither row) as ``vmap`` over a fixed slot axis: requests
    whose whole-job adapters were unpreemptible single units become
    sequences of step-boundary yield points, and same-shape requests
    stack into the one batched call.  The carried state IS the output:
    per-step ``outs`` is None and ``finish`` slices the final state at
    the row's slot.

    ``make_rows(spec) -> [(row_state_pytree, n_steps), ...]`` builds
    the initial carried state per request row; ``finalize(row_state)``
    turns a final row state into the request's value (must match the
    solo adapter bit-for-bit — all three built-ins do, measured).
    """

    def __init__(self, *, workload: str, n_slots: int, template_row,
                 iter_fn, make_rows, finalize,
                 prefill_cost=None, decode_cost=None,
                 assemble=None):
        import jax

        from repro.core.cost_model import CostTerms

        self._jax = jax
        self.workload = workload
        self.n_slots = int(n_slots)
        self._template = template_row
        self._make_rows = make_rows
        self._finalize = finalize
        self._assemble = assemble
        self.prefill_cost = prefill_cost or CostTerms()
        self.decode_cost = decode_cost or CostTerms()
        self._step = jax.jit(jax.vmap(iter_fn))
        # donated, as ``LMStepper``'s: a join writes its row in place
        self._insert = jax.jit(
            lambda state, slot, row: jax.tree.map(
                lambda full, r: jax.lax.dynamic_update_index_in_dim(
                    full, r, slot, 0), state, row),
            donate_argnums=0)

    def init_slots(self):
        jax, jnp = self._jax, self._jax.numpy
        return jax.tree.map(
            lambda a: jnp.zeros((self.n_slots,) + tuple(a.shape), a.dtype),
            self._template)

    def prefill(self, spec):
        return [(row_state, None, n_steps)
                for row_state, n_steps in self._make_rows(spec)]

    def insert(self, state, slot, row_state):
        import numpy as np
        return self._insert(state, np.int32(slot), row_state)

    def step(self, state):
        return self._step(state), None

    def finish(self, state, slot, first_out, collected):
        jax = self._jax
        row = jax.tree.map(lambda a: a[slot], state)
        return self._finalize(jax.device_get(row))

    def assemble(self, row_values):
        if self._assemble is not None:
            return self._assemble(row_values)
        return row_values[0] if len(row_values) == 1 else row_values

    def warm(self) -> None:
        """Compile insert + the vmapped step ahead of traffic."""
        state = self.insert(self.init_slots(), 0, self._template)
        state = self.step(state)[0]
        self._jax.block_until_ready(state)
