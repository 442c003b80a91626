"""Hybrid executor: chunk-pipelined work sharing over JAX device groups.

Execution model
---------------
A work-shared call is planned (throughput-proportional integer shares,
paper §5.4.3), cut into uniform chunks, and handed to the
``AsyncChunkExecutor``:

* **Real overlap** — when the device groups own disjoint devices (two
  JAX platforms, or one platform with ≥2 devices, e.g. under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=2``) each group
  gets a worker thread pinned to its primary device and the groups
  compute concurrently; the reported makespan is the real wall-clock
  span of the joined threads.
* **Simulated overlap** — on a single device the groups share the
  hardware, so concurrency is simulated with per-group virtual clocks:
  chunks interleave in virtual-time order, the slower group's chunk
  times are scaled by its ``slowdown`` factor, and the makespan is the
  paper's overlap model max(t_fast, t_slow) + comm.  Every result
  records which mode produced it (``HybridResult.mode`` and
  ``WorkSharedOutput.simulated``).

Within one call a group that drains its chunk queue *steals* from the
tail of the slowest group's queue, so a mis-calibrated split (or a
mid-run straggler) self-corrects without waiting for the next call's
``refine_split``.  Calibration is remembered process-wide per
(workload, group, slowdown) in the ``CalibrationCache``: the first call
for a workload probes once per group and warms compilation; every
steady-state call after that executes each chunk exactly once.

Both the measured makespan and the analytic model makespan
(``WorkPlan.hybrid_time``) are reported side by side so the overlap
benchmarks can show how far reality is from the model.
"""
from __future__ import annotations

import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import jax

from repro.core import work_sharing
from repro.core.async_executor import (AsyncChunkExecutor, ExecutionTrace,
                                       make_chunks, make_share_chunks)
from repro.core.calibration import (ThroughputTracker,
                                    get_calibration_cache, measure)
from repro.core.metrics import HybridResult
from repro.obs import get_recorder


@dataclass
class DeviceGroup:
    name: str
    devices: List
    device_class: str                # "accel" | "host"
    slowdown: float = 1.0            # simulated relative slowdown (>=1)

    @property
    def device(self):
        """The lane's device (its primary): where its calls run."""
        return self.devices[0] if self.devices else None


def detect_platform(simulated_ratio: float = 4.0,
                    force_simulated: bool = False
                    ) -> Tuple[List[DeviceGroup], bool]:
    """Build device groups.

    A TPU -> the paper's hybrid for real: ``accel`` is the TPU chip and
    ``host`` is the host CPU (``jax.devices("cpu")``).  A process that
    sees more than one TPU raises: the hybrid drives one chip and its
    host per process, so a multi-chip host runs one worker process per
    chip (``ProcWorker(env=serve.transport.chip_env(i))`` behind
    ``serve.router.Router``) instead of quietly placing every lane on
    chip 0.  A CPU-only process: >=2 devices (forced host devices) ->
    split them into two groups (real concurrency, homogeneous
    hardware); a single device -> simulate a hybrid pair with the given
    throughput ratio (Hybrid-Low's GPU:CPU sustained ratio 77.7/20 ~=
    3.9 is the default).

    ``force_simulated`` skips detection and always builds the simulated
    pair on the primary device — benchmarks that sweep throughput
    ratios (table2's Hybrid-High vs -Low) need the ratio honored even
    on a multi-device host, where detection would otherwise return a
    real pair and silently drop the ratio."""
    devs = jax.devices()
    if force_simulated:
        only = devs[:1]
        return ([DeviceGroup("accel", only, "accel", slowdown=1.0),
                 DeviceGroup("host", only, "host",
                             slowdown=simulated_ratio)], True)
    if devs[0].platform == "tpu":
        if len(devs) > 1:
            raise RuntimeError(
                f"this process sees {len(devs)} TPU chips; the hybrid "
                "scheduler drives one chip and its host CPU per process. "
                "Run one worker process per chip (serve.transport."
                "ProcWorker(env=chip_env(i)) behind serve.router.Router).")
        return ([DeviceGroup("accel", devs, "accel"),
                 DeviceGroup("host", jax.devices("cpu")[:1], "host")],
                False)
    if len(devs) >= 2:
        half = max(len(devs) // 2, 1)
        return ([DeviceGroup("accel", devs[:half], "accel"),
                 DeviceGroup("host", devs[half:], "host")], False)
    only = devs[: max(1, len(devs))]
    return ([DeviceGroup("accel", only, "accel", slowdown=1.0),
             DeviceGroup("host", only, "host", slowdown=simulated_ratio)],
            True)


def _assigned_units(units: Sequence[int], names: Sequence[str],
                    chunk_units: int) -> List[int]:
    """Units per group after rounding shares to whole chunks — what the
    executor will actually run, which the analytic model must predict."""
    active = [(n, k) for n, k in zip(names, units) if k > 0]
    if not active:
        return [0] * len(names)
    queues = make_chunks([k for _, k in active], [n for n, _ in active],
                         chunk_units)
    per = {n: sum(c.units for c in q) for n, q in queues.items()}
    return [per.get(n, 0) for n in names]


@dataclass
class WorkSharedOutput:
    value: object
    result: HybridResult
    plan: work_sharing.WorkPlan
    simulated: bool
    trace: Optional[ExecutionTrace] = None


class HybridExecutor:
    """Work-sharing executor over two (or more) device groups.

    ``run_share(group_name, start_unit, n_units)`` must execute one
    chunk and block until its output is ready (call
    ``block_until_ready`` on device arrays before returning)."""

    def __init__(self, groups: Optional[List[DeviceGroup]] = None,
                 simulated_ratio: float = 4.0, n_chunks: int = 16,
                 steal: bool = True,
                 time_model: Optional[Callable[[str, int], float]] = None,
                 force_simulated: bool = False):
        if groups is None:
            groups, sim = detect_platform(simulated_ratio, force_simulated)
            self.simulated = sim
        else:
            self.simulated = len({id(d) for g in groups
                                  for d in g.devices}) < len(
                [d for g in groups for d in g.devices])
        self.groups = groups
        self.n_chunks = max(int(n_chunks), 1)
        self.tracker = ThroughputTracker([g.name for g in groups])
        self.cache = get_calibration_cache()
        self.time_model = time_model
        self._async = AsyncChunkExecutor(groups, steal=steal,
                                         time_model=time_model)
        self._cache_key: Optional[str] = None
        self._warm = False
        # the serving scheduler shares ONE executor between concurrent
        # worker threads: calibrate/run_work_shared mutate tracker,
        # steal flags and warm state, so a work-shared call holds this
        # lock end to end (re-entrant: calibrate inside a locked call)
        self._call_lock = threading.RLock()
        self.last_probe_runs = 0     # probe executions paid by the last
        #                              calibrate() (0 = cache/model hit)

    # ------------------------------------------------------------------
    def calibrate(self, fn: Callable[[str, int], object], probe_units: int,
                  workload: Optional[str] = None, iters: int = 1,
                  unit_cost=None, probe: bool = True) -> None:
        """Seed per-group throughput for a workload (paper §4.5).

        On a cache hit for every group the probe runs are skipped
        entirely — the cached seconds/unit are installed; the cache is
        disk-persistent, so a *fresh process* also plans its first call
        with zero probe runs.  Compile warmup is tracked separately:
        only entries measured in this process suppress it (a disk hit
        calibrates the plan but jit shapes are still cold here).

        ``unit_cost`` (a ``core.cost_model.CostTerms`` describing ONE
        work unit, or a per-group-name dict of them for workloads whose
        groups run *different algorithms* — spmv's ELL head vs COO
        tail) supplies a model-predicted prior on a cache miss, so
        even a first-ever call plans without probes; the model's guess
        is never persisted — the first real chunks overwrite it with
        measurements.  On a miss without ``unit_cost`` (or with the
        model disabled) each group runs the probe ``1 + iters`` times
        (one warmup so jit compilation never distorts the measurement),
        *under the group's pinned device context* — jit executables are
        cached per device, and jax.default_device is part of the cache
        key, so an unpinned probe would time (and warm) the main
        thread's device for every group, leaving the other groups'
        compiles inside the timed path and their probe timings wrong.
        ``last_probe_runs`` reports how many groups actually probed
        (0 = fully cache/model seeded: PR 3's zero-probe contract).

        ``probe=False`` forbids probe runs entirely (the serving
        scheduler's batched executions, where ``fn`` would re-execute a
        member request): a group with neither a cache entry nor a model
        prior is simply left unseeded — the plan starts symmetric and
        work stealing absorbs the error within the first call.
        """
        with self._call_lock:
            self.tracker.reset()
            self._cache_key = workload
            probe_units = max(int(probe_units), 1)
            warm = True
            self.last_probe_runs = 0
            for g in self.groups:
                cached = (self.cache.get(workload, g.name, g.slowdown)
                          if workload else None)
                if cached is not None:
                    self.tracker.seed(g.name, cached)
                    warm = warm and self.cache.warmed_in_process(
                        workload, g.name, g.slowdown)
                    continue
                warm = False
                uc = (unit_cost.get(g.name)
                      if isinstance(unit_cost, dict) else unit_cost)
                if uc is not None:
                    from repro.core import cost_model
                    if cost_model.enabled():
                        t_unit = cost_model.predict(uc, g.device) \
                            * g.slowdown
                        self.tracker.seed(g.name, t_unit)
                        continue
                if not probe:
                    continue
                dev = g.devices[0] if g.devices else None
                ctx = (jax.default_device(dev) if dev is not None
                       else nullcontext())
                with ctx:
                    t = measure(lambda: fn(g.name, probe_units), warmup=1,
                                iters=iters)
                self.last_probe_runs += 1
                t *= g.slowdown
                self.tracker.update(g.name, probe_units, t)
                if workload:
                    self.cache.put(workload, g.name, t / probe_units,
                                   g.slowdown)
            self._warm = warm
            self.tracker.mark_planned()

    def plan(self, total_units: int, comm_cost: float = 0.0,
             post_cost: float = 0.0,
             min_units: int = 0) -> work_sharing.WorkPlan:
        thr = self.tracker.throughputs([g.name for g in self.groups])
        return work_sharing.plan_work(total_units, thr, comm_cost, post_cost,
                                      min_units=min_units)

    # ------------------------------------------------------------------
    def _mode(self) -> str:
        if self.time_model is not None or self.simulated:
            return "virtual"
        return "threads"

    def run_work_shared(self, workload: str, total_units: int,
                        run_share: Callable[[str, int, int], object],
                        combine: Callable[[Sequence[object]], object],
                        comm_cost: float = 0.0, post_cost: float = 0.0,
                        warmup: Optional[bool] = None,
                        plan_override: Optional[Sequence[int]] = None,
                        sequential: bool = False,
                        steal: Optional[bool] = None,
                        whole_shares: bool = False,
                        min_units: int = 0) -> WorkSharedOutput:
        """Execute one work-shared computation, chunk-pipelined.

        run_share(group_name, start_unit, n_units) -> share output
        combine(outputs) -> final value (outputs arrive in unit order)
        warmup: force (True) or suppress (False) the one untimed
        warmup chunk per group; default None warms only when the
        calibration cache was cold for this workload.
        plan_override: force this exact unit split (benchmark sweeps);
        also disables stealing so the forced split is honored.
        sequential: run the no-overlap baseline loop instead (each
        chunk still executes exactly once).
        steal: per-call work-stealing override — suitability-split
        workloads (spmv's dense-head/sparse-tail) pass False because a
        cross-path steal recompiles data-dependent shapes mid-run.
        whole_shares: execute each group's share as ONE chunk (implies
        no stealing) — for suitability splits whose per-chunk shapes
        are data-dependent, where a uniform chunk grid would make
        every chunk a fresh jit compile + packing in the timed path.
        min_units: floor every live group's share (the serving
        scheduler's batched executions pass 1 so a group with a stale
        slow estimate keeps executing — and correcting — its own
        measurement instead of starving on its own history).

        Thread-safe: the whole call holds the executor's re-entrant
        call lock (a work-shared call needs every group anyway), so the
        serving scheduler can share one executor between workers."""
        with self._call_lock:
            return self._run_work_shared_locked(
                workload, total_units, run_share, combine, comm_cost,
                post_cost, warmup, plan_override, sequential, steal,
                whole_shares, min_units)

    def _run_work_shared_locked(self, workload, total_units, run_share,
                                combine, comm_cost, post_cost, warmup,
                                plan_override, sequential, steal,
                                whole_shares, min_units) -> WorkSharedOutput:
        cache_key = self._cache_key or workload
        plan = self.plan(total_units, comm_cost, post_cost,
                         min_units=min_units)
        chunk_units = max(total_units // self.n_chunks, 1)
        if plan_override is not None:
            units = list(plan_override)
        else:
            # chunk-rounded shares, damped against call-to-call drift so
            # chunk->group assignment (and jit shapes) stay stable
            names = [g.name for g in self.groups]
            # plans are per platform: the same workload on a different
            # slowdown profile (Hybrid-High vs -Low) must not reuse or
            # damp against this platform's chunk assignment
            plan_key = cache_key + "|" + ",".join(
                f"{g.name}:{g.slowdown:g}" for g in self.groups)
            assigned0 = ([int(u) for u in plan.units] if whole_shares
                         else _assigned_units(plan.units, names,
                                              chunk_units))
            units = self.cache.sticky_plan(
                plan_key, total_units, chunk_units, assigned0)
        do_warmup = (not self._warm) if warmup is None else warmup

        mode = "sequential" if sequential else self._mode()
        # what the scheduler will actually allow (mirrors the override
        # applied to self._async.steal below + AsyncChunkExecutor.run)
        base_steal = self._async.steal if steal is None else steal
        eff_steal = (base_steal and mode != "sequential"
                     and not whole_shares and plan_override is None)

        if do_warmup:
            # warm the chunk shapes each group will actually execute:
            # one representative per (units, at-lo-boundary,
            # at-hi-boundary) signature — boundary chunks see
            # halo-clamped shapes, the grid tail may be a short chunk,
            # and suitability-split groups (spmv) must not be warmed on
            # ranges the other path owns.  Each group warms *under its
            # device context*: the worker threads pin devices and jit
            # executables are cached per device, so a main-thread
            # warmup would leave the other device's compiles inside
            # the timed path.  With stealing on, every group warms the
            # whole grid's signatures — a stolen boundary chunk must
            # not compile mid-run either.
            names = [g.name for g in self.groups]
            active = [(n_, k) for n_, k in zip(names, units) if k > 0]
            total_assigned = sum(k for _, k in active)
            if whole_shares:
                queues = make_share_chunks([k for _, k in active],
                                           [n_ for n_, _ in active])
            else:
                queues = make_chunks([k for _, k in active],
                                     [n_ for n_, _ in active], chunk_units)
            all_chunks = [c for q in queues.values() for c in q]
            by_name = {g.name: g for g in self.groups}
            warmed = set()
            for name, q in queues.items():
                g = by_name[name]
                dev = g.devices[0] if g.devices else None
                ctx = (jax.default_device(dev) if dev is not None
                       else nullcontext())
                chunks = all_chunks if eff_steal else q
                with ctx:
                    for c in chunks:
                        end = c.start + c.units
                        # near-boundary flags: halo workloads clamp the
                        # SECOND and PENULTIMATE chunks too (a halo that
                        # reaches past the grid edge), so those shapes
                        # get their own warmup representative
                        sig = (id(dev) if dev is not None else None,
                               c.units, c.start == 0,
                               c.start <= chunk_units,
                               end == total_assigned,
                               total_assigned - end <= chunk_units)
                        if sig in warmed:
                            continue
                        warmed.add(sig)
                        jax.block_until_ready(
                            run_share(name, c.start, c.units))

        saved_steal = self._async.steal
        if plan_override is not None:
            self._async.steal = False
        elif steal is not None:
            self._async.steal = steal
        try:
            thr = self.tracker.throughputs([g.name for g in self.groups])
            priors = {g.name: (1.0 / t if t > 0 else 1.0)
                      for g, t in zip(self.groups, thr)}
            # groups with a calibrated/model-seeded unit time carry a
            # trustworthy projection: they may steal before timing a
            # chunk of their own this call (cold first calls included)
            trusted = [g.name for g in self.groups
                       if self.tracker.stats[g.name].n_obs > 0]
            trace = self._async.run(units, run_share, chunk_units, mode,
                                    unit_time_priors=priors,
                                    whole_shares=whole_shares,
                                    trusted_priors=trusted)
        finally:
            self._async.steal = saved_steal
        self._trace_chunks(workload, trace)

        if do_warmup:
            combine(list(trace.outputs))     # warm merge-path compiles too
        t0 = time.perf_counter()
        value = combine(list(trace.outputs))
        merge_t = time.perf_counter() - t0

        # measured makespan: concurrent span + un-hidden comm + merge
        hybrid_time = trace.makespan + comm_cost + merge_t + post_cost
        # analytic model of the *chunked* assignment (shares round to
        # whole chunks, so the ideal fractional plan would under- or
        # over-state the slow group's span)
        assigned = (list(units) if whole_shares else
                    _assigned_units(units, [g.name for g in self.groups],
                                    chunk_units))
        thr_now = self.tracker.throughputs([g.name for g in self.groups])
        spans = [u / t for u, t in zip(assigned, thr_now) if t > 0]
        n_active = sum(1 for u in assigned if u > 0)
        analytic = (max(spans) if spans else 0.0) + (
            comm_cost + post_cost if n_active > 1 else 0.0)
        # the same model with THIS run's observed per-unit times — the
        # paper's overlap structure (max, not sum) minus EWMA staleness
        # and machine-speed drift; groups that executed nothing fall
        # back to the EWMA estimate
        spans_obs = []
        for g, u, t in zip(self.groups, assigned, thr_now):
            if u <= 0:
                continue
            done_u = trace.group_units.get(g.name, 0)
            if done_u > 0:
                spans_obs.append(u * trace.group_busy[g.name] / done_u)
            elif t > 0:
                spans_obs.append(u / t)
        analytic_obs = (max(spans_obs) if spans_obs else 0.0) + (
            comm_cost + merge_t + post_cost if n_active > 1 else merge_t)
        for g in self.groups:
            n_done = trace.group_units.get(g.name, 0)
            if n_done > 0:
                self.tracker.update(g.name, n_done,
                                    trace.group_busy[g.name])
                if cache_key:
                    self.cache.put(cache_key, g.name,
                                   trace.group_busy[g.name] / n_done,
                                   g.slowdown)
        # single-device-alone times from calibrated throughput
        single = {}
        for g in self.groups:
            thr = self.tracker.throughputs([g.name])[0]
            single[g.name] = total_units / thr if thr > 0 else float("inf")
        busy = {g.name: trace.group_busy.get(g.name, 0.0)
                for g in self.groups}
        res = HybridResult(workload, hybrid_time, single, busy,
                           analytic_time=analytic,
                           steals=trace.steals, n_chunks=trace.n_chunks,
                           mode=trace.mode,
                           analytic_observed_time=analytic_obs)
        return WorkSharedOutput(value, res, plan, self.simulated, trace)

    @staticmethod
    def _trace_chunks(workload: str, trace: ExecutionTrace) -> None:
        """Per-chunk spans + steal instants for the tracing layer.

        Emitted post-hoc from the execution records (no per-chunk hook
        in the hot worker loop): records carry call-relative times, so
        ``trace.t_base`` re-anchors them onto the recorder's monotonic
        timeline.  Virtual-mode spans are positioned by the simulated
        clocks — flagged in args so a viewer knows they are modeled."""
        rec = get_recorder()
        if not rec.enabled or not trace.records:
            return
        for r in trace.records:
            track = f"hybrid:{r.group}"
            rec.complete("chunk", "exec", trace.t_base + r.t_start,
                         trace.t_base + r.t_end, track,
                         workload=workload, units=r.chunk.units,
                         seq=r.chunk.seq, owner=r.chunk.owner,
                         stolen=r.stolen, mode=trace.mode)
            if r.stolen:
                rec.instant("steal", "exec", track, workload=workload,
                            seq=r.chunk.seq, owner=r.chunk.owner)

    # ------------------------------------------------------------------
    def run_single(self, group_name: str, fn: Callable[[], object]
                   ) -> Tuple[object, float]:
        g = next(g for g in self.groups if g.name == group_name)
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)   # time execution, not async launch
        return out, (time.perf_counter() - t0) * g.slowdown
