"""One run of one cell: set-up, the measured window, the check, the
metrics and the result line.

The clock is ``time.monotonic()`` throughout.  ``setup_s`` runs from
the start of the process's benchmark code to the first request due;
the window is ``[t0, t0 + seconds)``.  The traffic is a closed loop of
clients; a request is timed from when its client sent it.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import sys
import threading
import time
from types import SimpleNamespace
from typing import Callable, List, Optional

from . import files, stats, trace as tr_mod

WAIT_AFTER_S = 60.0          # how long answers due in the window are awaited


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def prepare(root: str, cell: str, trace: bool,
            state: Optional[str] = None) -> str:
    """Fixed stores inside the checkout (``<root>/.bench_state``), set
    before the program loads: the compile cache and the autotune store
    last across a cell's runs; the calibration store is new in every
    run."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"no program under {src}")
    state = state or os.path.join(root, ".bench_state")
    os.makedirs(os.path.join(state, "calibration"), exist_ok=True)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(state, "jax_cache"))
    os.environ["REPRO_TUNE_CACHE"] = os.path.join(state, "autotune.json")
    calib = os.path.join(state, "calibration", cell + ".json")
    if os.path.exists(calib):
        os.remove(calib)
    os.environ["REPRO_CALIB_CACHE"] = calib
    os.environ["REPRO_TRACE"] = "1" if trace else "0"
    if src not in sys.path:
        sys.path.insert(0, src)
    return state


class Window:
    """Drives the traffic of one window and keeps one record per request:
    ``[due, done, status]`` with status ``None`` (no answer yet), ``"ok"``,
    ``"refused"`` or ``"error"``."""

    def __init__(self, system, tr: dict, seconds: float):
        self.system, self.tr = system, tr
        self.seconds = float(seconds)
        self.records: List[list] = []
        self.errors: List[str] = []
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)

    def _send(self, item, due: float) -> threading.Event:
        rec = [due, None, None]
        ev = threading.Event()
        with self._lock:
            self.records.append(rec)

        def on_done(exc):
            now = time.monotonic()
            with self._cv:
                rec[1] = now
                if exc is None:
                    rec[2] = "ok"
                elif type(exc).__name__ == "RequestRejected":
                    rec[2] = "refused"
                else:
                    rec[2] = "error"
                    self.errors.append(f"{type(exc).__name__}: {exc}")
                self._cv.notify_all()
            ev.set()

        self.system.submit(item, on_done)
        return ev

    def run(self, t0: float) -> None:
        """Each client sends its next request when its last is answered,
        until the window closes."""
        if self.tr["loop"] != "closed":
            raise ValueError(f"unknown loop {self.tr['loop']!r}")
        t1 = t0 + self.seconds
        think = float(self.tr.get("think_s", 0.0))

        def client(c: int) -> None:
            k = 0
            while True:
                now = time.monotonic()
                if now >= t1:
                    return
                item = self.system.item(c, k)
                k += 1
                if not self._send(item, now).wait(t1 - now + WAIT_AFTER_S):
                    return
                if think > 0:
                    time.sleep(think)

        threads = [threading.Thread(target=client, args=(c,),
                                    name=f"bench-client-{c}")
                   for c in range(int(self.tr["clients"]))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def wait(self, until: float) -> int:
        """Wait for every answer up to ``until``; the number missing."""
        with self._cv:
            while True:
                missing = sum(1 for r in self.records if r[2] is None)
                left = until - time.monotonic()
                if missing == 0 or left <= 0:
                    return missing
                self._cv.wait(min(left, 0.5))


def _obs_spans(t0: float, t1: float) -> list:
    """The program's spans on the benchmark clock, those that end inside
    ``[t0, t1)``."""
    from repro.obs import get_recorder
    rec = get_recorder()
    if not rec.enabled:
        return []
    t_mark = time.monotonic()
    rec.instant("bench_sync", "bench", "bench")
    evs = rec.events()
    marks = [e for e in evs if e["name"] == "bench_sync"]
    anchor = marks[-1]["ts"] / 1e6 - t_mark
    out = []
    for e in evs:
        if e.get("ph") != "X":
            continue
        a = e["ts"] / 1e6 - anchor
        b = a + e.get("dur", 0.0) / 1e6
        if t0 <= b < t1:
            out.append({"name": e["name"], "track": e.get("track", "?"),
                        "t0": a, "t1": b, "args": e.get("args", {})})
    return out


class Profiler:
    """A ``jax.profiler`` trace of ``[start, start + seconds)``, taken by
    a thread of this process (the one that holds the chip)."""

    def __init__(self, out_dir: str, start: float, seconds: float):
        self.dir, self.start, self.seconds = out_dir, start, seconds
        self.t_sync = self.t0 = self.t1 = None
        self.error = None
        shutil.rmtree(out_dir, ignore_errors=True)
        self._thread = threading.Thread(target=self._run,
                                        name="bench-profiler")
        self._thread.start()

    def _run(self) -> None:
        import jax
        try:
            time.sleep(max(self.start - time.monotonic(), 0.0))
            jax.profiler.start_trace(self.dir)
            with jax.profiler.TraceAnnotation(tr_mod.SYNC):
                self.t_sync = time.monotonic()
            self.t0 = time.monotonic()
            time.sleep(max(self.t0 + self.seconds - time.monotonic(), 0.0))
            self.t1 = time.monotonic()
            jax.profiler.stop_trace()
        except Exception as e:                     # noqa: BLE001
            self.error = f"{type(e).__name__}: {e}"

    def join(self) -> None:
        self._thread.join()

    def reduce(self, n_devices: int) -> dict:
        if self.error:
            raise RuntimeError(f"profiler: {self.error}")
        events = tr_mod.load(tr_mod.find(self.dir))
        out = tr_mod.reduce(events, self.t0, self.t1, self.t_sync, n_devices)
        out["events"] = events
        return out


def _compile_counter():
    import jax
    n = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}
    lock = threading.Lock()

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            with lock:
                n["compiles"] += 1
                n["compile_s"] += duration

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            with lock:
                n["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return n


class _compile_names:
    """Names of the functions JAX compiles until ``stop()``, read from its
    compile log."""

    def __init__(self):
        import collections
        import logging

        import jax
        self.names = collections.Counter()
        outer = self

        class Handler(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    outer.names[msg.split()[1]] += 1

        # the log goes to this handler alone while the window runs
        self._logger = logging.getLogger("jax")
        self._saved = (self._logger.handlers, self._logger.propagate)
        self._logger.handlers = [Handler(level=logging.WARNING)]
        self._logger.propagate = False
        self._jax = jax
        self._was = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)

    def stop(self):
        self._jax.config.update("jax_log_compiles", self._was)
        self._logger.handlers, self._logger.propagate = self._saved
        return self.names


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (AttributeError, KeyError, TypeError, RuntimeError):
            pass
    return max(peaks) if peaks else None


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: str = files.ROOT,
             require_chip: bool = True,
             config_overrides: Optional[dict] = None,
             traffic_overrides: Optional[dict] = None,
             hook: Optional[Callable] = None,
             state_dir: Optional[str] = None,
             controls: tuple = (), bench: Optional[dict] = None) -> dict:
    """Run one cell once and return the result object (``--seed``,
    ``--seconds``, ``--trace``).  ``require_chip=False`` and the overrides
    are for the benchmark's own tests on the CPU; ``hook(system)`` runs
    after set-up, before the window; ``state_dir`` replaces the
    checkout's store directory; ``controls`` names the configuration's
    controls to read after the check (``bench/control.py``); ``bench``
    replaces BENCHMARK.json."""
    bench = bench or files.benchmark(root)
    cell, cfg_entry = files.cell(bench, cell_name)
    state = prepare(root, cell_name, trace, state_dir)
    cfg = files.config(cfg_entry, root)
    cfg.update(config_overrides or {})
    tr = files.traffic(cell["traffic"])
    tr.update(traffic_overrides or {})

    import jax
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < int(cell["chips"])):
        raise NoChip(f"cell {cell_name} needs {cell['chips']} TPU chip(s); "
                     f"JAX found {len(devices)} {devices[0].platform} "
                     f"device(s)")
    from repro.core import compile_cache
    compile_cache.enable()
    counts = _compile_counter()
    if cfg.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])
    ref = files.module("reference", cfg["name"])
    system = files.module("systems", cfg["system"]).setup(cfg, tr, seed, ref,
                                                          log)
    if hook is not None:
        hook(system)
    setup_counts = dict(counts)
    compiled = _compile_names()
    win = Window(system, tr, seconds)
    from repro.obs import get_recorder
    get_recorder().clear()
    t0 = time.monotonic()
    t1 = t0 + float(seconds)
    prof = None
    if trace:
        # the trace covers [lead, lead + trace_seconds) of the window, as
        # shares of it and seconds, cut at its end
        lead = float(tr.get("trace_lead_s", 0.25)) * float(seconds)
        prof = Profiler(os.path.join(state, "profile", cell_name), t0 + lead,
                        min(float(tr.get("trace_seconds", 4.0)),
                            float(seconds) - lead))
    win.run(t0)
    missing = win.wait(t1 + WAIT_AFTER_S)
    if prof is not None:
        prof.join()
    window_counts = {k: counts[k] - setup_counts[k] for k in counts}
    window_compiled = compiled.stop()
    peak = memory_peak(devices)
    spans = _obs_spans(t0, t1) if trace else []
    counters = system.counters()
    system.shutdown()

    # a request that failed or never came reads as infinitely late
    records = [(due, done, status == "ok")
               for due, done, status in win.records]
    n_due = sum(1 for r in win.records if t0 <= r[0] < t1)
    failed = sum(1 for r in win.records if r[2] != "ok")
    numbers = system.check()
    control_readings = {c: system.check(c) for c in controls}
    correct = (all(v <= lim for _, v, lim in numbers) and missing == 0
               and not win.errors and bool(numbers))

    ctx = SimpleNamespace(
        cell=cell_name, cfg=cfg, traffic=tr, system=system, t0=t0, t1=t1,
        seconds=float(seconds), setup_s=t0 - t_start, spans=spans,
        counters=counters,
        device_kind=devices[0].device_kind, trace=None)
    breakdown = None
    dev = device_info(devices)
    dev["memory_peak_bytes"] = peak
    if trace:
        red = prof.reduce(int(cell["chips"]))
        ctx.trace = red
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        breakdown = {
            "device_ops": tr_mod.top_ops(red["op_s"]),
            "idle_gaps": tr_mod.label_gaps(
                red["gaps"], [s for s in spans
                              if s["t1"] > prof.t0 and s["t0"] < prof.t1])}
    metrics = {}
    for m in files.metrics_of(bench, cell_name, layer=trace):
        value = files.module("metrics", m["name"]).read(ctx)
        if value is None:
            continue
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} is not finite: {value}")
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    log(f"# window {seconds}s: {len(win.records)} sent, {n_due} due in the "
        f"window, {failed} failed or refused, {missing} never answered")
    lat = stats.latencies(records, t0, t1)
    if lat:
        log(f"# latency of the {len(lat)} requests due in the window: p50 "
            f"{stats.median(lat):.4f}s p90 {stats.percentile(lat, 90):.4f}s"
            f" ({stats.beyond(len(lat), 90)} beyond it) max {max(lat):.4f}s")
    log(f"# compiles: set-up {setup_counts} window {window_counts}")
    if window_compiled:
        log(f"# compiled in the window: {window_compiled.most_common(12)}")
    log(f"# counters {json.dumps(counters, sort_keys=True)}")
    for e in win.errors[:5]:
        note(f"# error: {e}")
    # an answer of the wrong shape or not finite reads as the largest
    # float, so that the line stays plain JSON
    compared = [{"name": n, "value": float(min(v, sys.float_info.max)),
                 "limit": float(lim)} for n, v, lim in numbers]
    result = {"correct": bool(correct), "attempted": len(win.records),
              "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if controls:
        result["controls"] = {c: {n: v for n, v, _ in r}
                              for c, r in control_readings.items()}
    result["compared"] = compared
    return result
