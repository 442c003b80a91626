"""Production serving launcher: batched generation for an assigned arch.

    PYTHONPATH=src python -m repro.launch.serve --arch minicpm3-4b \
        --batch 4 --new-tokens 16 [--hybrid | --stream]

``--hybrid`` splits ONE request batch across the detected device groups
through the chunk-pipelined HybridExecutor (rows = work units), so on a
multi-device host the shares decode concurrently and the report shows
measured vs model makespan.

``--stream`` drives the full serving subsystem instead: a synthetic
open-loop arrival trace (Poisson inter-arrivals at ``--rate`` req/s for
``--duration`` seconds) submitted to the ``repro.serve.Scheduler``,
which places each request (dedicated / work-shared / queued) from the
cost model, coalesces same-shape arrivals, and sheds what misses
``--deadline``.  Prints per-request latency percentiles and the
scheduler's load telemetry.

``--trace out.json`` exports the run's span timeline as Chrome
trace-event JSON (open in ``chrome://tracing`` or Perfetto);
``--stats-json stats.json`` dumps the final ``ServeStats`` snapshot
plus engine placements as JSON for scripting.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.models import model_zoo, param
from repro.serve.serve_step import generate


def _percentiles(xs):
    if not xs:
        return {}
    arr = np.asarray(sorted(xs))
    return {p: float(np.percentile(arr, p)) for p in (50, 95, 99)}


def run_stream(cfg, params, args) -> None:
    """Open-loop arrival trace through the serving scheduler."""
    from repro.serve.scheduler import Scheduler
    from repro.serve.request_queue import RequestRejected
    from repro.workloads import requests as adapters

    if args.continuous:
        wl = adapters.make_continuous_lm_adapter(
            cfg, params, prompt_len=args.prompt_len,
            new_tokens=args.new_tokens)
        adapters.wait_precompiled(timeout=600)
    else:
        wl = adapters.make_lm_adapter(cfg, params,
                                      prompt_len=args.prompt_len,
                                      new_tokens=args.new_tokens)
    sched = Scheduler(max_batch=args.max_batch,
                      batch_window_s=args.window_ms / 1e3)
    # one warmup request outside the measured trace: jit compilation is
    # a property of the process, not of the scheduler under test
    sched.submit(wl, {"batch": args.batch}).result(timeout=600)

    import threading

    rng = np.random.default_rng(0)
    futs = []
    done_at = {}
    done_lock = threading.Lock()

    def stamp(f):
        with done_lock:
            done_at[id(f)] = time.perf_counter()

    t_end = time.perf_counter() + args.duration
    t0 = time.perf_counter()
    while time.perf_counter() < t_end:
        f = sched.submit(wl, {"batch": args.batch},
                         deadline=args.deadline)
        # completion stamped by the resolving thread: awaiting futures
        # in submission order would record trace position, not latency
        f.add_done_callback(stamp)
        futs.append((time.perf_counter(), f))
        # open-loop: the NEXT arrival does not wait for this result
        time.sleep(float(rng.exponential(1.0 / max(args.rate, 1e-6))))
    lat, decode, rejected = [], [], 0
    for t_sub, f in futs:
        try:
            f.result(timeout=600)
            lat.append(done_at[id(f)] - t_sub)
            # per-request decode span from the executing lane's stamps
            # (the engine stamps first token after prefill and last
            # token at final eviction) — completion-callback time alone
            # can't separate queueing from decode
            t_ft = f.meta.get("t_first_token")
            t_lt = f.meta.get("t_last_token")
            if t_ft is not None and t_lt is not None:
                decode.append(t_lt - t_ft)
        except RequestRejected:
            rejected += 1
    wall = (max(done_at.values()) - t0) if done_at \
        else time.perf_counter() - t0
    placements = dict(sched.engine_placements)
    audit = sched.audit.summary()
    sched.shutdown()
    if args.stats_json:
        snap = sched.stats.snapshot()
        doc = {"arch": cfg.name, "stats": snap,
               "placement_audit": audit,
               "engine_placements": {
                   name: {"prefill": plan.prefill_group,
                          "decode": plan.decode_group,
                          "disaggregated": plan.disaggregated}
                   for name, plan in placements.items()}}
        with open(args.stats_json, "w") as fh:
            json.dump(doc, fh, indent=2, default=str)
        print(f"stats json -> {args.stats_json}")
    if args.trace:
        from repro.obs import get_recorder
        n = get_recorder().export_chrome(args.trace)
        print(f"trace -> {args.trace} ({n} events)")
    pct = _percentiles(lat)
    print(f"{cfg.name}: {len(futs)} requests over {wall:.1f}s "
          f"(rate {args.rate}/s), {len(lat)} served, {rejected} "
          f"rejected/shed")
    if pct:
        print(f"latency p50={pct[50] * 1e3:.1f}ms "
              f"p95={pct[95] * 1e3:.1f}ms p99={pct[99] * 1e3:.1f}ms "
              f"throughput={len(lat) / wall:.2f} req/s")
    dpct = _percentiles(decode)
    if dpct:
        print(f"decode p50={dpct[50] * 1e3:.1f}ms "
              f"p95={dpct[95] * 1e3:.1f}ms p99={dpct[99] * 1e3:.1f}ms "
              f"({len(decode)} stamped)")
    for name, plan in placements.items():
        print(f"engine {name}: prefill={plan.prefill_group} "
              f"decode={plan.decode_group} "
              f"disaggregated={plan.disaggregated}")
    # fault-tolerance counters: a clean run prints all zeros, which is
    # itself the signal — nonzero retries/failovers under a healthy
    # fleet mean a lane is flapping
    st = sched.stats
    print(f"ft: retries={st.retries} failovers={st.failovers} "
          f"lane_deaths={st.lane_deaths} revivals={st.lane_revivals} "
          f"hedges={st.hedges}/{st.hedge_wins} "
          f"watchdog={st.watchdog_timeouts} "
          f"brownout_shed={st.shed_brownout}")
    print(st.row())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--hybrid", action="store_true",
                    help="work-share the batch across device groups")
    ap.add_argument("--stream", action="store_true",
                    help="drive the serving scheduler with a synthetic "
                         "open-loop arrival trace")
    ap.add_argument("--continuous", action="store_true",
                    help="--stream via the continuous-batching engine "
                         "(decode step as the scheduling quantum)")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="--stream mean arrival rate, requests/s")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="--stream trace length, seconds")
    ap.add_argument("--deadline", type=float, default=None,
                    help="--stream per-request deadline, seconds")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--window-ms", type=float, default=2.0)
    ap.add_argument("--trace", type=str, default=None, metavar="PATH",
                    help="--stream: export Chrome trace-event JSON of "
                         "the run's span timeline")
    ap.add_argument("--stats-json", type=str, default=None,
                    metavar="PATH",
                    help="--stream: dump the final ServeStats snapshot "
                         "+ placement audit as JSON")
    args = ap.parse_args(argv)
    from repro.core import compile_cache
    compile_cache.enable()

    cfg = registry.get(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    if cfg.is_encoder_decoder:
        raise SystemExit("enc-dec serving: see tests/test_archs.py whisper "
                         "decode path")
    params = param.values(model_zoo.init(cfg, jax.random.key(0)))

    if args.stream:
        run_stream(cfg, params, args)
        return

    prompt = jax.random.randint(jax.random.key(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    cache_len = args.prompt_len + args.new_tokens + 1

    if args.hybrid:
        from repro.core.cost_model import lm_decode_terms
        from repro.core.hybrid_executor import HybridExecutor

        ex = HybridExecutor(n_chunks=min(4, args.batch))

        def run_share(group, start, k):
            out = generate(cfg, params, prompt[start:start + k],
                           args.new_tokens, cache_len=cache_len)
            out.block_until_ready()
            return out

        # Calibration threads the group through ex.calibrate the way
        # workloads/conv.py does: the executor pins each group's device
        # context around its probe (an unpinned probe timed — and
        # warmed — the main thread's device for every group) and the
        # decode-roofline unit_cost prior lets a cold cache plan with
        # zero probe runs, so no group ever decodes rows it doesn't own
        # inside the timed path.
        n_params = sum(int(np.prod(x.shape))
                       for x in jax.tree_util.tree_leaves(params))
        unit_cost = lm_decode_terms(n_params, args.new_tokens + 1)
        ex.calibrate(lambda g, k: run_share(g, 0, k),
                     probe_units=max(args.batch // 2, 1),
                     workload=f"serve/{cfg.name}", unit_cost=unit_cost)
        t0 = time.perf_counter()
        ws = ex.run_work_shared(
            f"serve/{cfg.name}", args.batch, run_share,
            combine=lambda outs: jnp.concatenate(outs, axis=0))
        dt = time.perf_counter() - t0
        print(f"{cfg.name}: generated {ws.value.shape} hybrid in {dt:.2f}s")
        print(ws.result.row())
        return

    t0 = time.perf_counter()
    out = generate(cfg, params, prompt, args.new_tokens,
                   cache_len=cache_len)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    print(f"{cfg.name}: generated {out.shape} in {dt:.2f}s")


if __name__ == "__main__":
    main()
