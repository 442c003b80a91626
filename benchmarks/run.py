"""Benchmark entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  table2/*   — Table 2 (13 workloads x 2 platforms, gain/idle/eff,
               measured vs analytic-model makespan)
  fig3/*     — Fig. 3 scaling over input sizes
  fig4/*     — Fig. 4 Conv overlap timeline (measured vs model)
  fig5/*     — Fig. 5 LR task assignment
  split_sweep/* — §5.4.3 work-split sweep, executed splits vs model
  kernels/*  — per-kernel microbenches
  roofline/* — §Roofline terms per (arch x shape), from dry-run+probe

``--json`` additionally writes machine-readable results so the perf
trajectory is tracked across PRs:
  BENCH_kernels.json  — kernels/*, cold_start/* and roofline/* rows
  BENCH_hybrid.json   — table2/fig3/fig4/fig5/split_sweep rows
  BENCH_serving.json  — serving/* rows (written by serving_bench)
  BENCH_history.jsonl — one timestamped line per kernel, cold-start
                        AND serving row per run; benchmarks/regress.py
                        gates on it (>20% regression vs the previous
                        entry fails; cold_start/* and serving/* rows
                        gate at looser thresholds — subprocess cold
                        numbers carry compile noise, serving rows
                        carry queueing-tail noise)

The cold_start and serving sections (fresh-process first-call latency;
scheduler-vs-FIFO latency percentiles + the two-process zero-probe
check) only run under ``--json`` — they spawn subprocesses and are the
slowest sections.
"""
import argparse
import datetime
import io
import json
import os
import re
import sys
from contextlib import redirect_stdout

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ROW = re.compile(r"^([A-Za-z0-9_./+-]+/[^,]*),([-\d.]+),(.*)$")


def _capture(fn):
    """Run a section, tee its stdout, return parsed CSV rows."""
    buf = io.StringIO()
    with redirect_stdout(buf):
        fn()
    text = buf.getvalue()
    sys.stdout.write(text)
    rows = []
    for line in text.splitlines():
        m = _ROW.match(line.strip())
        if m:
            rows.append({"name": m.group(1), "us": float(m.group(2)),
                         "derived": m.group(3)})
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_kernels.json / BENCH_hybrid.json")
    args = ap.parse_args()

    for p in (_ROOT, os.path.join(_ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchmarks import (cold_start, fig3_scaling, fig4_overlap,
                            fig5_tasks, kernels_bench, roofline,
                            split_sweep, table2_hybrid)
    from repro.core import compile_cache
    compile_cache.enable()
    hybrid_rows, kernel_rows = [], []
    cold_ok = serving_ok = True
    if args.json:
        # the sections that start JAX processes run first, while this
        # process is still off JAX (a parent holding the chip would lock
        # its children out of it)
        print("# === cold start (fresh-process first-call latency) ===")
        cold_state = {}
        kernel_rows += _capture(
            lambda: cold_state.update(ok=cold_start.run()))
        cold_ok = cold_state.get("ok", False)
        print("# === serving (scheduler vs FIFO, smoke trace) ===")
        from benchmarks import serving_bench
        serving_state = {}

        def _serving():
            # json_out=False: the smoke trace must not clobber a full
            # 2-device measurement stored in BENCH_serving.json; the
            # trajectory still lands in BENCH_history.jsonl below
            ok, _ = serving_bench.run(smoke=True, json_out=False)
            serving_state["ok"] = ok

        kernel_rows += _capture(_serving)
        serving_ok = serving_state.get("ok", False)
    print("# === Table 2: hybrid gain / idle (13 workloads) ===")
    hybrid_rows += _capture(table2_hybrid.run)
    print("# === Fig 3: scaling ===")
    hybrid_rows += _capture(fig3_scaling.run)
    print("# === Fig 4: Conv overlap (measured vs model) ===")
    hybrid_rows += _capture(fig4_overlap.run)
    print("# === Fig 5: LR tasks ===")
    hybrid_rows += _capture(fig5_tasks.run)
    print("# === 5.4.3: split sweep (executed) ===")
    hybrid_rows += _capture(split_sweep.run)
    print("# === kernels ===")
    kernel_rows += _capture(kernels_bench.run)
    print("# === roofline (40 cells) ===")
    kernel_rows += _capture(roofline.run)

    if args.json:
        import jax
        d = jax.devices()
        meta = {"platform": d[0].platform, "device_kind": d[0].device_kind,
                "n_devices": len(d)}
        with open(os.path.join(_ROOT, "BENCH_kernels.json"), "w") as f:
            json.dump({"meta": meta, "rows": kernel_rows}, f, indent=1)
        with open(os.path.join(_ROOT, "BENCH_hybrid.json"), "w") as f:
            json.dump({"meta": meta, "rows": hybrid_rows}, f, indent=1)
        ts = datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds")
        n_hist = 0
        with open(os.path.join(_ROOT, "BENCH_history.jsonl"), "a") as f:
            for row in kernel_rows:
                if not row["name"].startswith(("kernels/", "cold_start/",
                                               "serving/")):
                    continue
                f.write(json.dumps({"ts": ts, "backend": meta["platform"],
                                    **row}) + "\n")
                n_hist += 1
        print(f"# wrote BENCH_kernels.json ({len(kernel_rows)} rows), "
              f"BENCH_hybrid.json ({len(hybrid_rows)} rows), "
              f"BENCH_history.jsonl (+{n_hist} rows)")
    if not (serving_ok and cold_ok):
        # a failed phase (serving invariants, a cold-start child) must
        # not pass silently through a bench run
        print("# FAILED — see the serving and cold start sections above")
        sys.exit(1)


if __name__ == '__main__':
    main()
