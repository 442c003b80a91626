"""Cold-vs-warm first-call latency: what a *fresh process* pays.

Two serving-scale costs are measured, each in its own subprocess so jit
caches, tune caches and calibration stores are genuinely cold:

* **Autotune search** — per kernel: the model-ranked top-K search
  (`REPRO_TUNE_TOPK`, the default) vs the exhaustive full search
  (`REPRO_TUNE_TOPK=0`), on fresh cache files, plus the warm pure-
  lookup cost.  The top-K search runs FIRST in the subprocess, so it
  pays all cold-compile cost and the full search inherits warm
  executables — the reported speedup is conservative.  Winner quality
  is checked by timing both winners head-to-head (`winner_time_ratio`
  = topk winner time / full winner time; 1.0 = identical pick or a
  tie).
* **Hybrid calibration** — process A runs the Conv workload twice
  against a fresh persistent calibration store (probing, converging,
  persisting); process B starts cold on the same store and must plan
  its first call with ZERO probe runs and a plan matching A's within
  one chunk per group.  (`REPRO_COST_MODEL=0` in both, so the match
  demonstrates *persistence*, not model priors.)

Rows land in BENCH_history.jsonl via ``run.py --json`` and
``regress.py`` gates them (with a looser threshold — subprocess
cold-start numbers carry compile-time noise).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KERNELS = ("conv2d", "hist", "flash_attention", "gmm")


# ---------------------------------------------------------------------------
# Child-process workers
# ---------------------------------------------------------------------------
def _setup(kernel, neighbor: bool = False):
    """(tuned_config thunk, run(cfg) thunk, n_candidates) per kernel,
    at the kernels_bench reference shapes.  ``neighbor=True`` builds a
    sibling shape one bucket over (cross-shape-transfer target)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if kernel == "conv2d":
        from repro.kernels.conv2d import ops
        n = 768 if neighbor else 512
        img = jax.random.normal(jax.random.key(5), (n, n))
        w = jax.random.normal(jax.random.key(6), (15, 15))
        return (lambda: ops.tuned_config(img, w),
                lambda cfg: ops.conv2d(img, w, config=cfg)
                .block_until_ready(),
                len(ops.candidates(n, n, 15)))
    if kernel == "hist":
        from repro.kernels.hist import ops
        n = (1 << 19) if neighbor else (1 << 20)
        x = jnp.asarray(np.random.default_rng(0).integers(
            0, 256, n, dtype=np.int32))
        return (lambda: ops.tuned_config(x, 256),
                lambda cfg: ops.histogram(x, 256, config=cfg)
                .block_until_ready(),
                len(ops.candidates(n, 256)))
    if kernel == "flash_attention":
        from repro.kernels.flash_attention import ops
        t = 512 if neighbor else 1024
        q = jax.random.normal(jax.random.key(0), (1, t, 8, 64),
                              jnp.bfloat16)
        k = jax.random.normal(jax.random.key(1), (1, t, 2, 64),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.key(2), (1, t, 2, 64),
                              jnp.bfloat16)
        return (lambda: ops.tuned_config(q, k, v),
                lambda cfg: ops.flash_attention(q, k, v, config=cfg)
                .block_until_ready(),
                len(ops.candidates(t, t, 64)))
    if kernel == "gmm":
        from repro.kernels.gmm import ops
        c = 512 if neighbor else 256
        xe = jax.random.normal(jax.random.key(3), (8, c, 256),
                               jnp.bfloat16)
        we = jax.random.normal(jax.random.key(4), (8, 256, 512),
                               jnp.bfloat16)
        return (lambda: ops.tuned_config(xe, we),
                lambda cfg: ops.gmm(xe, we, config=cfg)
                .block_until_ready(),
                len(ops.candidates(8, c, 256, 512)))
    raise ValueError(kernel)


def _child_profile() -> None:
    """Measure the hardware profile once into the (parent-supplied,
    throwaway) REPRO_CALIB_CACHE store, so the search children below
    get a disk hit instead of measuring it inside their timed search —
    and none of them ever touch the user's real store."""
    from repro.core import cost_model
    cost_model.get_profile()
    print("RESULT" + json.dumps({"ok": True}))


def _child_search(kernel: str, tmpdir: str, mode: str,
                  rival_cfg: str = "") -> None:
    """One genuinely-cold search in THIS process (the parent points
    REPRO_CALIB_CACHE at a throwaway store pre-warmed by
    ``_child_profile``).  mode="topk" uses the default model-ranked
    search, then demonstrates cross-shape transfer on a neighbor
    bucket; mode="full" disables ranking and transfer (the pre-PR-3
    exhaustive search) and, when the topk winner differs (passed via
    ``rival_cfg``), times both winners head-to-head."""
    os.environ["REPRO_AUTOTUNE"] = "1"
    os.environ["REPRO_TUNE_CACHE"] = os.path.join(tmpdir, mode + ".json")
    if mode == "full":
        os.environ["REPRO_TUNE_TOPK"] = "0"
        os.environ["REPRO_TUNE_TRANSFER"] = "0"
    else:
        os.environ.pop("REPRO_TUNE_TOPK", None)
        os.environ.pop("REPRO_TUNE_TRANSFER", None)
    from repro.core.calibration import measure
    from repro.kernels import autotune as at

    tuned, run, n_cands = _setup(kernel)
    calls = []
    default_timer = at._default_timer
    at.set_timer(lambda fn: (calls.append(1), default_timer(fn))[1])

    at.reset_tune_cache()
    t0 = time.perf_counter()
    cfg = tuned()                              # cold: search + compiles
    t_search = time.perf_counter() - t0
    n_measured = len(calls)

    at.reset_tune_cache()                      # drop memory, keep file
    t0 = time.perf_counter()
    cfg_warm = tuned()                         # pure disk lookup
    t_warm = time.perf_counter() - t0
    assert cfg_warm == cfg, (cfg_warm, cfg)

    out = {"t_search": t_search, "t_warm": t_warm,
           "n_measured": n_measured, "n_candidates": n_cands,
           "cfg": cfg}
    if mode == "topk":
        # neighbor bucket: seeded by transfer (1 measurement expected)
        calls.clear()
        tuned_nb, _, _ = _setup(kernel, neighbor=True)
        t0 = time.perf_counter()
        out["cfg_transfer"] = tuned_nb()
        out["t_transfer"] = time.perf_counter() - t0
        out["n_transfer"] = len(calls)
    at.set_timer(None)
    if mode == "full" and rival_cfg:
        rival = json.loads(rival_cfg)
        if rival != cfg:
            t_mine = measure(lambda: run(cfg), warmup=1, iters=3,
                             reduce="min")
            t_rival = measure(lambda: run(rival), warmup=1, iters=3,
                              reduce="min")
            out["winner_time_ratio"] = t_rival / max(t_mine, 1e-9)
    print("RESULT" + json.dumps(out))


def _child_hybrid(phase: int, tmpdir: str) -> None:
    os.environ["REPRO_CALIB_CACHE"] = os.path.join(tmpdir, "calib.json")
    os.environ["REPRO_TUNE_CACHE"] = os.path.join(tmpdir, "tune.json")
    os.environ["REPRO_COST_MODEL"] = "0"       # isolate persistence
    os.environ["REPRO_AUTOTUNE"] = "1"
    from repro.core import hybrid_executor as hx
    from repro.workloads import conv

    probes = []
    orig_measure = hx.measure
    hx.measure = lambda fn, **kw: (probes.append(1),
                                   orig_measure(fn, **kw))[1]
    ex = hx.HybridExecutor(n_chunks=16)
    t0 = time.perf_counter()
    out = conv.run_hybrid(ex, size=512, ksize=15)
    t_first = time.perf_counter() - t0
    probes_first = len(probes)
    if phase == 1:                             # converge + persist
        out = conv.run_hybrid(ex, size=512, ksize=15)
    plan = {}
    for c in out.trace.chunks:
        plan[c.owner] = plan.get(c.owner, 0) + c.units
    print("RESULT" + json.dumps({
        "probes_first_call": probes_first, "plan": plan,
        "t_first": t_first, "chunk_units": 512 // 16}))


# ---------------------------------------------------------------------------
# Parent: orchestrate subprocesses, print CSV rows
# ---------------------------------------------------------------------------
def _spawn(args, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env.update(extra_env or {})
    res = subprocess.run([sys.executable, os.path.abspath(__file__)] + args,
                         capture_output=True, text=True, timeout=900,
                         env=env, cwd=_ROOT)
    if res.returncode != 0:
        raise RuntimeError(f"cold_start child {args} failed:\n"
                           f"{res.stdout}\n{res.stderr}")
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def run() -> bool:
    """Every phase in its own child; this process never touches JAX.
    True when every phase ran — a failed child is reported and fails
    the run, it is never skipped."""
    with tempfile.TemporaryDirectory(prefix="repro-cold-store-") as store:
        failures = _run(store)
    for msg in failures:
        print(f"cold_start: FAIL {msg}")
    return not failures


def _run(store: str) -> list:
    failures = []
    calib_env = {"REPRO_CALIB_CACHE": os.path.join(store, "calib.json")}
    try:
        _spawn(["--child", "profile"], calib_env)
    except (RuntimeError, subprocess.TimeoutExpired, IndexError) as e:
        failures.append(f"profile warm: {e}")
    for kernel in KERNELS:
        with tempfile.TemporaryDirectory(prefix="repro-cold-") as d:
            try:
                topk = _spawn(["--child", "search", "--kernel", kernel,
                               "--tmpdir", d, "--mode", "topk"],
                              calib_env)
                full = _spawn(["--child", "search", "--kernel", kernel,
                               "--tmpdir", d, "--mode", "full",
                               "--rival-cfg", json.dumps(topk["cfg"])],
                              calib_env)
            except (RuntimeError, subprocess.TimeoutExpired, IndexError) as e:
                failures.append(f"{kernel}: {e}")
                continue
        speedup = full["t_search"] / max(topk["t_search"], 1e-9)
        match = topk["cfg"] == full["cfg"]
        # identical winners are by definition equally fast; only a
        # differing pick gets the measured head-to-head ratio
        ratio = 1.0 if match else full.get("winner_time_ratio", 1.0)
        print(f"cold_start/{kernel}_search_full,"
              f"{full['t_search'] * 1e6:.0f},"
              f"measured={full['n_measured']}/{full['n_candidates']}")
        print(f"cold_start/{kernel}_search_topk,"
              f"{topk['t_search'] * 1e6:.0f},"
              f"speedup={speedup:.2f}x|measured={topk['n_measured']}"
              f"|winner_match={match}"
              f"|winner_time_ratio={ratio:.2f}")
        print(f"cold_start/{kernel}_transfer_bucket,"
              f"{topk['t_transfer'] * 1e6:.0f},"
              f"measured={topk['n_transfer']}|seeded_from_sibling")
        print(f"cold_start/{kernel}_warm_lookup,"
              f"{topk['t_warm'] * 1e6:.0f},cache_hit")

    with tempfile.TemporaryDirectory(prefix="repro-cold-") as d:
        try:
            a = _spawn(["--child", "hybrid", "--phase", "1", "--tmpdir", d])
            b = _spawn(["--child", "hybrid", "--phase", "2", "--tmpdir", d])
        except (RuntimeError, subprocess.TimeoutExpired, IndexError) as e:
            failures.append(f"hybrid: {e}")
            return failures
    cu = a["chunk_units"]
    groups = set(a["plan"]) | set(b["plan"])
    max_delta = max(abs(a["plan"].get(g, 0) - b["plan"].get(g, 0))
                    for g in groups)
    print(f"cold_start/hybrid_conv_first_call,{b['t_first'] * 1e6:.0f},"
          f"probes={b['probes_first_call']}"
          f"|plan_match={max_delta <= cu}"
          f"|max_plan_delta_units={max_delta}"
          f"|cold_probes={a['probes_first_call']}")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", choices=["search", "hybrid", "profile"])
    ap.add_argument("--kernel", default="conv2d")
    ap.add_argument("--mode", default="topk", choices=["topk", "full"])
    ap.add_argument("--rival-cfg", default="")
    ap.add_argument("--phase", type=int, default=1)
    ap.add_argument("--tmpdir", default=None)
    args = ap.parse_args()
    if args.child == "search":
        _child_search(args.kernel, args.tmpdir, args.mode, args.rival_cfg)
    elif args.child == "hybrid":
        _child_hybrid(args.phase, args.tmpdir)
    elif args.child == "profile":
        _child_profile()
    else:
        sys.exit(0 if run() else 1)


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    main()
