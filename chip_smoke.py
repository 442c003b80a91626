"""Chip smoke: the system's main path once, on a TPU host.

    python chip_smoke.py               # one chip: Table-1, kernel, LM phases
    python chip_smoke.py --four-chips  # fleet over four chips vs one worker

One chip.  The scheduler runs the paper's hybrid for real: the ``accel``
lane is the TPU chip and the ``host`` lane is the host CPU.

* Table-1 phase: a ``Scheduler`` serves each of the 13 workloads at the
  sizes of ``benchmarks/table2_hybrid.py``, placed by the cost model;
  each workload is also served pinned to each lane (a FIFO scheduler on
  that lane), and conv is split across both lanes (the paper's §5.4.3
  work share).  Every result is checked against a plain NumPy or
  ``ref.py`` reference computed on the host CPU.
* Kernel phase: each of the seven Pallas kernels is called directly,
  compiled for the TPU (not through the autotuner, which would skip a
  kernel the compiler refuses), and compared with its ``ref.py``.
* LM phase: ``xlstm-350m`` at its published widths serves 4 requests
  (prompt 128, 32 new tokens) through the scheduler's continuous-
  batching engine at ``"highest"`` matmul precision; every served token
  is checked against the engine's stepper driven in a plain loop on the
  same lanes, and the prefill logits and first token against a
  forward.

Four chips (``--four-chips``): this process never imports JAX.  A
router over four worker processes, one chip each, serves a fixed
request list (the Table-1 mix plus reduced-width LM requests), and one
worker on one chip serves the same list; the outputs must agree request
by request, every worker must have served traffic, and the four
workers must report four different TPU chips.

Any failure exits non-zero.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and is printed only when every check passed.  JAX's compile cache lives
in ``$JAX_COMPILATION_CACHE_DIR`` or else ``<repo>/.jax_cache``; the tune
and calibration stores in ``<repo>/.repro_state``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
STATE = os.path.join(ROOT, ".repro_state")

# -- stated tolerances -----------------------------------------------------
# Error is measured as max|out - ref| / max|ref| (the output's scale).
# F32: results computed elementwise in float32 (VPU / XLA:CPU), compared
# with a float64 or float32 reference — rounding and reduction order.
TOL_F32 = 1e-4
# MATMUL: workloads whose hot loop is a matmul or convolution.  On the
# TPU, JAX's default precision rounds float32 operands to bfloat16
# (8 significant bits, 2^-8 = 3.9e-3 per product), so sums of many such
# products land near 1e-2 of the output's scale.
TOL_MATMUL = 2e-2
# bundle reports the squared reprojection residual after two
# Levenberg-Marquardt steps: that is the 0.01 observation noise, and a
# bfloat16 projection moves it by a similar amount.
TOL_BUNDLE = 0.25
# dither: error diffusion is chaotic — one rounding difference flips a
# pixel and its error moves on through the row.  Checked as the share of
# pixels that differ from a float32 NumPy run, and the mean intensity.
DITHER_MAX_FLIPPED = 0.02
DITHER_MEAN_TOL = 0.01
# LM: at default precision on the chip, the random-weight xlstm-350m's
# prefill logits differed from a float32-weight forward by 0.46-0.89 of
# their scale (why is not established on the chip), so the LM phase
# serves at "highest" matmul precision.  The prefill function's logits
# are held to a forward at this share of their scale, and the first
# served token to the forward's argmax at the same share (a near tie may
# go either way).  The default-precision error is printed for
# information.  Every served token must equal the engine's stepper run
# in a plain loop on the same lanes, exactly: the model keeps its
# activations in bfloat16, and a random-weight model this deep turns the
# rounding differences between a slot-batched and a one-request decode
# step into different tokens, so a one-request decode is no reference.
TOL_LOGITS = 1e-2

LM_ARCH = "xlstm-350m"
LM_PROMPT, LM_NEW, LM_REQUESTS = 128, 32, 4
FLEET_CHIPS = 4
# repro.workloads.raycast.N_MARCH_STEPS, which the fleet's parent may not
# import (the module imports JAX); table1_cases checks the two agree
RAYCAST_MARCH_STEPS = 96


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _setup_env() -> None:
    """Fixed, git-ignored stores under the checkout, so nothing in
    ``~/.cache`` decides what runs; set before anything imports repro."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, src)
    os.makedirs(STATE, exist_ok=True)
    os.environ["REPRO_TUNE_CACHE"] = os.path.join(STATE, "autotune.json")
    os.environ["REPRO_CALIB_CACHE"] = os.path.join(STATE,
                                                   "calibration.json")
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [src] + ([os.environ["PYTHONPATH"]]
                 if os.environ.get("PYTHONPATH") else []))


def rel_err(out, ref) -> float:
    import numpy as np
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape:
        return float("inf")
    if not np.all(np.isfinite(out)):
        return float("inf")
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)),
                                                 1e-30))


def result_platform(value) -> str:
    """Platform of the device a result lives on (NumPy values were
    computed on the host)."""
    devices = getattr(value, "devices", None)
    if devices is None:
        return "host-numpy"
    return ",".join(sorted({d.platform for d in devices()}))


# ---------------------------------------------------------------------------
# Table-1 references: plain NumPy (or ref.py on the host CPU)
# ---------------------------------------------------------------------------
def _canon_labels(labels):
    """Component labels -> each vertex's smallest member id."""
    import numpy as np
    labels = np.asarray(labels)
    _, inv = np.unique(labels, return_inverse=True)
    first = np.full(inv.max() + 1, labels.shape[0], np.int64)
    np.minimum.at(first, inv, np.arange(labels.shape[0]))
    return first[inv]


def _np_dither(img):
    import numpy as np
    img = np.asarray(img, np.float32)
    H, W = img.shape
    out = np.zeros_like(img)
    below = np.zeros(W, np.float32)
    s7, s5, s3, s1 = (np.float32(v / 16) for v in (7, 5, 3, 1))
    for i in range(H):
        errs = np.zeros(W, np.float32)
        right = np.float32(0.0)
        for j in range(W):
            old = img[i, j] + below[j] + right
            new = np.float32(255.0) if old > 127.5 else np.float32(0.0)
            e = old - new
            out[i, j] = new
            errs[j] = e
            right = e * s7
        down = errs * s5
        left = np.roll(errs * s3, -1)
        left[-1] = 0.0
        rgt = np.roll(errs * s1, 1)
        rgt[0] = 0.0
        below = down + left + rgt
    return out


def _np_lbm(f, n_steps):
    import numpy as np

    from repro.workloads import lbm
    C, W, om = lbm.C.astype(np.float64), lbm.W.astype(np.float64), lbm.OMEGA
    f = np.asarray(f, np.float64)
    for _ in range(n_steps):
        rho = f.sum(0)
        u = np.einsum("qxyz,qi->ixyz", f, C) / np.maximum(rho, 1e-9)[None]
        cu = np.einsum("qi,ixyz->qxyz", C, u)
        uu = (u * u).sum(0)[None]
        feq = W[:, None, None, None] * rho[None] * (
            1 + 3 * cu + 4.5 * cu ** 2 - 1.5 * uu)
        post = f + om * (feq - f)
        f = np.stack([np.roll(post[q], shift=tuple(int(c) for c in C[q]),
                              axis=(0, 1, 2)) for q in range(19)])
    return f


def _np_raycast(vol, ro, rd, n_steps):
    """Sample positions in float32 like the workload, so a sample on
    the volume's boundary falls on the same side of it."""
    import numpy as np
    f32 = np.float32
    vol = np.asarray(vol, np.float64)
    ro = np.asarray(ro, f32)
    rd = np.asarray(rd, f32)
    inv = f32(1.0) / np.where(np.abs(rd) < 1e-9, f32(1e-9), rd)
    t_in = np.maximum(np.max(np.minimum((f32(0.0) - ro) * inv,
                                        (f32(1.0) - ro) * inv), axis=-1),
                      f32(0.0))
    D = vol.shape[0]
    dt = f32(1.7 / n_steps)
    acc = np.zeros(ro.shape[0])
    for k in range(n_steps):
        p = ro + rd * (t_in + f32(k) * dt)[:, None]
        inside = np.all((p >= 0) & (p <= 1), axis=-1)
        g = np.clip(p, 0.0, 1.0) * (D - 1)
        i0 = np.floor(g).astype(np.int64)
        i1 = np.minimum(i0 + 1, D - 1)
        fr = g - i0
        c = np.zeros(ro.shape[0])
        for ix, wx in ((i0, 1 - fr[:, 0]), (i1, fr[:, 0])):
            for iy, wy in ((i0, 1 - fr[:, 1]), (i1, fr[:, 1])):
                for iz, wz in ((i0, 1 - fr[:, 2]), (i1, fr[:, 2])):
                    c += wx * wy * wz * vol[ix[:, 0], iy[:, 1], iz[:, 2]]
        acc += np.where(inside, c, 0.0) * float(dt)
    return acc


def _np_montecarlo(u):
    import numpy as np

    from repro.workloads import montecarlo as mc
    u = np.asarray(u, np.float32)
    w = np.ones(u.shape[0], np.float32)
    absorbed = np.zeros_like(w)
    frac = np.float32(mc.MU_A / (mc.MU_A + mc.MU_S))
    for k in range(mc.N_STEPS):
        dw = w * frac
        absorbed += dw
        w = w - dw
        w = np.where((u[:, k] < 0.9) | (w > 1e-4), w, 0.0).astype(
            np.float32)
    return float(absorbed.astype(np.float64).mean())


def _np_bundle(cams, pts, obs, n_iters, lam=1e-3):
    """Levenberg-Marquardt on the camera parameters in float64, with a
    central-difference Jacobian."""
    import numpy as np
    cams = np.asarray(cams, np.float64)
    pts = np.asarray(pts, np.float64)
    obs = np.asarray(obs, np.float64)

    def proj(c):
        w = c[:, :3]
        z = np.zeros(len(c))
        K = np.stack([np.stack([z, -w[:, 2], w[:, 1]], -1),
                      np.stack([w[:, 2], z, -w[:, 0]], -1),
                      np.stack([-w[:, 1], w[:, 0], z], -1)], -2)
        R = np.eye(3)[None] + K
        X = np.einsum("cij,pj->cpi", R, pts) + c[:, None, 3:]
        return X[..., :2] / np.maximum(X[..., 2:3], 1e-3)

    def res(flat):
        return (proj(flat.reshape(cams.shape)) - obs).reshape(-1)

    c = cams.reshape(-1)
    err = float("inf")
    for _ in range(n_iters):
        r = res(c)
        h = 1e-6
        J = np.stack([(res(c + h * e) - res(c - h * e)) / (2 * h)
                      for e in np.eye(c.size)], 1)
        JtJ = J.T @ J
        A = JtJ + lam * np.diag(np.diag(JtJ))
        c = c - np.linalg.solve(A, J.T @ r)
        err = float(np.sum(r ** 2))
    return err


def table1_cases(sizes):
    """(workload, payload, reference value, checker) for the 13
    Table-1 workloads at ``sizes``."""
    import jax
    import numpy as np

    from repro.core.host_offload import host_prng_stream
    from repro.kernels.bilateral.ref import bilateral_ref
    from repro.kernels.conv2d.ref import conv2d_ref
    from repro.workloads import (bundle, concomp, conv, dither, hist, lbm,
                                 listrank, montecarlo, raycast, spgemm,
                                 spmv)
    from repro.workloads import bilateral as bl

    cpu = jax.devices("cpu")[0]

    def close(tol):
        return lambda out, ref: (rel_err(out, ref), rel_err(out, ref) <= tol)

    def exact(out, ref):
        same = (np.asarray(out).shape == np.asarray(ref).shape
                and np.array_equal(np.asarray(out), np.asarray(ref)))
        return (0.0 if same else float("inf")), same

    def same_components(out, ref):
        same = np.array_equal(_canon_labels(out), _canon_labels(ref))
        return (0.0 if same else float("inf")), same

    def dither_ok(out, ref):
        out, ref = np.asarray(out), np.asarray(ref)
        if out.shape != ref.shape:
            return float("inf"), False
        flipped = float(np.mean(out != ref))
        mean_err = abs(out.mean() - ref.mean()) / max(ref.mean(), 1e-9)
        return flipped, (flipped <= DITHER_MAX_FLIPPED
                         and mean_err <= DITHER_MEAN_TOL)

    def bundle_ok(out, ref):
        e = abs(float(out) - ref) / max(abs(ref), 1e-30)
        return e, bool(np.isfinite(out)) and e <= TOL_BUNDLE

    cases = []
    s = sizes
    x = np.random.default_rng(0).random(s["sort"]["n"]).astype(np.float32)
    cases.append(("sort", s["sort"], np.sort(x), exact))

    hx = np.asarray(hist.make_inputs(s["hist"]["n"], 256, 0))
    cases.append(("hist", s["hist"], np.bincount(hx, minlength=256), exact))

    n = s["spmv"]["n"]
    A = spmv.make_matrix(n, 0.01, 0).astype(np.float64)
    xv = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    cases.append(("spmv", s["spmv"], A @ xv, close(TOL_F32)))

    n = s["spgemm"]["n"]
    A, B = spgemm.make_matrices(n, 0.02, 0)
    cases.append(("spgemm", s["spgemm"],
                  A.astype(np.float64) @ B.astype(np.float64),
                  close(TOL_MATMUL)))

    p = s["raycast"]
    if raycast.N_MARCH_STEPS != RAYCAST_MARCH_STEPS:
        fail("RAYCAST_MARCH_STEPS is not raycast.N_MARCH_STEPS")
    vol = raycast.make_volume(p["d"], 0)
    ro, rd = raycast.make_rays(p["n_rays"], 1)
    # every ray enters the volume exactly at its face, and a sample on
    # the boundary is in or out depending on whether the backend fuses
    # o + d*t into one FMA: a ray may differ by its entry and exit
    # samples, each at most dt * max(vol)
    boundary = 2 * (1.7 / raycast.N_MARCH_STEPS) * float(np.max(vol))

    def raycast_ok(out, ref):
        out = np.asarray(out, np.float64)
        if out.shape != ref.shape or not np.all(np.isfinite(out)):
            return float("inf"), False
        worst = float(np.max(np.abs(out - ref)))
        return worst, worst <= boundary

    cases.append(("raycast", p,
                  _np_raycast(vol, ro, rd, raycast.N_MARCH_STEPS),
                  raycast_ok))

    p = s["bilateral"]
    img = bl.make_inputs(p["size"], 0)
    with jax.default_device(cpu):
        ref = np.asarray(bilateral_ref(jax.device_put(img, cpu), 3.0, 30.0,
                                       7))
    cases.append(("bilateral", p, ref, close(1e-3)))

    p = s["conv"]
    img, w = conv.make_inputs(p["size"], p["ksize"], 0)
    with jax.default_device(cpu):
        ref = np.asarray(conv2d_ref(jax.device_put(img, cpu),
                                    jax.device_put(w, cpu)))
    cases.append(("conv", p, ref, close(TOL_MATMUL)))

    p = s["montecarlo"]
    u = np.asarray(host_prng_stream(42, p["n_photons"] * montecarlo.N_STEPS)
                   ).reshape(p["n_photons"], montecarlo.N_STEPS)
    cases.append(("montecarlo", p, _np_montecarlo(u), close(TOL_F32)))

    succ, head = listrank.make_list(s["listrank"]["n"], 0)
    succ = np.asarray(succ)
    order = [head]
    while succ[order[-1]] != order[-1]:
        order.append(int(succ[order[-1]]))
    rank = np.empty(len(succ), np.int64)
    rank[np.asarray(order)] = np.arange(len(order))[::-1]
    cases.append(("listrank", s["listrank"], rank, exact))

    nv, edges = concomp.make_graph(s["concomp"]["n"], 4.0, 0)
    cases.append(("concomp", s["concomp"],
                  concomp.bfs_components_np(nv, edges), same_components))

    p = s["lbm"]
    cases.append(("lbm", p, _np_lbm(lbm.init_state(p["d"], 0),
                                    p["n_steps"]), close(TOL_MATMUL)))

    p = s["dither"]
    cases.append(("dither", p,
                  _np_dither(dither.make_image(p["h"], p["w"], 0)),
                  dither_ok))

    p = s["bundle"]
    cams, pts, obs = bundle.make_problem(p["n_cams"], p["n_pts"], 0)
    cases.append(("bundle", p, _np_bundle(cams, pts, obs, 3), bundle_ok))
    return cases


def table1_phase(groups) -> int:
    from benchmarks.table2_hybrid import SIZES
    from repro.core.hybrid_executor import HybridExecutor
    from repro.serve.scheduler import Scheduler

    log("# === Table-1 phase: 13 workloads, references on the host CPU ===")
    t0 = time.perf_counter()
    cases = table1_cases(SIZES)
    log(f"# references built in {time.perf_counter() - t0:.1f}s")
    n_fail = 0
    lanes_used = set()
    # the slowest request per lane, against the scheduler's default
    # watchdog: each scheduler below is new, so a workload's first
    # request on a lane includes its compiles and autotune search
    slowest = {}

    def check(tag, wl, out, ref, checker, lane, seconds):
        nonlocal n_fail
        err, ok = checker(out, ref)
        plat = result_platform(out)
        lanes_used.add(lane)
        if seconds > slowest.get(lane, ("", 0.0))[1]:
            slowest[lane] = (f"{tag} {wl}", seconds)
        n_fail += 0 if ok else 1
        log(f"table1 {tag:<8} {wl:<10} lane={lane:<6} "
            f"result_device={plat:<10} err={err:.3g} "
            f"{seconds:.2f}s {'ok' if ok else 'FAIL'}")

    def serve(sched, wl, payload):
        t = time.perf_counter()
        fut = sched.submit(wl, dict(payload))
        out = fut.result(timeout=600)
        return fut, out, time.perf_counter() - t

    # placed by the cost model
    sched = Scheduler(groups=groups)
    watchdog_s = sched.exec_timeout_s
    try:
        for wl, payload, ref, checker in cases:
            fut, out, dt = serve(sched, wl, payload)
            place = fut.meta.get("placement", {})
            lanes = "+".join(place.get("groups", [])) or "?"
            check("placed", wl, out, ref, checker, lanes, dt)
            log(f"#   {wl}: {place.get('kind', '?')}")
    finally:
        sched.shutdown()

    # pinned: a FIFO scheduler serves every request on one lane
    for g in groups:
        sched = Scheduler(groups=groups, policy="fifo", fifo_group=g.name)
        try:
            for wl, payload, ref, checker in cases:
                _, out, dt = serve(sched, wl, payload)
                check("pinned", wl, out, ref, checker, g.name, dt)
        finally:
            sched.shutdown()

    # the paper's §5.4.3 split of one conv request across both lanes
    from repro.workloads import requests as adapters
    conv_case = next(c for c in cases if c[0] == "conv")
    spec = adapters.make_request("conv", dict(conv_case[1]))
    H = spec.total_units
    ws = HybridExecutor(groups=groups).run_work_shared(
        spec.workload + "@split", H, spec.run_share, spec.combine,
        plan_override=[H // 2, H - H // 2])
    busy = {g: u for g, u in ws.trace.group_units.items() if u > 0}
    spans = sorted(busy)
    err, ok = conv_case[3](ws.value, conv_case[2])
    ok = ok and spans == sorted(g.name for g in groups)
    n_fail += 0 if ok else 1
    log(f"table1 split    conv       lanes={'+'.join(spans)} "
        f"units={busy} mode={ws.result.mode} err={err:.3g} "
        f"{'ok' if ok else 'FAIL'}")
    for lane, (what, seconds) in sorted(slowest.items()):
        log(f"table1 slowest request on lane {lane}: {what} "
            f"{seconds:.2f}s (watchdog floor {watchdog_s:g}s)")
    missing = {g.name for g in groups} - lanes_used
    if missing:
        log(f"table1: no request ran on lane(s) {sorted(missing)}")
        n_fail += 1
    return n_fail


# ---------------------------------------------------------------------------
# Kernel phase: each Pallas kernel compiled for the TPU, vs its ref.py
# ---------------------------------------------------------------------------
def kernel_phase() -> int:
    import jax
    import numpy as np

    from repro.core.host_offload import bilateral_luts
    from repro.kernels.bilateral.bilateral import bilateral_pallas
    from repro.kernels.bilateral.ref import bilateral_ref
    from repro.kernels.conv2d.conv2d import conv2d_pallas
    from repro.kernels.conv2d.ref import conv2d_ref
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_pallas
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.gmm.gmm import gmm_pallas
    from repro.kernels.gmm.ref import gmm_ref
    from repro.kernels.hist.hist import hist_pallas
    from repro.kernels.hist.ref import hist_ref
    from repro.kernels.sort_bitonic.ref import sort_rows_ref
    from repro.kernels.sort_bitonic.sort_bitonic import sort_rows_pallas
    from repro.kernels.spmv.ref import spmv_ell_ref
    from repro.kernels.spmv.spmv import spmv_ell_pallas

    log("# === kernel phase: 7 Pallas kernels compiled for the TPU ===")
    cpu = jax.devices("cpu")[0]
    rng = np.random.default_rng(0)
    f32 = np.float32
    img = (rng.random((768, 768)) * 255).astype(f32)
    sp, rl = bilateral_luts(3.0, 30.0, 7)
    bimg = (rng.random((256, 256)) * 255).astype(f32)
    R, K, C = 4096, 32, 4096
    cases = [
        ("conv2d", lambda a, w: conv2d_pallas(a, w, row_tile=64,
                                              interpret=False),
         (img, rng.standard_normal((15, 15)).astype(f32)),
         conv2d_ref, TOL_F32),
        ("hist", lambda x: hist_pallas(x, 256, tile=2048, interpret=False),
         (rng.integers(0, 256, 1 << 21).astype(np.int32),),
         lambda x: hist_ref(x, 256), 0.0),
        ("spmv", lambda v, i, x: spmv_ell_pallas(v, i, x, row_tile=256,
                                                 interpret=False),
         (rng.standard_normal((R, K)).astype(f32),
          rng.integers(0, C, (R, K)).astype(np.int32),
          rng.standard_normal(C).astype(f32)),
         spmv_ell_ref, TOL_F32),
        ("sort_bitonic", lambda x: sort_rows_pallas(x, row_tile=256,
                                                    interpret=False),
         (rng.standard_normal((256, 256)).astype(f32),),
         sort_rows_ref, 0.0),
        ("bilateral", lambda a, s_, r_: bilateral_pallas(
            a, s_, r_, row_tile=64, interpret=False),
         (bimg, sp, rl),
         lambda a, s_, r_: bilateral_ref(a, 3.0, 30.0, 7), 1e-3),
        ("flash_attention", lambda q, k, v: flash_attention_pallas(
            q, k, v, causal=True, block_q=256, block_k=256,
            interpret=False),
         tuple(rng.standard_normal((16, 512, 64)).astype(f32)
               for _ in range(3)),
         lambda q, k, v: attention_ref(q, k, v, causal=True), TOL_MATMUL),
        ("gmm", lambda x, w: gmm_pallas(x, w, interpret=False),
         (rng.standard_normal((8, 256, 512)).astype(f32),
          rng.standard_normal((8, 512, 512)).astype(f32)),
         gmm_ref, TOL_MATMUL),
    ]
    n_fail = 0
    tpu = jax.devices()[0]
    for name, fn, args, ref_fn, tol in cases:
        t = time.perf_counter()
        try:
            compiled = jax.jit(fn).lower(
                *[jax.device_put(a, tpu) for a in args]).compile()
            kernel = "tpu_custom_call" in compiled.as_text()
            out = compiled(*[jax.device_put(a, tpu) for a in args])
            out = np.asarray(jax.block_until_ready(out))
        except Exception as e:                    # noqa: BLE001
            first = (str(e).strip().splitlines() or [""])[0]
            log(f"kernel {name:<16} FAIL compile/run: "
                f"{type(e).__name__}: {first}")
            n_fail += 1
            continue
        with jax.default_device(cpu), jax.default_matmul_precision(
                "highest"):
            ref = np.asarray(ref_fn(*[jax.device_put(a, cpu)
                                      for a in args]))
        err = rel_err(out, ref)
        ok = kernel and err <= tol
        n_fail += 0 if ok else 1
        log(f"kernel {name:<16} tpu_custom_call={kernel} err={err:.3g} "
            f"tol={tol:g} {time.perf_counter() - t:.1f}s "
            f"{'ok' if ok else 'FAIL'}")
    return n_fail


# ---------------------------------------------------------------------------
# LM phase: xlstm-350m at its published widths through the engine
# ---------------------------------------------------------------------------
def lm_phase(groups) -> int:
    import jax
    import numpy as np

    from repro.configs import registry
    from repro.models import model_zoo, param

    log(f"# === LM phase: {LM_ARCH} (published widths), {LM_REQUESTS} "
        f"requests, prompt {LM_PROMPT}, {LM_NEW} new tokens ===")
    cfg = registry.get(LM_ARCH)
    t = time.perf_counter()
    params = jax.jit(lambda k: param.values(model_zoo.init(cfg, k)))(
        jax.random.key(0))
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    n_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    log(f"# params: {n_params / 1e6:.1f}M ({n_bytes / 2**30:.2f} GiB), "
        f"d_model={cfg.d_model} layers={cfg.n_layers} "
        f"vocab={cfg.vocab_size}, init {time.perf_counter() - t:.1f}s")
    # served at "highest" precision (see TOL_LOGITS); the lane threads
    # read the global setting, not this thread's context
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        return _serve_and_check_lm(cfg, params, groups)
    finally:
        jax.config.update("jax_default_matmul_precision", None)


def _plain_slot_decode(stepper, specs, pre_dev, dec_dev):
    """Greedy tokens of ``specs`` by the engine's own stepper driven in
    a plain loop, no scheduler and no engine: prefill on the prefill
    lane's device, every request in its own slot from the first step,
    slot steps on the decode lane's device."""
    import jax
    out = []
    for k in range(0, len(specs), stepper.n_slots):
        group = specs[k:k + stepper.n_slots]
        with jax.default_device(pre_dev):
            rows = [stepper.prefill(sp)[0] for sp in group]
        with jax.default_device(dec_dev):
            state = stepper.init_slots()
            for slot, (row_state, _, _) in enumerate(rows):
                state = stepper.insert(state, slot, row_state)
            steps = []
            for _ in range(stepper.new_tokens):
                state, toks = stepper.step(state)
                steps.append(toks)
        out += [stepper.finish(None, slot, first, [t[slot] for t in steps])
                for slot, (_, first, _) in enumerate(rows)]
    return out


def _serve_and_check_lm(cfg, params, groups) -> int:
    import jax
    import numpy as np

    from repro.models import model_zoo
    from repro.serve.scheduler import Scheduler
    from repro.workloads import requests as adapters

    wl = adapters.make_continuous_lm_adapter(
        cfg, params, prompt_len=LM_PROMPT, new_tokens=LM_NEW,
        warm_background=False)
    sched = Scheduler(groups=groups)
    seeds = list(range(1, LM_REQUESTS + 1))
    try:
        t_sub, futs = {}, {}
        for s in seeds:
            t_sub[s] = sched.clock()
            futs[s] = sched.submit(wl, {"batch": 1, "seed": s})
        outs = {s: np.asarray(f.result(timeout=900))
                for s, f in futs.items()}
        plan = sched.engine_placements[wl]
    finally:
        sched.shutdown()
    by_name = {g.name: g for g in groups}
    pre_dev = by_name[plan.prefill_group].device
    dec_dev = by_name[plan.decode_group].device
    log(f"lm lanes: prefill={plan.prefill_group} "
        f"({pre_dev.platform}) decode={plan.decode_group} "
        f"({dec_dev.platform}), matmul precision=highest")

    specs = [adapters.make_request(wl, {"batch": 1, "seed": s})
             for s in seeds]
    t = time.perf_counter()
    plain = dict(zip(seeds, _plain_slot_decode(specs[0].stepper, specs,
                                               pre_dev, dec_dev)))
    log(f"# plain slot decode of {len(seeds)} requests in "
        f"{time.perf_counter() - t:.1f}s")

    L = LM_PROMPT + LM_NEW + 1
    prefill_logits = jax.jit(lambda p, tok: model_zoo.prefill(
        cfg, p, {"tokens": tok}, cache_len=L)[0][:, -1])
    forward_logits = jax.jit(lambda p, tok: model_zoo.forward(
        cfg, p, {"tokens": tok})[0][:, -1])
    lane_params = jax.device_put(params, pre_dev)
    n_fail = 0
    for s, spec in zip(seeds, specs):
        prompt = spec.arrays[0]
        tok = outs[s]
        # every served token is the plain loop's: the engine's join,
        # slot and lane bookkeeping changed nothing
        same = tok.shape == plain[s].shape and bool(
            np.array_equal(tok, plain[s]))
        n_same = (int(np.sum(tok == plain[s])) if tok.shape ==
                  plain[s].shape else 0)
        fwd = np.asarray(forward_logits(params, prompt), np.float64)[0]
        with jax.default_device(pre_dev):
            p_dev = jax.device_put(prompt, pre_dev)
            pre = np.asarray(prefill_logits(lane_params, p_dev),
                             np.float64)[0]
            with jax.default_matmul_precision("default"):
                pre_default = np.asarray(
                    prefill_logits(lane_params, p_dev), np.float64)[0]
        err = rel_err(pre, fwd)
        # the first token is the forward's argmax, up to the tolerance
        first = int(tok[0, 0])
        margin = (fwd.max() - fwd[first]) / np.max(np.abs(fwd))
        meta = futs[s].meta
        ttft = meta.get("t_first_token", float("nan")) - t_sub[s]
        tbt = ((meta.get("t_last_token", float("nan"))
                - meta.get("t_first_token", float("nan"))) / LM_NEW)
        ok = same and err <= TOL_LOGITS and margin <= TOL_LOGITS
        n_fail += 0 if ok else 1
        log(f"lm request seed={s} ttft={ttft * 1e3:.1f}ms "
            f"tbt={tbt * 1e3:.2f}ms served_tokens={tok.size} "
            f"equal_to_plain_decode={n_same} prefill_fn_err={err:.3g} "
            f"first_token_margin={margin:.3g} (tol {TOL_LOGITS:g}) "
            f"prefill_fn_default_precision_err="
            f"{rel_err(pre_default, fwd):.3g} "
            f"{'ok' if ok else 'FAIL'}")
    return n_fail


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def one_chip() -> dict:
    import jax

    from repro.core import compile_cache
    cache_dir = compile_cache.enable()
    stats = {"hits": 0, "misses": 0, "compiles": 0, "compile_s": 0.0}

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            stats["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            stats["misses"] += 1

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            stats["compiles"] += 1
            stats["compile_s"] += duration

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {devs[0].platform} devices only")
    from repro.core.hybrid_executor import detect_platform
    groups, simulated = detect_platform()
    log(f"jax {jax.__version__} device_kind={devs[0].device_kind!r} "
        f"count={len(devs)}")
    for g in groups:
        log(f"lane {g.name} -> {g.device} ({g.device.platform}, "
            f"{g.device.device_kind})")
    log(f"compile cache: {cache_dir}")
    if simulated or [g.device.platform for g in groups] != ["tpu", "cpu"]:
        fail("lanes are not the TPU and the host CPU")

    t0 = time.perf_counter()
    n_fail = table1_phase(groups)
    log(f"# Table-1 phase {time.perf_counter() - t0:.1f}s, "
        f"{n_fail} failure(s)")
    t1 = time.perf_counter()
    n_k = kernel_phase()
    log(f"# kernel phase {time.perf_counter() - t1:.1f}s, "
        f"{n_k} failure(s)")
    t2 = time.perf_counter()
    n_lm = lm_phase(groups)
    log(f"# LM phase {time.perf_counter() - t2:.1f}s, {n_lm} failure(s)")
    log(f"compile cache: dir={cache_dir} hits={stats['hits']} "
        f"misses={stats['misses']} backend_compiles={stats['compiles']} "
        f"compile_s={stats['compile_s']:.1f}")
    if n_fail or n_k or n_lm:
        fail(f"{n_fail} Table-1, {n_k} kernel, {n_lm} LM check(s) failed")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# four chips: this process stays off JAX; workers own one chip each
# ---------------------------------------------------------------------------
_PROBE = r"""
import json, jax
from repro.serve.transport import device_report
d = jax.devices()
print("DEVICES" + json.dumps({**device_report(), "count": len(d),
                              "coords": [list(getattr(x, "coords", ()))
                                         for x in d]}))
"""


def _worker_identity(dev: dict) -> tuple:
    """What tells one worker's chip from another's: JAX's id and coords
    (each worker may number its one chip 0) and the chip's device file."""
    return (dev.get("id"), tuple(dev.get("coords") or ()),
            tuple(dev.get("files") or ()))


def fleet_requests():
    """The fixed request list: the Table-1 mix at the table2 sizes, two
    seeds each (so keys spread over the ring), plus reduced-width LM
    requests."""
    from benchmarks.table2_hybrid import SIZES    # imports no JAX
    reqs = []
    for wl, p in SIZES.items():
        for seed in (0, 1):
            reqs.append((wl, {**p, "seed": seed}))
    lm = f"serve-lm-cb/{LM_ARCH}"
    reqs += [(lm, {"batch": 1, "seed": s}) for s in range(1, 5)]
    return reqs


def _fleet_close(wl: str, a, b) -> tuple:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return float("inf"), False
    if wl.startswith("serve-lm") or a.dtype.kind in "iub":
        same = np.array_equal(a, b)
        if wl == "concomp":
            same = np.array_equal(_canon_labels(a), _canon_labels(b))
        return (0.0 if same else float("inf")), same
    if wl == "dither":
        flipped = float(np.mean(a != b))
        return flipped, flipped <= DITHER_MAX_FLIPPED
    if wl == "raycast":
        # the two runs may place a request on different lanes, and the
        # lanes may differ by a ray's entry and exit samples (see
        # table1_cases); the volume's values lie in [0, 1)
        worst = float(np.max(np.abs(a - b)))
        return worst, worst <= 2 * (1.7 / RAYCAST_MARCH_STEPS)
    tol = {"bundle": TOL_BUNDLE, "spgemm": TOL_MATMUL, "conv": TOL_MATMUL,
           "lbm": TOL_MATMUL, "bilateral": 1e-3}.get(wl, TOL_F32)
    err = rel_err(a, b)
    return err, err <= tol


def _serve(router, reqs, timeout=900.0):
    futs = [router.submit(wl, p) for wl, p in reqs]
    return [f.result(timeout=timeout) for f in futs]


def four_chips() -> dict:
    from repro.serve.router import Router
    from repro.serve.transport import ProcWorker, chip_env

    if "jax" in sys.modules:
        fail("the fleet's parent imported JAX and may hold a chip")
    res = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                         text=True, timeout=300)
    line = [ln for ln in res.stdout.splitlines() if ln.startswith("DEVICES")]
    if res.returncode != 0 or not line:
        fail(f"device probe failed:\n{res.stdout}\n{res.stderr}")
    dev = json.loads(line[-1][len("DEVICES"):])
    log(f"devices: {dev}")
    if dev["platform"] != "tpu" or dev["count"] < FLEET_CHIPS:
        fail(f"--four-chips needs {FLEET_CHIPS} TPU chips, found {dev}")

    from repro.core.compile_cache import DEFAULT_DIR, ENV
    cache_dir = os.environ.get(ENV) or DEFAULT_DIR
    log(f"compile cache: {cache_dir}")
    reqs = fleet_requests()

    def fleet(k):
        workers = [ProcWorker(f"w{i}", env=chip_env(i),
                              lm=LM_ARCH, hb_interval_s=0.5)
                   for i in range(k)]
        return Router(workers, hb_timeout_s=120.0).start()

    def run(k):
        t = time.perf_counter()
        router = fleet(k)
        try:
            outs = _serve(router, reqs)
            stats = router.refresh_stats(timeout=30.0)
            devices = router.worker_devices()
        finally:
            router.shutdown(timeout=120)
        log(f"# {k} worker(s), one chip each: {len(outs)} requests in "
            f"{time.perf_counter() - t:.1f}s")
        return outs, stats, devices

    ref, _, ref_devices = run(1)
    outs, stats, devices = run(FLEET_CHIPS)
    n_fail = 0
    # every worker reports the device its lanes run on; chip_env names
    # the platforms, so a worker that could not open its chip exits
    # instead of serving from the CPU
    for name, d in sorted({**{"one/" + n: d for n, d in
                              ref_devices.items()},
                           **devices}.items()):
        ok = d.get("platform") == "tpu"
        n_fail += 0 if ok else 1
        log(f"fleet worker {name}: device={json.dumps(d)} "
            f"{'ok' if ok else 'FAIL'}")
    ids = {_worker_identity(d) for d in devices.values()}
    if len(ids) != FLEET_CHIPS:
        n_fail += 1
        log(f"fleet: {len(ids)} distinct chips among {len(devices)} "
            f"workers: FAIL")
    for (wl, p), a, b in zip(reqs, outs, ref):
        err, ok = _fleet_close(wl, a, b)
        n_fail += 0 if ok else 1
        log(f"fleet {wl:<22} seed={p.get('seed')} err={err:.3g} "
            f"{'ok' if ok else 'FAIL'}")
    for name in sorted(stats):
        served = stats[name].get("completed", 0)
        log(f"fleet worker {name}: completed={served:.0f}")
        if served <= 0:
            n_fail += 1
            log(f"fleet worker {name} served no traffic: FAIL")
    if len(stats) != FLEET_CHIPS:
        n_fail += 1
    if "jax" in sys.modules:
        n_fail += 1
        log("fleet parent imported JAX: FAIL")
    if n_fail:
        fail(f"{n_fail} fleet check(s) failed")
    return {"platform": dev["platform"], "kind": dev["kind"],
            "count": dev["count"]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet over four chips and the "
                         "one-worker run it is compared with")
    args = ap.parse_args()
    _setup_env()
    device = four_chips() if args.four_chips else one_chip()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
