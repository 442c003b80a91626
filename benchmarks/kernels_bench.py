"""Per-kernel microbenchmarks: autotuned path vs the seed baseline.

Each row times the kernel's *autotuned* implementation (the config the
per-backend tune cache picked for this shape bucket — see
src/repro/kernels/autotune.py) and reports, in the derived column, the
winning config plus the speedup over the seed baseline (the path the
seed benchmark measured: the XLA reference formulations, which on this
CPU container are also what the pre-autotune workloads executed).

Config resolution happens *before* timing: the first ``--json`` run
pays the search and writes the cache file; the second run is a pure
cache hit, so the timed path never contains a search.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _t(fn, iters=7):
    """us per call, min-of-N: the trajectory gate (regress.py) compares
    runs across sessions on a noisy shared box, and the minimum is the
    stable estimator of a kernel's achievable time (mean-of-5 showed
    ~25-30% run-to-run swing here, tripping the 20%% gate on noise).
    Sub-millisecond kernels get more reps — per-call dispatch jitter is
    tens of us, a huge relative error at that scale."""
    fn()
    fn()
    best = float("inf")
    done = 0
    while done < iters:
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
        done += 1
        if done == iters and best < 1e-3 and iters < 50:
            iters = 50
    return best * 1e6


def _fmt_cfg(cfg: dict) -> str:
    return ";".join(f"{k}={v}" for k, v in sorted(cfg.items()))


def _row(name: str, tuned_us: float, seed_us: float, cfg: dict,
         extra: str) -> None:
    speed = seed_us / max(tuned_us, 1e-9)
    print(f"kernels/{name},{tuned_us:.0f},{extra}|cfg={_fmt_cfg(cfg)}"
          f"|seed_us={seed_us:.0f}|vs_seed={speed:.2f}x")


def run():
    # ----------------------------------------------------------- hist
    from repro.kernels.hist import ops as hist_ops
    from repro.kernels.hist.ref import hist_ref
    x = jnp.asarray(np.random.default_rng(0).integers(0, 256, 1 << 20,
                                                      dtype=np.int32))
    cfg = hist_ops.tuned_config(x, 256)
    seed = _t(lambda: hist_ref(x, 256).block_until_ready())
    tuned = _t(lambda: hist_ops.histogram(x, 256, config=cfg)
               .block_until_ready())
    _row("hist_1M", tuned, seed, cfg, "bins=256")

    # ------------------------------------------------ flash attention
    from repro.kernels.flash_attention import ops as attn_ops
    q = jax.random.normal(jax.random.key(0), (1, 1024, 8, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (1, 1024, 2, 64), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (1, 1024, 2, 64), jnp.bfloat16)
    cfg = attn_ops.tuned_config(q, k, v)
    seed = _t(lambda: attn_ops.flash_attention(q, k, v, use_kernel=False)
              .block_until_ready())
    tuned = _t(lambda: attn_ops.flash_attention(q, k, v, config=cfg)
               .block_until_ready())
    _row("attn_1k", tuned, seed, cfg, "B1_T1024_H8_GQA")

    # ------------------------------------------------------------ gmm
    from repro.kernels.gmm import ops as gmm_ops
    from repro.kernels.gmm.ref import gmm_ref
    xe = jax.random.normal(jax.random.key(3), (8, 256, 256), jnp.bfloat16)
    we = jax.random.normal(jax.random.key(4), (8, 256, 512), jnp.bfloat16)
    cfg = gmm_ops.tuned_config(xe, we)
    seed = _t(lambda: gmm_ref(xe, we).block_until_ready())
    tuned = _t(lambda: gmm_ops.gmm(xe, we, config=cfg).block_until_ready())
    _row("gmm_8x256", tuned, seed, cfg, "E8_C256_D256_F512")

    # ----------------------------------------------------------- conv
    from repro.kernels.conv2d import ops as conv_ops
    from repro.kernels.conv2d.ref import conv2d_ref
    img = jax.random.normal(jax.random.key(5), (512, 512))
    w = jax.random.normal(jax.random.key(6), (15, 15))
    cfg = conv_ops.tuned_config(img, w)
    seed = _t(lambda: conv2d_ref(img, w).block_until_ready())
    tuned = _t(lambda: conv_ops.conv2d(img, w, config=cfg)
               .block_until_ready())
    _row("conv_512", tuned, seed, cfg, "15x15")

    # ----------------------------------------------------------- sort
    from repro.kernels.sort_bitonic import ops as sort_ops
    from repro.kernels.sort_bitonic.ref import sort_rows_ref
    s = jax.random.normal(jax.random.key(10), (256, 1024))
    cfg = sort_ops.tuned_config(s)
    seed = _t(lambda: sort_rows_ref(s).block_until_ready())
    tuned = _t(lambda: sort_ops.sort_rows(s, config=cfg)
               .block_until_ready())
    _row("sort_256x1k", tuned, seed, cfg, "rows")


if __name__ == "__main__":
    run()
