"""Jitted public wrapper for conv2d, with autotuned configs.

``conv2d(img, w)`` resolves the best (impl, row_tile, col_tile) for this
backend and shape bucket via kernels/autotune.py; pass ``config=`` to
pin one, ``use_kernel=False`` for the XLA-conv oracle path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.core.cost_model import CostTerms
from repro.kernels.autotune import (Config, autotune, bucket,
                                    cached_or_default, default_config,
                                    freeze, is_tracer)
from repro.core.device import platform
from repro.kernels.conv2d.conv2d import (conv2d_pallas, conv2d_shift_add,
                                         window_shape)
from repro.kernels.conv2d.ref import conv2d_ref

# Seed constants (PR 1): 1-D row tiling, whole image resident.
SEED_CONFIG: Config = {"impl": "pallas", "row_tile": 64, "col_tile": 0}
# Default when search is disabled: the oracle path (safe everywhere).
DEFAULT_CONFIG: Config = {"impl": "xla_conv", "row_tile": 64, "col_tile": 0}


# TPU v5e: a halo window over 1 MiB makes the kernel's shifted
# temporaries overflow the 16 MiB of scoped VMEM (the compiler refuses
# 512x512 and 512xfull-width tiles of a 768^2, K=15 image).
TPU_WINDOW_BYTES = 1 << 20


def candidates(H: int, W: int, K: int):
    """Per-shape config space: XLA variants + 2-D Pallas tilings.

    On the TPU, XLA's own convolution is left out: the TPU compiler
    did not finish a single-channel 15x15 convolution of a 768^2 image
    in 15 minutes.  Pallas tilings whose halo window exceeds
    ``TPU_WINDOW_BYTES`` are left out there too."""
    tpu = platform() == "tpu"
    cands = [] if tpu else [{"impl": "xla_conv"}]
    cands.append({"impl": "xla_shift"})
    for rt in (64, 128, 256, 512):
        if rt > max(H, 64) * 2:
            continue
        for ct in (0, 128, 256, 512):
            if ct and ct > max(W, 128) * 2:
                continue
            win = window_shape(H, W, K, rt, ct)
            if tpu and 4 * win[0] * win[1] > TPU_WINDOW_BYTES:
                continue
            cands.append({"impl": "pallas", "row_tile": rt, "col_tile": ct})
    return cands


@functools.partial(jax.jit, static_argnames=("cfg",))
def _conv2d_cfg(img, w, cfg):
    c = dict(cfg)
    impl = c.get("impl", "pallas")
    if impl == "xla_conv":
        return conv2d_ref(img, w)
    if impl == "xla_shift":
        return conv2d_shift_add(img, w)
    return conv2d_pallas(img, w, row_tile=int(c.get("row_tile", 64)),
                         col_tile=int(c.get("col_tile", 0)))


def shape_bucket(H: int, W: int, K: int) -> str:
    return f"H{bucket(H)}_W{bucket(W)}_K{K}"


def cost_terms(cfg: Config, H: int, W: int, K: int) -> CostTerms:
    """Analytic work of one candidate (ranks the autotune search)."""
    flops = 2.0 * H * W * K * K
    impl = cfg.get("impl", "pallas")
    if impl == "xla_conv":
        return CostTerms(flops=flops, bytes=4.0 * (2 * H * W + K * K))
    if impl == "xla_shift":
        # K^2 shifted multiply-accumulates, each streaming the image
        return CostTerms(flops=flops, bytes=4.0 * 2 * H * W * K * K,
                         steps=K * K)
    rt = max(int(cfg.get("row_tile", 64)), 1)
    ct = int(cfg.get("col_tile", 0)) or W
    tiles = -(-H // rt) * (-(-W // ct))
    halo = (rt + K - 1) * (ct + K - 1)                 # per-tile read
    from repro.kernels.common import default_interpret
    return CostTerms(flops=2.0 * tiles * rt * ct * K * K,
                     bytes=4.0 * tiles * (halo + rt * ct),
                     steps=tiles,
                     interpret_steps=tiles if default_interpret() else 0)


def tuned_config(img, w) -> Config:
    """Resolve (searching at most once per backend/shape bucket) the
    tuned config for this input — callable outside the timed path.
    Under jit tracing this degrades to a cache-hit-or-default lookup
    (timing tracers is meaningless)."""
    H, W = img.shape
    K = w.shape[0]
    default = default_config(SEED_CONFIG, DEFAULT_CONFIG)
    if is_tracer(img) or is_tracer(w):
        return cached_or_default("conv2d", shape_bucket(H, W, K), default)
    return autotune(
        "conv2d", shape_bucket(H, W, K), candidates(H, W, K),
        lambda cfg: lambda: _conv2d_cfg(img, w, freeze(cfg)),
        default,
        cost_fn=lambda cfg: cost_terms(cfg, H, W, K))


@jax.jit
def conv2d_batched(imgs, ws):
    """Batched 'same' 2-D correlation: ``(R, H, W)`` images against
    ``(R, K, K)`` per-row kernels -> ``(R, H, W)``, one vmapped
    XLA-conv call for the whole stack.

    The serving merge hook stacks same-bucket conv requests into this
    single launch.  Pinned to the ``xla_conv`` impl because vmap of
    ``conv2d_ref`` is bit-identical per row to the solo xla_conv path
    (measured; the shift-add and Pallas impls reassociate under vmap
    and are NOT) — the merge hook therefore only engages when the solo
    path resolves to xla_conv, keeping merged == solo exact."""
    return jax.vmap(conv2d_ref)(imgs, ws)


def conv2d(img, w, *, use_kernel: bool = True,
           config: Optional[Config] = None,
           row_tile: Optional[int] = None):
    """'same' 2-D correlation with an autotuned implementation.

    config=None -> autotuned; explicit ``row_tile`` forces the Pallas
    path with that tiling (legacy API)."""
    if not use_kernel:
        return _conv2d_cfg(img, w, freeze({"impl": "xla_conv"}))
    if config is None:
        if row_tile is not None:
            config = {"impl": "pallas", "row_tile": row_tile, "col_tile": 0}
        else:
            config = tuned_config(img, w)
    return _conv2d_cfg(img, w, freeze(config))
