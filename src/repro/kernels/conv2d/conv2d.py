"""Tiled 2-D convolution Pallas kernel (paper §4.6 Conv, TPU adaptation).

Each grid step computes one (row_tile, col_tile) output tile from its
own halo-expanded input window: the image BlockSpec uses element
indexing (``pl.Element``) so step (i, j) receives the
(row_tile + K - 1, col_tile + K - 1) window it needs, rounded up to the
(8, 128) tiling — the K x K filter
sweep is a shifted multiply-add on the VPU, and VMEM holds one window
per step instead of the whole padded image (the pre-autotune version
kept the full image resident, capping images at ~2k x 2k f32 per core).

Tunable knobs (searched by kernels/autotune.py): row_tile, col_tile
(col_tile=0 -> full width, the 1-D tiling of the seed).

``conv2d_shift_add`` is the same shifted multiply-add as a plain XLA
program — the tuned CPU winner (XLA's own conv lowering loses badly on
large filters), and the candidate the autotuner weighs against the
Pallas tilings per backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def tile_shape(H: int, W: int, row_tile: int, col_tile: int):
    """The output tile: rows a multiple of 8, columns a multiple of 128
    (``col_tile=0``: the full width), neither larger than the image
    rounded up to that tiling.  The halo window's element offsets are
    multiples of the tile, and the TPU compiler must prove them aligned
    even where one tile covers the whole image (a row share of 391
    rows is refused as a 391-row tile)."""
    rows = min(_round_up(row_tile, 8), _round_up(H, 8))
    full = _round_up(W, 128)
    cols = full if col_tile <= 0 else min(_round_up(col_tile, 128), full)
    return rows, cols


def window_shape(H: int, W: int, K: int, row_tile: int, col_tile: int):
    """The (rows, cols) halo window one grid step holds in VMEM: the
    output tile plus the K-1 halo, rounded up to the (8, 128) f32
    tiling."""
    rows, cols = tile_shape(H, W, row_tile, col_tile)
    return (_round_up(rows + K - 1, 8), _round_up(cols + K - 1, 128))


def _conv_kernel(img_ref, w_ref, o_ref, *, K: int, row_tile: int,
                 col_tile: int):
    # img_ref: the (8, 128)-aligned halo window; w_ref: (K, K) in SMEM.
    # The filter row di is a rolled loop (an unrolled K*K sweep keeps
    # every shifted temporary live and overflows VMEM at K=15); row di
    # of the window is brought to the top by a sublane rotation, since
    # a load at an offset that is not a multiple of 8 does not lower
    win = img_ref[...]
    n = win.shape[0]

    def row(di, acc):
        rows = pltpu.roll(win, (n - di) % n, 0)[:row_tile]
        for dj in range(K):
            acc += w_ref[di, dj] * rows[:, dj:dj + col_tile]
        return acc

    acc = jax.lax.fori_loop(0, K, row,
                            jnp.zeros((row_tile, col_tile), jnp.float32))
    o_ref[...] = acc.astype(o_ref.dtype)


def conv2d_pallas(img: jnp.ndarray, w: jnp.ndarray, *, row_tile: int = 64,
                  col_tile: int = 0, interpret: bool | None = None
                  ) -> jnp.ndarray:
    """'same' 2-D correlation. img: (H, W) f32; w: (K, K), odd K."""
    interpret = resolve_interpret(interpret)
    H, W = img.shape
    K = w.shape[0]
    r = K // 2
    # the halo window is rounded up to the TPU's (8, 128) f32 tiling;
    # the extra rows/columns are zero padding the kernel never reads
    win_h, win_w = window_shape(H, W, K, row_tile, col_tile)
    row_tile, col_tile = tile_shape(H, W, row_tile, col_tile)
    pad_h = (-H) % row_tile
    pad_w = (-W) % col_tile
    padded = jnp.pad(img, ((r, win_h - row_tile - r + pad_h),
                           (r, win_w - col_tile - r + pad_w)))
    grid = ((H + pad_h) // row_tile, (W + pad_w) // col_tile)
    out = pl.pallas_call(
        functools.partial(_conv_kernel, K=K, row_tile=row_tile,
                          col_tile=col_tile),
        grid=grid,
        in_specs=[
            # halo window per step: element offsets stride by the output
            # tile while the block extends past it on both axes
            pl.BlockSpec((pl.Element(win_h), pl.Element(win_w)),
                         lambda i, j: (i * row_tile, j * col_tile)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((row_tile, col_tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((H + pad_h, W + pad_w), img.dtype),
        interpret=interpret,
    )(padded, w)
    return out[:H, :W]


def conv2d_shift_add(img: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """XLA shifted multiply-add variant (no Pallas): K*K fused
    vector FMAs over the full image."""
    H, W = img.shape
    K = w.shape[0]
    r = K // 2
    padded = jnp.pad(img, ((r, r), (r, r)))
    acc = jnp.zeros((H, W), jnp.float32)
    for di in range(K):
        for dj in range(K):
            acc = acc + w[di, dj] * jax.lax.dynamic_slice(
                padded, (di, dj), (H, W))
    return acc.astype(img.dtype)
