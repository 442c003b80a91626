"""LUT-based bilateral filter Pallas kernel (paper §4.6 Bilat).

The paper's key task-parallel insight: only (2r+1)^2 spatial weights and
256 range weights ever need transcendental evaluation — precompute both
LUTs on the *host* (core.host_offload.bilateral_luts) and ship them to
the accelerator.  This kernel consumes those LUTs: per output row-tile,
sweep the (K, K) neighborhood; the range weight is a VMEM LUT lookup on
the quantized intensity difference — no exp() anywhere on the device.

VMEM: padded image resident + spatial LUT (K, K) + range LUT (1, 256).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret


_LANES = 128


def _lut_lookup(rlut_ref, q, n_levels: int):
    """``rlut[q]`` as 128-lane in-register gathers: the TPU gathers
    within one vreg only, so the LUT is cut into 128-entry pieces, the
    (rows, 128k) index array into 128-lane column chunks, and each
    piece answers the indices that fall in it."""
    rows, width = q.shape
    cols = []
    for c in range(0, width, _LANES):
        qc = q[:, c:c + _LANES]
        out = jnp.zeros(qc.shape, rlut_ref.dtype)
        for p in range(0, n_levels, _LANES):
            table = jnp.broadcast_to(rlut_ref[:, p:p + _LANES], qc.shape)
            got = jnp.take_along_axis(table, qc & (_LANES - 1), axis=1)
            out = jnp.where((qc >= p) & (qc < p + _LANES), got, out)
        cols.append(out)
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def _bilat_kernel(img_ref, sp_ref, rng_ref, o_ref, *, K: int,
                  row_tile: int, win_h: int, n_levels: int):
    i = pl.program_id(0)
    # an (8, 128)-aligned window of rows; row di of it is brought to
    # the top by a sublane rotation (loads at offsets that are not a
    # multiple of 8 do not lower)
    win = img_ref[pl.ds(pl.multiple_of(i * row_tile, 8), win_h), :]
    W_out = o_ref.shape[1]
    r = K // 2
    center = win[r:r + row_tile, r:r + W_out]

    # the filter row di is a rolled loop: unrolling the K*K sweep keeps
    # every shifted temporary live and overflows VMEM
    def row(di, carry):
        num, den = carry
        rows = pltpu.roll(win, (win_h - di) % win_h, 0)[:row_tile]
        for dj in range(K):
            nb = rows[:, dj:dj + W_out]
            diff = jnp.abs(nb - center)
            q = jnp.clip(diff.astype(jnp.int32), 0, n_levels - 1)
            wgt = sp_ref[di, dj] * _lut_lookup(rng_ref, q, n_levels)
            num += wgt * nb
            den += wgt
        return num, den

    zeros = jnp.zeros((row_tile, W_out), jnp.float32)
    num, den = jax.lax.fori_loop(0, K, row, (zeros, zeros))
    o_ref[...] = (num / jnp.maximum(den, 1e-12)).astype(o_ref.dtype)


def bilateral_lut_xla(img: jnp.ndarray, spatial_lut: jnp.ndarray,
                      range_lut: jnp.ndarray) -> jnp.ndarray:
    """The LUT filter as a plain XLA program (K*K shifted fused
    lookups) — the non-Pallas candidate the autotuner ranks."""
    H, W = img.shape
    K = spatial_lut.shape[0]
    r = K // 2
    n_levels = range_lut.shape[0]
    padded = jnp.pad(img, r, mode="edge")
    num = jnp.zeros((H, W), jnp.float32)
    den = jnp.zeros((H, W), jnp.float32)
    for di in range(K):
        for dj in range(K):
            nb = jax.lax.dynamic_slice(padded, (di, dj), (H, W))
            q = jnp.clip(jnp.abs(nb - img).astype(jnp.int32), 0,
                         n_levels - 1)
            wgt = spatial_lut[di, dj] * jnp.take(range_lut, q)
            num += wgt * nb
            den += wgt
    return (num / jnp.maximum(den, 1e-12)).astype(img.dtype)


def bilateral_pallas(img: jnp.ndarray, spatial_lut: jnp.ndarray,
                     range_lut: jnp.ndarray, *, row_tile: int = 64,
                     interpret: bool | None = None) -> jnp.ndarray:
    """img: (H, W) f32 intensities in [0, 255]. LUTs from host precompute.

    Tunable knob (kernels/autotune.py): row_tile."""
    interpret = resolve_interpret(interpret)
    H, W = img.shape
    row_tile = min(row_tile, H)
    K = spatial_lut.shape[0]
    r = K // 2
    n_levels = range_lut.shape[0]
    pad_h = (-H) % row_tile
    # output columns and LUT entries round up to whole 128-lane vregs
    # (the LUT gather works one vreg at a time); the extra columns are
    # edge padding nobody reads, the extra LUT entries are never indexed
    pad_w = (-W) % _LANES
    win_h = -(-(row_tile + K - 1) // 8) * 8
    padded = jnp.pad(img, ((r, win_h - row_tile - r + pad_h),
                           (r, r + pad_w)), mode="edge")
    lut = jnp.pad(range_lut, (0, (-n_levels) % _LANES))[None, :]
    grid = ((H + pad_h) // row_tile,)
    out = pl.pallas_call(
        functools.partial(_bilat_kernel, K=K, row_tile=row_tile,
                          win_h=win_h, n_levels=n_levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec(padded.shape, lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(lut.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, W + pad_w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H + pad_h, W + pad_w), img.dtype),
        interpret=interpret,
    )(padded, spatial_lut, lut)
    return out[:H, :W]
