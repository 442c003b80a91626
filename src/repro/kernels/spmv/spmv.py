"""Row-binned spmv Pallas kernel (paper §4.3, TPU adaptation).

The paper sorts rows by nnz and sends dense rows to the GPU and the
sparse tail to the CPU.  The TPU version keeps the same transform:

  * rows are sorted by nnz and split at a threshold K;
  * the dense bin is ELL-packed — (R, K) values + column indices; XLA
    gathers x at those columns and this kernel streams row tiles of
    values and gathered x through VMEM, forming y as a row-sum (VPU)
    per tile;
  * the sparse tail (rows with nnz > K would explode ELL padding; rows
    with tiny nnz waste it) is handled by a COO segment-sum on the
    "host path" (ops.py) — exactly the paper's CPU-side share.

VMEM: two (TR, K) f32 tiles (values, gathered x).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret


def _spmv_kernel(vals_ref, xg_ref, o_ref):
    # vals, xg: (TR, K) — xg holds x gathered at each entry's column
    o_ref[...] = jnp.sum(vals_ref[...] * xg_ref[...], axis=1,
                         keepdims=True)


def spmv_ell_pallas(vals: jnp.ndarray, idx: jnp.ndarray, x: jnp.ndarray,
                    *, row_tile: int = 256, interpret: bool | None = None
                    ) -> jnp.ndarray:
    """ELL spmv: vals/idx (R, K) with zero-padding, x (C,). Returns (R,).

    Knob: row_tile.  Not autotuned: ops.DEFAULT_CONFIG is XLA's
    gather + row-sum, which this kernel repeats with an extra (R, K)
    array."""
    interpret = resolve_interpret(interpret)
    R, K = vals.shape
    row_tile = min(row_tile, max(R, 1))
    pad = (-R) % row_tile
    if pad:
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        idx = jnp.pad(idx, ((0, pad), (0, 0)))
    grid = (vals.shape[0] // row_tile,)
    # the column gather runs in XLA: the TPU kernel compiler gathers
    # only within one vreg, and x spans many
    xg = jnp.take(x, idx.astype(jnp.int32), axis=0)
    y = pl.pallas_call(
        _spmv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, K), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, K), lambda i: (i, 0)),
        ],
        # (rows, 1): the row sums come out along sublanes
        out_specs=pl.BlockSpec((row_tile, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((vals.shape[0], 1), vals.dtype),
        interpret=interpret,
    )(vals, xg)
    return y[:R, 0]
