"""Hybrid spmv: row-binning preprocessing + ELL kernel + COO tail.

This is the paper's §4.3 algorithm end-to-end: sort rows by nnz,
rearrange, dense bin -> accelerator kernel, sparse tail -> segment-sum
path.  ``prepare`` is the (amortized) preprocessing the paper relies on
("spmv is used over multiple iterations").
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.autotune import Config, freeze
from repro.kernels.spmv.spmv import spmv_ell_pallas
from repro.kernels.spmv.ref import spmv_coo_ref, spmv_ell_ref

# The path every call without a config takes.  The Pallas kernel sums rows of x gathered by
# XLA (the TPU kernel compiler gathers only within one vreg), so it
# computes what ``xla_ell`` computes and writes and reads back an extra
# (R, K) array: it runs only when a config names it, until x can be
# gathered inside VMEM.
DEFAULT_CONFIG: Config = {"impl": "xla_ell"}


@functools.partial(jax.jit, static_argnames=("cfg",))
def _ell_cfg(vals, idx, x, cfg):
    c = dict(cfg)
    if c.get("impl", "xla_ell") == "xla_ell":
        return spmv_ell_ref(vals, idx, x)
    return spmv_ell_pallas(vals, idx, x,
                           row_tile=int(c.get("row_tile", 256)))


def spmv_ell(vals, idx, x, *, config: Optional[Config] = None):
    """ELL spmv (config=None -> ``DEFAULT_CONFIG``)."""
    return _ell_cfg(vals, idx, x, freeze(config or DEFAULT_CONFIG))


@dataclass
class BinnedCSR:
    """Preprocessed matrix: ELL dense bin + COO tail + row permutation."""
    ell_vals: jnp.ndarray            # (R_dense, K)
    ell_idx: jnp.ndarray             # (R_dense, K)
    ell_rows: jnp.ndarray            # (R_dense,) original row ids
    coo_rows: jnp.ndarray            # (nnz_tail,)
    coo_cols: jnp.ndarray
    coo_vals: jnp.ndarray
    n_rows: int
    n_cols: int


def prepare(dense: np.ndarray, k_threshold: int = 32) -> BinnedCSR:
    """Row-bin a dense matrix (paper: sort rows by nnz, split at K)."""
    A = np.asarray(dense)
    R, C = A.shape
    nnz_per_row = (A != 0).sum(1)
    dense_rows = np.where(nnz_per_row <= k_threshold)[0]
    tail_rows = np.where(nnz_per_row > k_threshold)[0]
    K = max(int(nnz_per_row[dense_rows].max()) if len(dense_rows) else 1, 1)
    ell_vals = np.zeros((len(dense_rows), K), A.dtype)
    ell_idx = np.zeros((len(dense_rows), K), np.int32)
    for i, r in enumerate(dense_rows):
        cols = np.nonzero(A[r])[0]
        ell_vals[i, :len(cols)] = A[r, cols]
        ell_idx[i, :len(cols)] = cols
    rr, cc = [], []
    for r in tail_rows:
        cols = np.nonzero(A[r])[0]
        rr.extend([r] * len(cols))
        cc.extend(cols)
    rr = np.asarray(rr, np.int32)
    cc = np.asarray(cc, np.int32)
    vv = A[rr, cc] if len(rr) else np.zeros((0,), A.dtype)
    return BinnedCSR(jnp.asarray(ell_vals), jnp.asarray(ell_idx),
                     jnp.asarray(dense_rows.astype(np.int32)),
                     jnp.asarray(rr), jnp.asarray(cc), jnp.asarray(vv),
                     R, C)


@functools.partial(jax.jit, static_argnames=("n_rows", "cfg"))
def _spmv_binned(ell_vals, ell_idx, ell_rows, coo_rows, coo_cols, coo_vals,
                 x, n_rows: int, cfg):
    y_dense = _ell_cfg(ell_vals, ell_idx, x, cfg)
    y = jnp.zeros((n_rows,), x.dtype).at[ell_rows].set(y_dense)
    if coo_vals.shape[0]:
        y = y + spmv_coo_ref(coo_rows, coo_cols, coo_vals, x, n_rows)
    return y


def spmv(m: BinnedCSR, x: jnp.ndarray, use_kernel: bool = True,
         config: Optional[Config] = None) -> jnp.ndarray:
    """Binned spmv: ELL head via ``config`` (None -> ``DEFAULT_CONFIG``),
    COO tail via segment-sum."""
    if not use_kernel:
        config = {"impl": "xla_ell"}
    elif config is None:
        config = DEFAULT_CONFIG
    return _spmv_binned(m.ell_vals, m.ell_idx, m.ell_rows, m.coo_rows,
                        m.coo_cols, m.coo_vals, x, m.n_rows,
                        freeze(config))
