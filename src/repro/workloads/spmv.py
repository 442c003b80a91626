"""spmv workload (paper §4.3): the flagship work-sharing-by-suitability.

Rows are sorted by nnz; *dense* rows go to the accelerator (ELL kernel),
the *sparse tail* goes to the host path (COO segment-sum).  The split
threshold is exactly the work-share knob; the x vector is kept on both
devices (paper: "the entire x vector is kept at both the CPU and GPU").
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict

import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import CostTerms
from repro.core.hybrid_executor import HybridExecutor, WorkSharedOutput
from repro.kernels.spmv import ops as spmv_ops
from repro.kernels.spmv.ref import spmv_coo_ref


def make_matrix(n: int = 2048, density: float = 0.01, seed: int = 0,
                skew: float = 4.0):
    """Power-law row densities (like the paper's [49] suite)."""
    rng = np.random.default_rng(seed)
    base = rng.random((n, n)) < density
    heavy = rng.choice(n, max(n // 50, 1), replace=False)
    base[heavy] |= rng.random((len(heavy), n)) < density * skew * 10
    A = base.astype(np.float32) * rng.standard_normal((n, n)).astype(
        np.float32)
    return A


# ELL/COO packing is the paper's amortized preprocessing ("spmv is
# used over multiple iterations") — persisted across calls (matrices
# are deterministic per (n, density, seed)) so steady-state chunks
# never pay packing inside the timed path
_PREP_CACHE = {}


@dataclass(frozen=True)
class ShareSpec:
    """The work-shared form of one spmv problem, reusable by both
    ``run_hybrid`` and the serving request adapter."""
    total_units: int
    run_share: Callable[[str, int, int], object]
    combine: Callable[[list], object]
    unit_cost: Dict[str, CostTerms]
    comm_cost: float
    workload: str


def _per_path_unit_cost(unit: int) -> Dict[str, CostTerms]:
    """Per-path cost priors for ONE work unit (``unit`` nonzeros): the
    groups run *different algorithms*, so a single CostTerms cannot
    seed both.  ELL head (accel): vals+idx reads, x gather, padded-row
    waste folded into a 1.5x byte factor (power-law heads pad the tile
    width).  COO tail (host): rows+cols+vals reads, x gather, and the
    segment-sum's y read-modify-write."""
    return {
        "accel": CostTerms(flops=2.0 * unit, bytes=4.0 * 3.0 * unit * 1.5),
        "host": CostTerms(flops=2.0 * unit, bytes=4.0 * 5.0 * unit),
    }


def make_share_spec(n: int = 2048, density: float = 0.01, seed: int = 0
                    ) -> ShareSpec:
    """Build the suitability-split execution (paper §4.3): rows sorted
    by nnz, dense prefix -> ELL on the accel group, sparse tail -> COO
    on the host group; work units are nonzero blocks."""
    A = make_matrix(n, density, seed)
    x = jnp.asarray(np.random.default_rng(seed + 1).standard_normal(n)
                    .astype(np.float32))
    nnz = (A != 0).sum(1)
    # paper: sort rows by nnz; DENSE prefix -> accelerator (group 0),
    # sparse tail -> host (group 1)
    order = np.argsort(-nnz)
    A_sorted = A[order]
    # Work units are NONZEROS, not rows: per-row cost is wildly
    # non-uniform after the density sort, per-nnz cost is uniform.
    cum_nnz = np.concatenate([[0], np.cumsum(nnz[order])])
    total_nnz = int(cum_nnz[-1])
    unit = max(total_nnz // 256, 1)
    total_units = total_nnz // unit

    def rows_of(start_u, k_u):
        lo = int(np.searchsorted(cum_nnz, start_u * unit, side="left"))
        if start_u + k_u >= total_units:        # last share covers the rest
            return min(lo, n - 1), n
        hi = int(np.searchsorted(cum_nnz, (start_u + k_u) * unit,
                                 side="left"))
        return lo, max(hi, lo + 1)

    _prep_cache = _PREP_CACHE

    def run_share(group, start_u, k_u):
        lo, hi = rows_of(start_u, k_u)
        key = (n, density, seed, group, lo, hi)
        if key not in _prep_cache:
            block = A_sorted[lo:hi]
            if group == "accel":
                # dense rows -> ELL kernel, binned in row TILES so the
                # power-law head doesn't set the padding width for the
                # whole share (the paper's row binning, per 512 rows)
                tiles = []
                for t0 in range(0, block.shape[0], 512):
                    sub = block[t0:t0 + 512]
                    tiles.append(spmv_ops.prepare(
                        sub, k_threshold=int(max((sub != 0).sum(1).max(),
                                                 1))))
                _prep_cache[key] = tiles
            else:                               # sparse tail -> COO path
                rr, cc = np.nonzero(block)
                _prep_cache[key] = (
                    jnp.asarray(rr.astype(np.int32)),
                    jnp.asarray(cc.astype(np.int32)),
                    jnp.asarray(block[rr, cc]))
        if group == "accel":
            # ELL head through XLA's gather + row-sum (spmv ops
            # DEFAULT_CONFIG), COO tail below on the host lane
            parts = [spmv_ops.spmv(m_, x) for m_ in _prep_cache[key]]
            y = jnp.concatenate(parts)
        else:
            rr, cc, vv = _prep_cache[key]
            y = spmv_coo_ref(rr, cc, vv, x, hi - lo)
        y.block_until_ready()
        return (lo, hi, np.asarray(y))

    def combine(outs):
        y = np.zeros(n, np.float32)
        for lo, hi, part in outs:
            y[order[lo:hi]] = part              # undo row permutation
        return jnp.asarray(y)

    return ShareSpec(total_units=total_units, run_share=run_share,
                     combine=combine,
                     unit_cost=_per_path_unit_cost(unit),
                     comm_cost=n * 4 / 6e9,          # y merge
                     workload=f"spmv/{n}x{density}")


def run_hybrid(ex: HybridExecutor, n: int = 2048, density: float = 0.01
               ) -> WorkSharedOutput:
    spec = make_share_spec(n, density)
    # per-path cost priors (ROADMAP open item): a cold cache plans the
    # ELL head and COO tail from their own analytic terms with zero
    # probe runs instead of falling back to probe-only estimates
    ex.calibrate(lambda g, k: spec.run_share(g, 0, k),
                 probe_units=spec.total_units // 8,
                 workload=spec.workload, unit_cost=spec.unit_cost)
    # suitability split (dense head -> ELL, sparse tail -> COO): each
    # share runs as ONE chunk (no stealing) — ELL/COO shapes are
    # data-dependent per row range, so a uniform chunk grid would make
    # every chunk a fresh jit compile + packing inside the timed path
    return ex.run_work_shared("spmv", spec.total_units, spec.run_share,
                              spec.combine, comm_cost=spec.comm_cost,
                              whole_shares=True)
