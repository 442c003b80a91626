"""In-VMEM bitonic sorter Pallas kernel (paper §4.1 sort, TPU adaptation).

The CUDA sample-sort leaf sorts 32-element bins with warp-synchronous
quicksort.  Warps don't exist on TPU; the VREG-native equivalent is a
data-parallel bitonic network over the 128-wide lanes: each grid step
sorts a tile of rows entirely in VMEM with log^2(L) vectorized
compare-exchange sweeps (jnp.where on XOR-partner lanes).

Used as the leaf sorter of the hybrid sample sort in workloads/sort.py.
VMEM: (TR, L) f32 + index helpers; TR=256, L<=1024 -> ~1 MiB.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret


def _bitonic_rows(x: jnp.ndarray) -> jnp.ndarray:
    """Sort each row ascending; L = power of two (static unrolled net).

    The stride-j partner of lane i is i^j, i.e. lane i+j for the low
    half of each 2j block and lane i-j for the high half — so partner
    values come from two lane rotations and a select, never a gather
    (an unrolled ``jnp.take`` network compiles catastrophically: each
    sweep is an L-wide dynamic gather) and never a reversal (which the
    TPU kernel compiler does not lower)."""
    _, L = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, L), dimension=1)
    k = 2
    while k <= L:
        j = k // 2
        while j >= 1:
            is_lo = (lane & j) == 0          # lane < partner
            px = jnp.where(is_lo, jnp.roll(x, -j, axis=1),
                           jnp.roll(x, j, axis=1))
            ascending = (lane & k) == 0
            keep_min = is_lo == ascending
            x = jnp.where(keep_min, jnp.minimum(x, px), jnp.maximum(x, px))
            j //= 2
        k *= 2
    return x


def bitonic_rows_xla(x: jnp.ndarray) -> jnp.ndarray:
    """The same compare-exchange network as a plain XLA program over the
    whole array — the untiled candidate the autotuner ranks against the
    Pallas row tiles (and against the backend's native sort)."""
    return _bitonic_rows(x)


def _sort_kernel(x_ref, o_ref):
    o_ref[...] = _bitonic_rows(x_ref[...])


def sort_rows_pallas(x: jnp.ndarray, *, row_tile: int = 256,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Sort each row of (G, L) ascending; L must be a power of two."""
    interpret = resolve_interpret(interpret)
    G, L = x.shape
    assert (L & (L - 1)) == 0, f"L={L} must be a power of two"
    row_tile = min(row_tile, max(G, 1))
    pad = (-G) % row_tile
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    grid = (x.shape[0] // row_tile,)
    out = pl.pallas_call(
        _sort_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((row_tile, L), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((row_tile, L), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)
    return out[:G]
