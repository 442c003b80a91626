"""Request adapters: workloads as serving requests.

The serving scheduler (``repro.serve.scheduler``) is workload-agnostic;
this registry is where the paper's workloads become *requests*.  Each
adapter turns a payload into a ``RequestSpec``:

* ``run_one()`` — the whole request on the *current* device (the
  dedicated-placement path; must return a ready value, like
  ``run_share``),
* ``run_share(group, start, n)`` / ``combine(outs)`` — the work-shared
  form (the paper's §5.4.3 split, used when placement projects a
  makespan win over the split overhead),
* ``total_units`` / ``unit_cost`` — what placement scores against the
  PR-3 cost model before any probe has run (per-group dicts for
  suitability-split workloads whose groups run different algorithms),
* ``bucket`` — the shape bucket batching coalesces on: two requests
  merge only when a single batched execution can serve both,
* ``merge`` (optional) — array-level batching: stack same-shape
  payloads into ONE kernel call (a ``MergedBatch`` whose ``demux``
  recovers each member's exact result).  Without it the scheduler
  falls back to request-granularity coalescing (members run whole,
  one per work unit).

Every entry of ``repro.workloads.ALL_WORKLOADS`` — the paper's 13
Table-1 workloads — is registered here (plus ``attention`` and the
per-arch serve-LM adapters), each with a ``unit_cost`` prior, so a
fresh process can place ANY Table-1 request with zero probe runs.

Payloads are dicts of shape parameters (sizes, seeds) or raw arrays;
deterministic default inputs reuse each workload module's memoized
``make_inputs`` so repeated requests hit jit caches and the tune cache
the way real repeated traffic would.
"""
from __future__ import annotations

import functools
import threading
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Union

import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import CostTerms
from repro.kernels.autotune import bucket as pow2_bucket

UnitCost = Union[CostTerms, Dict[str, CostTerms], None]


@dataclass(frozen=True)
class RequestSpec:
    """Everything the scheduler needs to place and execute one request.
    ``workload`` keys the calibration cache (and therefore placement's
    learned per-group affinity); it must identify the computation AND
    the shape bucket.

    ``arrays`` holds the raw device/host input arrays when the adapter
    supports array-level batching; ``merge`` builds a ``MergedBatch``
    from a list of same-bucket specs (returning ``None`` when this
    particular batch cannot stack, e.g. mismatched shapes inside one
    pow2 bucket — the scheduler then falls back to per-request
    coalescing).

    ``stepper`` opts the request into the continuous-batching engine
    (``repro.serve.continuous``): the decode step/iteration becomes the
    scheduling quantum, same-bucket requests stack into one slot-
    batched kernel call per step, and the request is preemptible at
    every step boundary.  The stepper instance must be SHARED across
    requests of one workload (the engine is keyed by it); ``run_one``
    stays the monolithic fallback (``REPRO_SERVE_CONTINUOUS=0``, fifo
    policy)."""
    workload: str
    total_units: int
    run_one: Callable[[], object]
    run_share: Callable[[str, int, int], object]
    combine: Callable[[List[object]], object]
    unit_cost: UnitCost = None
    comm_cost: float = 0.0
    whole_shares: bool = False
    steal: Optional[bool] = None
    bucket: str = ""
    arrays: tuple = ()
    merge: Optional[Callable[[List["RequestSpec"]],
                             Optional["MergedBatch"]]] = None
    stepper: Optional[object] = None
    # contention pricing class: "jax" ops are internally multithreaded
    # (XLA grabs every core, so two lanes contend); "host" ops
    # (GIL-releasing single-core numpy, e.g. sort) overlap a jax lane
    # near-perfectly.  The scheduler prices shared/contended spans
    # with the factor probed for THIS class instead of one global one.
    lane_class: str = "jax"


@dataclass(frozen=True)
class MergedBatch:
    """One array-level batched execution serving several requests:
    ``spec`` runs the stacked inputs as one kernel call (dedicated
    path) or one work-shared grid (shared path); ``demux(value, i)``
    slices member ``i``'s exact result back out — batched execution
    must be bit-identical to per-request execution, so demux is pure
    indexing, never recomputation."""
    spec: RequestSpec
    demux: Callable[[object, int], object]


_REGISTRY: Dict[str, Callable[[Optional[dict]], RequestSpec]] = {}


def register(name: str,
             factory: Callable[[Optional[dict]], RequestSpec]) -> None:
    _REGISTRY[name] = factory


def available() -> List[str]:
    _ensure_defaults()
    return sorted(_REGISTRY)


def make_request(workload: str, payload: Optional[dict] = None
                 ) -> RequestSpec:
    """Resolve a (workload-name, payload) submission to a spec."""
    _ensure_defaults()
    if workload not in _REGISTRY:
        raise KeyError(f"unknown workload {workload!r}; registered: "
                       f"{sorted(_REGISTRY)}")
    return _REGISTRY[workload](payload)


# ---------------------------------------------------------------------------
# conv — regular, compute-bound; units are output rows
# ---------------------------------------------------------------------------
def _conv_merge(specs: List[RequestSpec]) -> Optional[MergedBatch]:
    """Stack same-shape conv requests into ONE vmapped XLA-conv call
    (``conv2d_batched``); demux returns row i.  Engages only when the
    members' tuned config resolves to the ``xla_conv`` impl: vmap over
    that impl is bit-identical per row to the solo path (measured),
    while the shift-add and Pallas impls reassociate under vmap — a
    tuned-to-pallas bucket declines and falls back to per-request
    coalescing (batching is an optimization, never a correctness
    risk)."""
    from repro.kernels.conv2d.ops import conv2d_batched, tuned_config

    arrs = [s.arrays for s in specs if len(s.arrays) == 2]
    if (len(arrs) != len(specs)
            or len({a[0].shape for a in arrs}) != 1
            or len({a[1].shape for a in arrs}) != 1):
        return None                     # pow2 bucket, unequal shapes
    cfg = tuned_config(arrs[0][0], arrs[0][1])   # memoized per bucket
    if dict(cfg).get("impl") != "xla_conv":
        return None
    n_real = len(arrs)
    rows = _ceil_pow2(n_real)           # bound jit shape variants
    imgs = _pad_pow2_rows(jnp.stack([a[0] for a in arrs]), rows)
    ws = _pad_pow2_rows(jnp.stack([a[1] for a in arrs]), rows)
    H, W = arrs[0][0].shape
    K = arrs[0][1].shape[0]

    def run_one():
        out = conv2d_batched(imgs, ws)
        out.block_until_ready()
        return out

    def run_share(group, start, k):
        out = conv2d_batched(imgs[start:start + k], ws[start:start + k])
        out.block_until_ready()
        return out

    base = specs[0]
    spec = RequestSpec(
        # row units are whole member convs — a different per-unit cost
        # than the base spec's output rows, so a distinct calibration key
        workload=f"{base.workload}@stack", total_units=n_real,
        run_one=run_one, run_share=run_share,
        combine=lambda outs: jnp.concatenate(outs, axis=0),
        unit_cost=CostTerms(flops=2.0 * H * W * K * K,
                            bytes=4.0 * (2 * H * W + K * K)),
        bucket=base.bucket)
    return MergedBatch(spec, lambda value, i: value[i])


def _conv_spec(payload: Optional[dict]) -> RequestSpec:
    from repro.kernels.conv2d.ops import conv2d, tuned_config
    from repro.workloads import conv

    p = dict(payload or {})
    if "image" in p:
        img = jnp.asarray(p["image"])
        w = jnp.asarray(p["weights"])
    else:
        img, w = conv.make_inputs(int(p.get("size", 512)),
                                  int(p.get("ksize", 15)),
                                  int(p.get("seed", 0)))
    H, W = img.shape
    K = w.shape[0]

    # the tuned config is resolved where the request runs: each lane
    # tunes for its own device (a search at most once per platform and
    # shape bucket, then a cache lookup)
    def run_one():
        out = conv2d(img, w, config=tuned_config(img, w))
        out.block_until_ready()
        return out

    def run_share(group, start, n):
        out = conv.conv_rows(img, w, start, n, config=tuned_config(img, w))
        out.block_until_ready()
        return out

    return RequestSpec(
        workload=f"serve-conv/{H}x{K}", total_units=H,
        run_one=run_one, run_share=run_share,
        combine=lambda outs: jnp.concatenate(outs, axis=0),
        unit_cost=CostTerms(flops=2.0 * W * K * K, bytes=4.0 * 2 * W),
        comm_cost=(K - 1) * W * 4 / 6e9,
        bucket=f"H{pow2_bucket(H)}_K{K}",
        arrays=(img, w), merge=_conv_merge)


# ---------------------------------------------------------------------------
# hist — memory-bound; units are element blocks
# ---------------------------------------------------------------------------
def _hist_merge(specs: List[RequestSpec]) -> Optional[MergedBatch]:
    """Stack same-length histogram payloads into a (R, n) matrix
    counted row-wise in ONE vmapped bincount call
    (``histogram_rows``); demux returns row i.  Counts are exact
    integer sums, so each row is bit-identical to the solo
    ``histogram`` of that payload regardless of which impl the solo
    path autotuned to.  Zero-pad rows land every count in bin 0 of a
    padded row nobody reads."""
    from repro.kernels.hist.ops import histogram_rows

    xs = [s.arrays[0] for s in specs if s.arrays]
    if len(xs) != len(specs) or len({x.shape for x in xs}) != 1:
        return None                     # pow2 bucket, unequal lengths
    n_bins = int(specs[0].workload.rsplit("x", 1)[1])
    n_real = len(xs)
    rows = _ceil_pow2(n_real)           # bound jit shape variants
    stack = _pad_pow2_rows(jnp.stack(xs), rows)
    n = int(xs[0].shape[0])

    def run_one():
        out = histogram_rows(stack, n_bins)
        out.block_until_ready()
        return out

    def run_share(group, start, k):
        out = histogram_rows(stack[start:start + k], n_bins)
        out.block_until_ready()
        return out

    base = specs[0]
    spec = RequestSpec(
        # row units are whole member histograms, not element blocks —
        # distinct calibration key
        workload=f"{base.workload}@stack", total_units=n_real,
        run_one=run_one, run_share=run_share,
        combine=lambda outs: jnp.concatenate(outs, axis=0),
        unit_cost=CostTerms(flops=2.0 * n, bytes=4.0 * (n + n_bins)),
        bucket=base.bucket)
    return MergedBatch(spec, lambda value, i: value[i])


def _hist_spec(payload: Optional[dict]) -> RequestSpec:
    from repro.kernels.hist.ops import histogram, tuned_config
    from repro.workloads import hist

    p = dict(payload or {})
    n_bins = int(p.get("n_bins", 256))
    if "data" in p:
        x = jnp.asarray(p["data"])
    else:
        x = hist.make_inputs(int(p.get("n", 1 << 20)), n_bins,
                             int(p.get("seed", 0)))
    n = x.shape[0]
    unit = max(n // 64, 1)
    units = max(n // unit, 1)
    half = x[:max(n // 2, 1)]

    # tuned where the request runs: each lane's own device decides
    def run_one():
        out = histogram(x, n_bins, config=tuned_config(half, n_bins))
        out.block_until_ready()
        return out

    def run_share(group, start, k):
        if k <= 0:
            return jnp.zeros((n_bins,), jnp.int32)
        out = histogram(x[start * unit:(start + k) * unit], n_bins,
                        config=tuned_config(half, n_bins))
        out.block_until_ready()
        return out

    return RequestSpec(
        workload=f"serve-hist/{n}x{n_bins}", total_units=units,
        run_one=run_one, run_share=run_share,
        combine=lambda outs: sum(outs),
        unit_cost=CostTerms(flops=2.0 * unit, bytes=4.0 * unit),
        comm_cost=n_bins * 4 / 6e9,
        bucket=f"N{pow2_bucket(n)}_B{n_bins}",
        arrays=(x,), merge=_hist_merge)


# ---------------------------------------------------------------------------
# spmv — the suitability split; units are nonzero blocks
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def _spmv_prepared(n: int, density: float, seed: int):
    from repro.kernels.spmv import ops as spmv_ops
    from repro.workloads import spmv as spmv_wl

    A = spmv_wl.make_matrix(n, density, seed)
    x = jnp.asarray(np.random.default_rng(seed + 1)
                    .standard_normal(n).astype(np.float32))
    return spmv_ops.prepare(A, k_threshold=32), x


@functools.lru_cache(maxsize=4)
def _spmv_share_spec(n: int, density: float, seed: int):
    """Memoized: make_share_spec regenerates the O(n^2) matrix and
    re-sorts rows by nnz — per-submit rebuilds would burn the client
    thread's cores against the lane workers."""
    from repro.workloads import spmv as spmv_wl
    return spmv_wl.make_share_spec(n, density, seed)


def _spmv_spec(payload: Optional[dict]) -> RequestSpec:
    from repro.kernels.spmv import ops as spmv_ops

    p = dict(payload or {})
    n = int(p.get("n", 1024))
    density = float(p.get("density", 0.01))
    seed = int(p.get("seed", 0))
    prepared, x = _spmv_prepared(n, density, seed)

    def run_one():
        # the single-device algorithm: ELL head + COO tail, both here
        out = spmv_ops.spmv(prepared, x)
        out.block_until_ready()
        return out

    shared = _spmv_share_spec(n, density, seed)

    return RequestSpec(
        workload=f"serve-spmv/{n}x{density:g}",
        total_units=shared.total_units,
        run_one=run_one, run_share=shared.run_share,
        combine=shared.combine,
        unit_cost=shared.unit_cost,
        comm_cost=shared.comm_cost, whole_shares=True, steal=False,
        bucket=f"N{pow2_bucket(n)}_d{density:g}")


# ---------------------------------------------------------------------------
# sort — host-native compute (paper §4.1's CPU leaf-sort path); units
# are key segments.  np.sort releases the GIL and runs single-core, so
# a sort request co-scheduled on one lane leaves the other lane's jax
# work genuinely unimpeded — the affinity spread the scheduler exploits.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=8)
def _sort_inputs(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).random(n).astype(np.float32)


def _sort_merge(specs: List[RequestSpec]) -> Optional[MergedBatch]:
    """Stack equal-length sort payloads into a (R, n) matrix sorted
    row-wise in ONE numpy call; demux returns row i.  Row-wise
    ``np.sort`` of the stack is bit-identical to sorting each payload
    alone (same algorithm over the same values)."""
    xs = [s.arrays[0] for s in specs if s.arrays]
    if len(xs) != len(specs) or len({x.shape for x in xs}) != 1:
        return None                     # pow2 bucket, unequal lengths
    stack = np.stack(xs)
    n = stack.shape[1]

    def run_one():
        return np.sort(stack, axis=-1, kind="stable")

    def run_share(group, start, k):
        return np.sort(stack[start:start + k], axis=-1, kind="stable")

    base = specs[0]
    lg = max(np.log2(max(n, 2)), 1.0)
    spec = RequestSpec(
        # row units are whole member sorts — a different per-unit cost
        # than the base spec's segments, so a distinct calibration key
        workload=f"{base.workload}@stack", total_units=len(xs),
        run_one=run_one, run_share=run_share,
        combine=lambda outs: np.concatenate(outs, axis=0),
        unit_cost=CostTerms(flops=2.0 * n * lg, bytes=8.0 * n * lg),
        bucket=base.bucket, lane_class="host")
    return MergedBatch(spec, lambda value, i: value[i])


def _sort_spec(payload: Optional[dict]) -> RequestSpec:
    p = dict(payload or {})
    if "data" in p:
        x = np.asarray(p["data"], dtype=np.float32)
    else:
        x = _sort_inputs(int(p.get("n", 1 << 16)), int(p.get("seed", 0)))
    n = x.shape[0]
    units = 16
    seg = -(-n // units)

    def run_one():
        return np.sort(x, kind="stable")

    def run_share(group, start, k):
        lo, hi = start * seg, min((start + k) * seg, n)
        return np.sort(x[lo:hi], kind="stable")

    def combine(outs):
        out = np.concatenate(outs)
        out.sort(kind="stable")                 # final merge pass
        return out

    lg = max(np.log2(max(n, 2)), 1.0)
    return RequestSpec(
        workload=f"serve-sort/{n}", total_units=units,
        run_one=run_one, run_share=run_share, combine=combine,
        unit_cost=CostTerms(flops=2.0 * seg * lg, bytes=8.0 * seg * lg),
        comm_cost=0.0,
        bucket=f"N{pow2_bucket(n)}",
        arrays=(x,), merge=_sort_merge, lane_class="host")


# ---------------------------------------------------------------------------
# attention — serve-LM's hot kernel; units are batch rows
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=8)
def _attn_inputs(B: int, T: int, H: int, d: int, Kv: int, seed: int):
    """Deterministic q/k/v, memoized: regenerating them on every
    submit puts RNG dispatches on the same cores the lane workers are
    serving from (conv/hist memoize their inputs for the same
    reason)."""
    import jax
    q = jax.random.normal(jax.random.key(seed), (B, T, H, d), jnp.float32)
    k = jax.random.normal(jax.random.key(seed + 1), (B, T, Kv, d),
                          jnp.float32)
    v = jax.random.normal(jax.random.key(seed + 2), (B, T, Kv, d),
                          jnp.float32)
    return q, k, v


def _ceil_pow2(n: int) -> int:
    return 1 << (max(int(n), 1) - 1).bit_length()


def _pad_pow2_rows(x, rows: int):
    """Zero-pad the leading axis to ``rows`` (a pow2): merged batches
    of 3, 5, 6... members would each jit-compile a fresh kernel shape
    inside the serving path; padding bounds the shape set to the
    pow2 sizes, which amortize after the first batch."""
    b = int(x.shape[0])
    if b == rows:
        return x
    return jnp.pad(x, [(0, rows - b)] + [(0, 0)] * (x.ndim - 1))


def _attn_merge(specs: List[RequestSpec]) -> Optional[MergedBatch]:
    """Concatenate same-shape attention requests along the batch axis
    into ONE sdpa call; demux slices each member's rows back out.
    Every (batch-row, head) is an independent program of the blocked
    kernel, so the stacked call is bit-identical per row (zero-pad
    rows compute garbage nobody reads)."""
    arrs = [s.arrays for s in specs if len(s.arrays) == 3]
    if (len(arrs) != len(specs)
            or len({a[0].shape[1:] for a in arrs}) != 1
            or len({a[1].shape[1:] for a in arrs}) != 1):
        return None                     # pow2 bucket, unequal shapes
    from repro.kernels.flash_attention import ops as attn_ops

    offs = np.cumsum([0] + [int(a[0].shape[0]) for a in arrs])
    rows = _ceil_pow2(int(offs[-1]))
    q = _pad_pow2_rows(jnp.concatenate([a[0] for a in arrs], axis=0),
                       rows)
    k = _pad_pow2_rows(jnp.concatenate([a[1] for a in arrs], axis=0),
                       rows)
    v = _pad_pow2_rows(jnp.concatenate([a[2] for a in arrs], axis=0),
                       rows)

    def run_one():
        out = attn_ops.sdpa(q, k, v, causal=True)
        out.block_until_ready()
        return out

    def run_share(group, start, n):
        out = attn_ops.sdpa(q[start:start + n], k[start:start + n],
                            v[start:start + n], causal=True)
        out.block_until_ready()
        return out

    base = specs[0]
    spec = RequestSpec(
        # distinct calibration key: run_one computes PADDED rows while
        # total_units counts real ones, so elapsed/real-rows would
        # overestimate the base workload's per-row time by up to 2x
        # and bias placement against whichever lane ran the merge
        workload=f"{base.workload}@stack", total_units=int(offs[-1]),
        run_one=run_one, run_share=run_share,
        combine=lambda outs: jnp.concatenate(outs, axis=0),
        unit_cost=base.unit_cost, comm_cost=base.comm_cost,
        bucket=base.bucket)
    return MergedBatch(spec,
                       lambda value, i: value[offs[i]:offs[i + 1]])


def _attention_spec(payload: Optional[dict]) -> RequestSpec:
    from repro.kernels.flash_attention import ops as attn_ops

    p = dict(payload or {})
    if "q" in p:
        q, k, v = (jnp.asarray(p[x]) for x in ("q", "k", "v"))
    else:
        q, k, v = _attn_inputs(
            int(p.get("batch", 4)), int(p.get("seq", 256)),
            int(p.get("heads", 8)), int(p.get("dim", 64)),
            int(p.get("kv_heads", p.get("heads", 8))),
            int(p.get("seed", 0)))
    B, T, H, d = q.shape
    S = k.shape[1]
    cfg = attn_ops.tuned_config(q, k, v, causal=True)

    def run_one():
        out = attn_ops.sdpa(q, k, v, causal=True)
        out.block_until_ready()
        return out

    def run_share(group, start, n):
        out = attn_ops.sdpa(q[start:start + n], k[start:start + n],
                            v[start:start + n], causal=True)
        out.block_until_ready()
        return out

    # per-batch-row analytic terms of the resolved config (BH = heads
    # of ONE row): placement scores reflect what will actually execute
    unit = attn_ops.cost_terms(cfg, H, T, S, d, True)

    return RequestSpec(
        workload=f"serve-attn/{T}x{H}x{d}", total_units=B,
        run_one=run_one, run_share=run_share,
        combine=lambda outs: jnp.concatenate(outs, axis=0),
        unit_cost=unit,
        comm_cost=T * H * d * 4 / 6e9,
        bucket=f"T{pow2_bucket(T)}_H{H}_d{d}",
        arrays=(q, k, v), merge=_attn_merge)


# ---------------------------------------------------------------------------
# spgemm — row-row product (paper §4.4); units are output rows.  The
# padded-ELL pack of A is input prep, memoized once per problem, so
# every request (and every row share) is a pure gather+einsum call —
# run_share slices the SAME packed arrays run_one uses, so shares are
# bit-identical to the dedicated path, uniform in shape, stealable.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def _spgemm_prepared(n: int, density: float, seed: int):
    from repro.workloads import spgemm as spgemm_wl

    A, B_np = spgemm_wl.make_matrices(n, density, seed)
    width = max(int((A != 0).sum(1).max()), 1)
    vals = np.zeros((n, width), np.float32)
    idx = np.zeros((n, width), np.int32)
    for i in range(n):
        c = np.nonzero(A[i])[0]
        vals[i, :len(c)] = A[i, c]
        idx[i, :len(c)] = c
    return jnp.asarray(vals), jnp.asarray(idx), jnp.asarray(B_np)


def _spgemm_spec(payload: Optional[dict]) -> RequestSpec:
    from repro.workloads import spgemm as spgemm_wl

    p = dict(payload or {})
    n = int(p.get("n", 512))
    density = float(p.get("density", 0.02))
    seed = int(p.get("seed", 0))
    vals, idx, B = _spgemm_prepared(n, density, seed)

    def rowrow(lo, hi):
        out = jnp.einsum("rk,rkc->rc", vals[lo:hi], B[idx[lo:hi]])
        out.block_until_ready()
        return out

    return RequestSpec(
        workload=f"serve-spgemm/{n}x{density:g}", total_units=n,
        run_one=lambda: rowrow(0, n),
        run_share=lambda group, start, k: rowrow(start, start + k),
        combine=lambda outs: jnp.concatenate(outs, axis=0),
        unit_cost=spgemm_wl.unit_cost_terms(n, density),
        comm_cost=n * n * density * 8 / 6e9,
        bucket=f"N{pow2_bucket(n)}_d{density:g}")


# ---------------------------------------------------------------------------
# raycast — two-phase volume render (paper §4.5); units are ray blocks.
# Per-ray independence lets one request's phases fuse per share AND
# lets same-volume requests stack (array-level batching).
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def _raycast_inputs(n_rays: int, d: int, seed: int):
    from repro.workloads import raycast as rc

    vol = rc.make_volume(d, seed)
    ro, rd = rc.make_rays(n_rays, seed + 1)
    return vol, ro, rd


def _raycast_run(vol, ro, rd):
    from repro.workloads import raycast as rc

    t_in = rc._entry(ro, rd)
    out = rc._march(vol, ro, rd, t_in)
    out.block_until_ready()
    return out


def _raycast_merge(specs: List[RequestSpec]) -> Optional[MergedBatch]:
    """Concatenate same-volume, same-count ray sets into ONE
    entry+march call; demux slices each member's rays back out (every
    ray is independent, so the stacked call is bit-identical)."""
    arrs = [s.arrays for s in specs if len(s.arrays) == 3]
    if len(arrs) != len(specs):
        return None
    vol = arrs[0][0]
    if (any(a[0] is not vol for a in arrs)      # memoized volume: identity
            or len({a[1].shape for a in arrs}) != 1):
        return None
    n_each = int(arrs[0][1].shape[0])
    n_real = len(arrs) * n_each
    rows = _ceil_pow2(n_real)               # bound jit shape variants
    ro = _pad_pow2_rows(jnp.concatenate([a[1] for a in arrs], axis=0),
                        rows)
    rd = _pad_pow2_rows(jnp.concatenate([a[2] for a in arrs], axis=0),
                        rows)
    base = specs[0]
    unit = max(n_each // max(int(base.total_units), 1), 1)
    total = len(arrs) * int(base.total_units)

    def run_share(group, start, k):
        lo = start * unit
        hi = n_real if start + k >= total else (start + k) * unit
        return _raycast_run(vol, ro[lo:hi], rd[lo:hi])

    spec = RequestSpec(
        # distinct calibration key: run_one computes the pow2-padded
        # ray count, so timing it against the real unit count would
        # inflate the base workload's per-unit estimate
        workload=f"{base.workload}@stack", total_units=total,
        run_one=lambda: _raycast_run(vol, ro, rd),
        run_share=run_share,
        combine=lambda outs: jnp.concatenate(outs, axis=0),
        unit_cost=base.unit_cost, comm_cost=base.comm_cost,
        bucket=base.bucket)
    return MergedBatch(
        spec, lambda value, i: value[i * n_each:(i + 1) * n_each])


def _raycast_spec(payload: Optional[dict]) -> RequestSpec:
    from repro.workloads import raycast as rc

    p = dict(payload or {})
    n_rays = int(p.get("n_rays", 1 << 14))
    d = int(p.get("d", 32))
    seed = int(p.get("seed", 0))
    vol, ro, rd = _raycast_inputs(n_rays, d, seed)
    unit = max(n_rays // 64, 1)
    units = max(n_rays // unit, 1)

    def run_share(group, start, k):
        lo = start * unit
        hi = n_rays if start + k >= units else (start + k) * unit
        return _raycast_run(vol, ro[lo:hi], rd[lo:hi])

    per_ray = rc.unit_cost_terms()
    return RequestSpec(
        workload=f"serve-raycast/{n_rays}x{d}", total_units=units,
        run_one=lambda: _raycast_run(vol, ro, rd),
        run_share=run_share,
        combine=lambda outs: jnp.concatenate(outs, axis=0),
        unit_cost=CostTerms(flops=per_ray.flops * unit,
                            bytes=per_ray.bytes * unit),
        comm_cost=n_rays * 4 / 6e9,
        bucket=f"R{pow2_bucket(n_rays)}_D{d}",
        arrays=(vol, ro, rd), merge=_raycast_merge)


# ---------------------------------------------------------------------------
# montecarlo — photon-migration estimator (paper §4.7); units are
# photon blocks, the request's value is the mean absorbed weight.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def _mc_inputs(n_photons: int, seed: int):
    from repro.core.host_offload import host_prng_stream
    from repro.workloads import montecarlo as mc

    u = np.asarray(host_prng_stream(seed, n_photons * mc.N_STEPS))
    return jnp.asarray(u).reshape(n_photons, mc.N_STEPS)


def _montecarlo_spec(payload: Optional[dict]) -> RequestSpec:
    from repro.workloads import montecarlo as mc

    p = dict(payload or {})
    n_photons = int(p.get("n_photons", 1 << 16))
    unit = max(min(int(p.get("unit", 1 << 12)), n_photons), 1)
    seed = int(p.get("seed", 42))
    units = max(n_photons // unit, 1)
    u_all = _mc_inputs(n_photons, seed)

    def run_one():
        out = mc.simulate_photons(u_all)
        out.block_until_ready()
        return float(np.asarray(out))

    def run_share(group, start, k):
        lo = start * unit
        hi = n_photons if start + k >= units else (start + k) * unit
        out = mc.simulate_photons(u_all[lo:hi])
        out.block_until_ready()
        return float(np.asarray(out)) * (hi - lo)

    return RequestSpec(
        workload=f"serve-mc/{n_photons}x{unit}", total_units=units,
        run_one=run_one, run_share=run_share,
        combine=lambda outs: float(sum(outs)) / n_photons,
        unit_cost=mc.unit_cost_terms(unit),
        comm_cost=n_photons * mc.N_STEPS * 4 / 6e9,
        bucket=f"P{pow2_bucket(n_photons)}_u{unit}")


# ---------------------------------------------------------------------------
# Iteration steppers — the sequential single-unit adapters (listrank /
# lbm / dither) as continuous-batching citizens: one pointer-jump
# round / BGK step / dither row is the engine's scheduling quantum, so
# a request becomes preemptible at every iteration boundary and
# same-shape requests stack into one vmapped call.  Opt-in via the
# ``continuous: True`` payload key: monolithic ``run_one`` (one fused
# while_loop/scan) is faster for a solo request, so solo-latency
# traffic keeps the old path; the engine wins when several same-shape
# requests are live or lane time must be shared at fine grain.
# Steppers are memoized per shape — the engine is keyed by stepper
# instance, so every same-shape request stacks into one slot state.
# ---------------------------------------------------------------------------
def _engine_slots(default: int = 4) -> int:
    import os
    try:
        return max(int(os.environ.get("REPRO_SERVE_SLOTS", default)), 1)
    except ValueError:
        return default


@functools.lru_cache(maxsize=4)
def _listrank_stepper(n: int):
    from repro.serve.continuous import IterStepper
    from repro.workloads import listrank as lr

    uc = lr.unit_cost_terms(n)
    steps = max(int(uc.steps), 1)

    def make_rows(spec):
        succ = spec.arrays[0]
        rank0 = jnp.where(succ == jnp.arange(n), 0, 1)
        return [((succ, rank0), steps)]

    return IterStepper(
        workload=f"serve-listrank/{n}", n_slots=_engine_slots(),
        template_row=(jnp.zeros((n,), jnp.int32),
                      jnp.zeros((n,), jnp.int32)),
        # exactly ceil(log2 n) rounds equal pointer_jump_rank's
        # while_loop (extra rounds are idempotent: the tail self-loop
        # fixes succ; measured bit-identical)
        iter_fn=lambda sr: lr._one_round(sr[0], sr[1]),
        make_rows=make_rows,
        finalize=lambda row: np.asarray(row[1]),
        prefill_cost=CostTerms(flops=2.0 * n, bytes=8.0 * n),
        decode_cost=CostTerms(flops=uc.flops / steps,
                              bytes=uc.bytes / steps))


@functools.lru_cache(maxsize=4)
def _lbm_stepper(d: int, n_steps: int):
    from repro.serve.continuous import IterStepper
    from repro.workloads import lbm

    uc = lbm.unit_cost_terms(d, n_steps)

    return IterStepper(
        workload=f"serve-lbm/{d}x{n_steps}", n_slots=_engine_slots(),
        template_row=jnp.zeros((19, d, d, d), jnp.float32),
        iter_fn=lbm.step_all,
        make_rows=lambda spec: [(spec.arrays[0], n_steps)],
        finalize=lambda row: row,
        prefill_cost=CostTerms(bytes=19.0 * 4.0 * d ** 3),
        decode_cost=CostTerms(flops=uc.flops / n_steps,
                              bytes=uc.bytes / n_steps))


@functools.lru_cache(maxsize=4)
def _dither_stepper(h: int, w: int):
    import jax

    from repro.serve.continuous import IterStepper
    from repro.workloads import dither

    def row_iter(state):
        # one Floyd-Steinberg row: identical col scan + carry update to
        # fsd_dither's row_step, addressed by a carried row index so
        # vmapped slots can sit at different rows (measured
        # bit-identical to the fused two-level scan)
        img, carry, out, i = state
        row = jax.lax.dynamic_index_in_dim(img, i, 0, keepdims=False)

        def col_step(err_right, inp):
            x, be = inp
            old = x + be + err_right
            new = jnp.where(old > 127.5, 255.0, 0.0)
            e = old - new
            return e * (7 / 16), (new, e)

        _, (orow, errs) = jax.lax.scan(col_step, 0.0, (row, carry))
        down = errs * (5 / 16)
        left = jnp.roll(errs * (3 / 16), -1).at[-1].set(0.0)
        right = jnp.roll(errs * (1 / 16), 1).at[0].set(0.0)
        out = jax.lax.dynamic_update_index_in_dim(out, orow, i, 0)
        return img, down + left + right, out, i + 1

    def make_rows(spec):
        img = spec.arrays[0]
        state = (img, jnp.zeros((w,), jnp.float32),
                 jnp.zeros((h, w), jnp.float32), jnp.int32(0))
        return [(state, h)]

    uc = dither.unit_cost_terms(h, w)
    return IterStepper(
        workload=f"serve-dither/{h}x{w}", n_slots=_engine_slots(),
        template_row=(jnp.zeros((h, w), jnp.float32),
                      jnp.zeros((w,), jnp.float32),
                      jnp.zeros((h, w), jnp.float32), jnp.int32(0)),
        iter_fn=row_iter, make_rows=make_rows,
        finalize=lambda row: row[2],
        prefill_cost=CostTerms(bytes=4.0 * h * w),
        decode_cost=CostTerms(flops=uc.flops / h, bytes=uc.bytes / h))


# ---------------------------------------------------------------------------
# listrank — Wyllie pointer jumping (paper §4.8).  The rounds are
# sequential, so a request is ONE indivisible unit: placement
# co-schedules whole rankings across lanes (the hybrid win inside one
# ranking is the Fig. 5 PRNG pipeline, exercised by run_hybrid).
# ``continuous: True`` payloads ride the step-quantum engine instead.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def _listrank_inputs(n: int, seed: int):
    from repro.workloads import listrank as lr

    succ, _head = lr.make_list(n, seed)
    return succ


def _listrank_spec(payload: Optional[dict]) -> RequestSpec:
    from repro.workloads import listrank as lr

    p = dict(payload or {})
    n = int(p.get("n", 1 << 14))
    seed = int(p.get("seed", 0))
    succ = _listrank_inputs(n, seed)

    def run_one():
        out = lr.pointer_jump_rank(succ)
        out.block_until_ready()
        return np.asarray(out)

    return RequestSpec(
        workload=f"serve-listrank/{n}", total_units=1,
        run_one=run_one,
        run_share=lambda group, start, k: run_one(),
        combine=lambda outs: outs[0],
        unit_cost=lr.unit_cost_terms(n),
        bucket=f"N{pow2_bucket(n)}",
        arrays=(succ,),
        stepper=_listrank_stepper(n) if p.get("continuous") else None)


# ---------------------------------------------------------------------------
# concomp — the per-subgraph suitability split (paper §4.8): host BFS
# vs accel label-prop run DIFFERENT algorithms, so the prior is a
# per-group dict; subgraph shapes are data-dependent -> whole shares.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def _concomp_share_spec(n: int, avg_deg: float, seed: int):
    from repro.workloads import concomp as cc

    return cc.make_share_spec(n, avg_deg, seed)


def _concomp_spec(payload: Optional[dict]) -> RequestSpec:
    p = dict(payload or {})
    n = int(p.get("n", 1 << 12))
    avg_deg = float(p.get("avg_deg", 4.0))
    seed = int(p.get("seed", 0))
    shared = _concomp_share_spec(n, avg_deg, seed)

    return RequestSpec(
        workload=f"serve-concomp/{n}x{avg_deg:g}",
        total_units=shared.total_units,
        # dedicated path: the accel algorithm labels the whole graph
        run_one=lambda: shared.run_share("accel", 0, shared.total_units),
        run_share=shared.run_share, combine=shared.combine,
        unit_cost=shared.unit_cost, comm_cost=shared.comm_cost,
        whole_shares=True, steal=False,
        bucket=f"N{pow2_bucket(n)}_g{avg_deg:g}")


# ---------------------------------------------------------------------------
# lbm — D3Q19 lattice Boltzmann (paper §4.9).  Steps are sequential
# (each streams the previous state), so a request is one unit; the
# plane-split task parallelism lives inside run_hybrid.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def _lbm_state(d: int, seed: int):
    from repro.workloads import lbm

    return lbm.init_state(d, seed)


def _lbm_spec(payload: Optional[dict]) -> RequestSpec:
    from repro.workloads import lbm

    p = dict(payload or {})
    d = int(p.get("d", 16))
    n_steps = max(int(p.get("n_steps", 2)), 1)
    seed = int(p.get("seed", 0))
    f0 = _lbm_state(d, seed)

    def run_one():
        cur = f0
        for _ in range(n_steps):
            cur = lbm.step_all(cur)
        cur.block_until_ready()
        return cur

    return RequestSpec(
        workload=f"serve-lbm/{d}x{n_steps}", total_units=1,
        run_one=run_one,
        run_share=lambda group, start, k: run_one(),
        combine=lambda outs: outs[0],
        unit_cost=lbm.unit_cost_terms(d, n_steps),
        bucket=f"D{d}_s{n_steps}",
        arrays=(f0,),
        stepper=_lbm_stepper(d, n_steps) if p.get("continuous") else None)


# ---------------------------------------------------------------------------
# dither — Floyd-Steinberg error diffusion (paper §4.10): inherently
# sequential (the paper's point), one indivisible unit per request.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def _dither_inputs(h: int, w: int, seed: int):
    from repro.workloads import dither

    return dither.make_image(h, w, seed)


def _dither_spec(payload: Optional[dict]) -> RequestSpec:
    from repro.workloads import dither

    p = dict(payload or {})
    h = int(p.get("h", 128))
    w = int(p.get("w", 128))
    seed = int(p.get("seed", 0))
    img = _dither_inputs(h, w, seed)

    def run_one():
        out = dither.fsd_dither(img)
        out.block_until_ready()
        return out

    return RequestSpec(
        workload=f"serve-dither/{h}x{w}", total_units=1,
        run_one=run_one,
        run_share=lambda group, start, k: run_one(),
        combine=lambda outs: outs[0],
        unit_cost=dither.unit_cost_terms(h, w),
        bucket=f"H{pow2_bucket(h)}_W{pow2_bucket(w)}",
        arrays=(img,),
        stepper=_dither_stepper(h, w) if p.get("continuous") else None)


# ---------------------------------------------------------------------------
# bundle — Levenberg-Marquardt task pipeline (paper §4.10): damped
# iterations are sequential, one unit per request; the value is the
# final squared residual.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def _bundle_problem(n_cams: int, n_pts: int, seed: int):
    from repro.workloads import bundle

    return bundle.make_problem(n_cams, n_pts, seed)


def _bundle_spec(payload: Optional[dict]) -> RequestSpec:
    from repro.workloads import bundle

    p = dict(payload or {})
    n_cams = int(p.get("n_cams", 4))
    n_pts = int(p.get("n_pts", 256))
    n_iters = max(int(p.get("n_iters", 3)), 1)
    seed = int(p.get("seed", 0))
    cams, pts, obs = _bundle_problem(n_cams, n_pts, seed)

    def run_one():
        cur, err = cams, float("inf")
        for _ in range(n_iters):
            cur, err = bundle.lm_step(cur, pts, obs, 1e-3)
        return float(err)

    return RequestSpec(
        workload=f"serve-bundle/{n_cams}x{n_pts}", total_units=1,
        run_one=run_one,
        run_share=lambda group, start, k: run_one(),
        combine=lambda outs: outs[0],
        unit_cost=bundle.unit_cost_terms(n_cams, n_pts, n_iters),
        bucket=f"C{n_cams}_P{pow2_bucket(n_pts)}_i{n_iters}")


# ---------------------------------------------------------------------------
# bilateral — LUT bilateral filter (paper §4.6); units are output
# rows, shares carry the radius halo exactly like run_hybrid's.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def _bilateral_prepared(size: int, sigma_s: float, sigma_r: float,
                        radius: int, seed: int):
    from repro.core.host_offload import bilateral_luts
    from repro.workloads import bilateral as bl

    img = bl.make_inputs(size, seed)
    sp, rl = bilateral_luts(sigma_s, sigma_r, radius)
    return img, jnp.asarray(sp), jnp.asarray(rl)


def _bilateral_spec(payload: Optional[dict]) -> RequestSpec:
    from repro.kernels.bilateral.ops import bilateral_filter, tuned_config

    p = dict(payload or {})
    size = int(p.get("size", 256))
    sigma_s = float(p.get("sigma_s", 3.0))
    sigma_r = float(p.get("sigma_r", 30.0))
    radius = int(p.get("radius", 7))
    seed = int(p.get("seed", 0))
    img, sp, rl = _bilateral_prepared(size, sigma_s, sigma_r, radius,
                                      seed)
    H, W = img.shape
    K = 2 * radius + 1

    # tuned where the request runs: each lane's own device decides
    def run_one():
        out = bilateral_filter(img, sp, rl, config=tuned_config(img, sp, rl))
        out.block_until_ready()
        return out

    def run_share(group, start, n):
        lo = max(0, start - radius)
        hi = min(H, start + n + radius)
        out = bilateral_filter(img[lo:hi], sp, rl,
                               config=tuned_config(img, sp, rl))
        out = out[start - lo:start - lo + n]
        out.block_until_ready()
        return out

    return RequestSpec(
        workload=f"serve-bilat/{size}x{radius}", total_units=H,
        run_one=run_one, run_share=run_share,
        combine=lambda outs: jnp.concatenate(outs, axis=0),
        unit_cost=CostTerms(flops=6.0 * W * K * K, bytes=8.0 * W * K * K),
        comm_cost=(int(sp.size) + int(rl.size)) * 4 / 6e9,
        bucket=f"S{pow2_bucket(size)}_r{radius}")


# ---------------------------------------------------------------------------
# serve-LM — full generate() requests (registered per arch on demand)
# ---------------------------------------------------------------------------
def make_lm_adapter(cfg, params, prompt_len: int = 16,
                    new_tokens: int = 16, name: Optional[str] = None
                    ) -> str:
    """Register a serve-LM adapter for an initialized arch and return
    its workload name.  Units are batch rows; ``run_share`` decodes a
    row slice (the §5.4.3 split ``launch/serve.py --hybrid`` uses),
    ``run_one`` decodes the whole batch.  The cost prior is the decode
    roofline: ~2 FLOPs per parameter per generated token per row."""
    from repro.serve.serve_step import generate

    import jax

    wl_name = name or f"serve-lm/{cfg.name}"
    cache_len = prompt_len + new_tokens + 1
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    unit = CostTerms(flops=2.0 * n_params * (new_tokens + 1),
                     bytes=4.0 * n_params, compute="matmul")

    def factory(payload: Optional[dict]) -> RequestSpec:
        p = dict(payload or {})
        if "prompt" in p:
            prompt = jnp.asarray(p["prompt"])
        else:
            B = int(p.get("batch", 2))
            prompt = jax.random.randint(
                jax.random.key(int(p.get("seed", 1))),
                (B, prompt_len), 0, cfg.vocab_size)
        B = prompt.shape[0]

        def run_one():
            out = generate(cfg, params, prompt, new_tokens,
                           cache_len=cache_len)
            out.block_until_ready()
            return out

        def run_share(group, start, k):
            out = generate(cfg, params, prompt[start:start + k],
                           new_tokens, cache_len=cache_len)
            out.block_until_ready()
            return out

        return RequestSpec(
            workload=wl_name, total_units=B,
            run_one=run_one, run_share=run_share,
            combine=lambda outs: jnp.concatenate(outs, axis=0),
            unit_cost=unit,
            bucket=f"B{pow2_bucket(B)}_P{prompt_len}_N{new_tokens}")

    register(wl_name, factory)
    return wl_name


def make_continuous_lm_adapter(cfg, params, prompt_len: int = 16,
                               new_tokens: int = 16,
                               name: Optional[str] = None,
                               n_slots: Optional[int] = None,
                               warm_background: bool = True) -> str:
    """Register a continuous-batching serve-LM adapter and return its
    workload name (default ``serve-lm-cb/{arch}``).

    Requests carry a shared :class:`repro.serve.continuous.LMStepper`:
    the scheduler routes them to ONE iteration-level engine whose
    scheduling quantum is the decode step — live requests stack into a
    single slot-batched kernel call per step, new arrivals join at step
    boundaries, finished rows demux exactly.  ``run_one`` keeps the
    monolithic solo ``generate`` as the fallback when the engine is
    disabled (``REPRO_SERVE_CONTINUOUS=0`` or fifo policy), so the
    workload stays servable either way.  Registration kicks off a
    background precompile of the stepper's fixed slot shapes (prefill +
    slot step), so the first request never pays the compile."""
    from repro.serve.continuous import LMStepper
    from repro.serve.serve_step import generate

    import jax

    wl_name = name or f"serve-lm-cb/{cfg.name}"
    cache_len = prompt_len + new_tokens + 1
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    unit = CostTerms(flops=2.0 * n_params * (new_tokens + 1),
                     bytes=4.0 * n_params, compute="matmul")
    stepper = LMStepper(cfg, params, prompt_len=prompt_len,
                        new_tokens=new_tokens, cache_len=cache_len,
                        n_slots=n_slots or _engine_slots(),
                        workload=wl_name)

    def factory(payload: Optional[dict]) -> RequestSpec:
        p = dict(payload or {})
        if "prompt" in p:
            prompt = jnp.asarray(p["prompt"])
        else:
            B = int(p.get("batch", 1))
            prompt = jax.random.randint(
                jax.random.key(int(p.get("seed", 1))),
                (B, prompt_len), 0, cfg.vocab_size)
        B = prompt.shape[0]

        def run_one():
            out = generate(cfg, params, prompt, new_tokens,
                           cache_len=cache_len)
            out.block_until_ready()
            return out

        return RequestSpec(
            workload=wl_name, total_units=B,
            run_one=run_one,
            run_share=lambda group, start, k: run_one(),
            combine=lambda outs: outs[0],
            unit_cost=unit,
            bucket=f"B{pow2_bucket(B)}_P{prompt_len}_N{new_tokens}",
            arrays=(prompt,), stepper=stepper)

    register(wl_name, factory)
    if warm_background:
        _spawn_precompile(stepper.warm, tag=wl_name)
    return wl_name


# ---------------------------------------------------------------------------
# Registry-level precompile: merged-stack pow2 shapes + stepper
# programs, compiled ahead of traffic (optionally in the background at
# adapter-registration time).  Merged executions run pow2-padded
# stacks and each padded shape jit-compiles once per (shape, device)
# — enough to cascade an open-loop backlog when it lands mid-trace.
# ---------------------------------------------------------------------------
_PRECOMPILE_THREADS: List[threading.Thread] = []
_PRECOMPILE_LOCK = threading.Lock()


def _spawn_precompile(fn: Callable[[], None], tag: str = "") -> None:
    """Run ``fn`` on a daemon thread named ``precompile-*`` (NEVER
    ``serve-*``: test teardown asserts those are all joined) and track
    it so ``wait_precompiled`` can rendezvous."""
    def work():
        try:
            fn()
        except Exception:
            pass  # precompile is best-effort; traffic just compiles lazily

    t = threading.Thread(target=work, daemon=True,
                         name=f"precompile-{tag or len(_PRECOMPILE_THREADS)}")
    with _PRECOMPILE_LOCK:
        _PRECOMPILE_THREADS.append(t)
    t.start()


def wait_precompiled(timeout: Optional[float] = None) -> bool:
    """Join all background precompile threads; True if all finished."""
    import time

    deadline = None if timeout is None else time.monotonic() + timeout
    with _PRECOMPILE_LOCK:
        threads = list(_PRECOMPILE_THREADS)
    for t in threads:
        left = (None if deadline is None
                else max(deadline - time.monotonic(), 0.0))
        t.join(timeout=left)
        if t.is_alive():
            return False
    return True


def precompile_merged(mix, max_batch: int = 8, background: bool = False,
                      devices=None) -> None:
    """Compile the merged-stack pow2 shapes (k in 2, 4, ``max_batch``)
    and any continuous-engine stepper programs for every workload in
    ``mix`` (a list of ``(workload, payload)`` pairs), on every device
    group — scheduler-driven warm bursts can't guarantee lane coverage
    because placement keeps picking the same idle lane.  Compile time
    is a property of the process, not of the policy under test.  With
    ``background=True`` this returns immediately; rendezvous via
    ``wait_precompiled``."""
    def work():
        import contextlib

        import jax

        if devices is not None:
            devs = list(devices)
        else:
            try:
                from repro.core.hybrid_executor import detect_platform
                groups, _ = detect_platform()
                devs = [g.devices[0] for g in groups if g.devices]
            except Exception:
                devs = []
        if not devs:
            devs = [None]
        warmed = set()
        for wl, payload in mix:
            try:
                probe = make_request(wl, payload)
            except Exception:
                continue
            stepper = getattr(probe, "stepper", None)
            if stepper is not None and id(stepper) not in warmed:
                warmed.add(id(stepper))
                try:
                    stepper.warm()
                except Exception:
                    pass
            if getattr(probe, "merge", None) is None:
                continue
            for k in (2, 4, max_batch):
                try:
                    merged = probe.merge(
                        [make_request(wl, payload) for _ in range(k)])
                except Exception:
                    continue
                if merged is None:
                    continue
                for dev in devs:
                    ctx = (jax.default_device(dev) if dev is not None
                           else contextlib.nullcontext())
                    with ctx:
                        merged.spec.run_one()

    if background:
        _spawn_precompile(work, tag="merged")
    else:
        work()


def _ensure_defaults() -> None:
    if "conv" in _REGISTRY:
        return
    # every ALL_WORKLOADS entry (the paper's 13 Table-1 workloads) ...
    register("conv", _conv_spec)
    register("hist", _hist_spec)
    register("spmv", _spmv_spec)
    register("sort", _sort_spec)
    register("spgemm", _spgemm_spec)
    register("raycast", _raycast_spec)
    register("bilateral", _bilateral_spec)
    register("montecarlo", _montecarlo_spec)
    register("listrank", _listrank_spec)
    register("concomp", _concomp_spec)
    register("lbm", _lbm_spec)
    register("dither", _dither_spec)
    register("bundle", _bundle_spec)
    # ... plus the serving-only kernels
    register("attention", _attention_spec)
