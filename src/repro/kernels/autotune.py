"""Kernel autotuning: measured search over per-kernel config spaces.

The paper's methodological core (vs Lee et al., ISCA 2010) is that a
platform comparison is only meaningful when each kernel is *tuned to
its platform* — the reported 90% resource efficiency comes from that
tuning, not from scheduling.  This module is the repo's measured-search
layer beneath the PR-1 scheduler: every kernel package exposes a small
config space (implementation variant, tile/block sizes, grid shape,
accumulate dtype) and ``autotune`` picks the best-measured candidate
per (kernel, backend, shape-bucket).

Design follows ``core/calibration.py:CalibrationCache`` — a process-wide
singleton keyed store — extended with on-disk JSON persistence so
steady-state *processes* pay zero search cost: the first run searches
and writes the cache file, every later run (and every later call in the
same process) is a pure lookup.

Since PR 3 the brute-force search is cost-model-seeded: each kernel's
``ops.py`` supplies an analytic ``cost_fn`` (flops, bytes incl. tile
padding waste, grid steps — see ``core/cost_model.py``) and the search
measures only the model's top-K candidates, always including every
implementation family's best-predicted member (the model ranks *within*
a family far better than across families, so family coverage is what
keeps the measured winner in the set).  New shape buckets are seeded by
*cross-shape transfer*: the nearest already-tuned bucket's winner is
measured once and adopted, instead of a fresh search.

Escape hatches (reproducibility / CI pinning):

* ``REPRO_AUTOTUNE=0``        — disable search, use each kernel's default
* ``REPRO_TUNE_CACHE=<path>`` — cache file location
  (default ``~/.cache/repro/autotune.json``)
* ``REPRO_TUNE_PIN_<KERNEL>='{"impl": ..., ...}'`` — pin one kernel's
  config (merged over its default; no search, no cache)
* ``REPRO_TUNE_TOPK=<n>``     — measured candidates per search (default
  2, with every impl family's best always included; 0 = measure
  everything, the pre-PR-3 full search)
* ``REPRO_TUNE_TRANSFER=0``   — disable cross-shape transfer seeding
* ``REPRO_COST_MODEL=0``      — disable the model entirely (full
  search, no ranking; see core/cost_model.py)

Timing uses ``core.calibration.measure`` (block_until_ready discipline,
min-of-N for search robustness); tests inject a deterministic timer via
``set_timer``.
"""
from __future__ import annotations

import math
import json
import os
import re
import sys
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from repro.core.device import platform
from repro.core.persist import JsonStore

Config = Dict[str, Any]
Timer = Callable[[Callable[[], Any]], float]
CostFn = Callable[[Config], Any]          # -> core.cost_model.CostTerms

ENV_DISABLE = "REPRO_AUTOTUNE"
ENV_CACHE = "REPRO_TUNE_CACHE"
ENV_PIN_PREFIX = "REPRO_TUNE_PIN_"
ENV_TOPK = "REPRO_TUNE_TOPK"
ENV_TRANSFER = "REPRO_TUNE_TRANSFER"
# family coverage is the floor, not the slot count: every impl
# family's best-predicted member is always measured (see
# _select_top_k), so K=2 means "family bests, plus a spare slot when
# there are fewer than 2 families" — raise REPRO_TUNE_TOPK to widen
DEFAULT_TOPK = 2


def default_cache_path() -> str:
    return os.environ.get(ENV_CACHE) or os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "autotune.json")


def bucket(n: int) -> int:
    """Shape bucket: next power of two (so nearby shapes share a tune)."""
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def freeze(config: Config) -> Tuple[Tuple[str, Any], ...]:
    """Hashable view of a config, for jit static args."""
    return tuple(sorted(config.items()))


def thaw(frozen: Sequence[Tuple[str, Any]]) -> Config:
    return dict(frozen)


def is_tracer(x: Any) -> bool:
    """True when ``x`` is an abstract value inside a jit/vmap trace —
    timing it would measure tracing, not execution, so ops fall back
    to ``cached_or_default`` resolution."""
    import jax.core
    return isinstance(x, jax.core.Tracer)


class TuneCache:
    """Persistent (kernel, backend, shape-bucket) -> config store.

    Layout mirrors the JSON file:
    ``{backend: {kernel: {bucket: {"config": {...}, "us": float}}}}``
    (transfer-seeded entries also carry ``"via": "transfer:<bucket>"``).
    Persistence (lazy load, merge-on-write so concurrent processes
    tuning different kernels never lose updates, atomic replace,
    corrupt-file tolerance) comes from ``core.persist.JsonStore``."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self._disk = JsonStore(self.path)

    def get(self, backend: str, kernel: str, shape_bucket: str
            ) -> Optional[dict]:
        with self._disk.lock:
            entry = (self._disk.data().get(backend, {}).get(kernel, {})
                     .get(shape_bucket))
            return dict(entry) if isinstance(entry, dict) else None

    def buckets(self, backend: str, kernel: str) -> Dict[str, dict]:
        """All tuned buckets for (backend, kernel) — transfer seeding."""
        with self._disk.lock:
            buckets = self._disk.data().get(backend, {}).get(kernel, {})
            return {b: dict(e) for b, e in buckets.items()
                    if isinstance(e, dict) and isinstance(
                        e.get("config"), dict)}

    def put(self, backend: str, kernel: str, shape_bucket: str,
            config: Config, us: float, via: Optional[str] = None) -> None:
        entry = {"config": dict(config), "us": round(float(us), 3)}
        if via:
            entry["via"] = via
        with self._disk.lock:
            self._disk.data().setdefault(backend, {}).setdefault(
                kernel, {})[shape_bucket] = entry
            self._disk.flush()

    def clear(self) -> None:
        self._disk.clear()


_GLOBAL: Optional[TuneCache] = None
_GLOBAL_PATH: Optional[str] = None
_CACHE_LOCK = threading.Lock()


def get_tune_cache() -> TuneCache:
    """Process-wide cache; re-resolved when REPRO_TUNE_CACHE changes
    (tests point it at tmp dirs)."""
    global _GLOBAL, _GLOBAL_PATH
    path = default_cache_path()
    with _CACHE_LOCK:
        if _GLOBAL is None or _GLOBAL_PATH != path:
            _GLOBAL = TuneCache(path)
            _GLOBAL_PATH = path
        return _GLOBAL


def reset_tune_cache() -> None:
    global _GLOBAL, _GLOBAL_PATH
    with _CACHE_LOCK:
        _GLOBAL = None
        _GLOBAL_PATH = None


_TIMER_OVERRIDE: Optional[Timer] = None


def set_timer(timer: Optional[Timer]) -> Optional[Timer]:
    """Install a timer (seconds per call) for the search; returns the
    previous override so tests can restore it."""
    global _TIMER_OVERRIDE
    prev = _TIMER_OVERRIDE
    _TIMER_OVERRIDE = timer
    return prev


def _default_timer(fn: Callable[[], Any]) -> float:
    from repro.core.calibration import measure
    return measure(fn, warmup=1, iters=2, reduce="min")


def default_config(seed: Config, safe: Config) -> Config:
    """The no-search config (REPRO_AUTOTUNE=0 / all candidates failed):
    the hand-written Pallas kernel with its seed tiles on TPU —
    disabling *search* must not silently swap the platform
    implementation — and the XLA formulation elsewhere (interpret-mode
    Pallas is never a sane default off-TPU).  The TPU is the platform
    of the device the call runs on (``core.device``)."""
    return dict(seed) if platform() == "tpu" else dict(safe)


def search_enabled() -> bool:
    return os.environ.get(ENV_DISABLE, "1").lower() not in (
        "0", "off", "false", "no")


def top_k() -> int:
    """Measured candidates per search; 0 = full (unranked) search."""
    try:
        return max(int(os.environ.get(ENV_TOPK, DEFAULT_TOPK)), 0)
    except ValueError:
        return DEFAULT_TOPK


def transfer_enabled() -> bool:
    return os.environ.get(ENV_TRANSFER, "1").lower() not in (
        "0", "off", "false", "no")


def pinned_config(kernel: str) -> Optional[Config]:
    raw = os.environ.get(ENV_PIN_PREFIX + kernel.upper().replace("-", "_"))
    if not raw:
        return None
    try:
        cfg = json.loads(raw)
        return cfg if isinstance(cfg, dict) else None
    except ValueError:
        return None


# ---------------------------------------------------------------------------
# Cost-model ranking + cross-shape transfer
# ---------------------------------------------------------------------------
_BUCKET_SEG = re.compile(r"([A-Za-z]+)(\d+)")


def _bucket_dims(bucket: str) -> Dict[str, int]:
    return {m.group(1): int(m.group(2))
            for m in _BUCKET_SEG.finditer(bucket)}


def nearest_bucket(buckets: Dict[str, dict], target: str
                   ) -> Optional[Tuple[str, dict]]:
    """Closest tuned bucket to ``target`` by log-space shape distance
    (buckets are pow-2, so log2 deltas count bucket hops).  Only
    buckets with the same dimension names are comparable, and a
    0-vs-1 mismatch is a *boolean flag* (e.g. attention's causal bit),
    not a size hop: those variants have different candidate spaces and
    non-transferable winners, so they never seed each other."""
    tgt = _bucket_dims(target)
    if not tgt:
        return None
    best = None
    for b, entry in buckets.items():
        if b == target:
            continue
        dims = _bucket_dims(b)
        if set(dims) != set(tgt):
            continue
        if any(dims[k] != tgt[k] and dims[k] <= 1 and tgt[k] <= 1
               for k in tgt):
            continue
        d = sum(abs(math.log2(dims[k] + 1) - math.log2(tgt[k] + 1))
                for k in tgt)
        if best is None or d < best[0]:
            best = (d, b, entry)
    return (best[1], best[2]) if best else None


def _select_top_k(cands: List[Config], predict, k: int) -> List[Config]:
    """The model's K best candidates — but every implementation
    family's best-predicted member is always included (the model ranks
    *within* a family far better than across families; coverage is
    what keeps the true winner measurable), so the result can exceed
    ``k`` when there are more families than slots."""
    scored = []
    for i, c in enumerate(cands):
        try:
            s = float(predict(c))
        except Exception:
            s = math.inf
        scored.append((s, i, c))
    scored.sort(key=lambda x: (x[0], x[1]))
    chosen_idx: List[int] = []
    seen_fam = set()
    for s, i, c in scored:
        fam = c.get("impl", "?")
        if fam not in seen_fam:
            seen_fam.add(fam)
            chosen_idx.append(i)
    for s, i, c in scored:
        if len(chosen_idx) >= max(k, len(seen_fam)):
            break
        if i not in chosen_idx:
            chosen_idx.append(i)
    return [cands[i] for i in chosen_idx]


def _make_predict(cost_fn: Optional[CostFn]):
    """Config -> predicted seconds, or None when the model is off."""
    if cost_fn is None:
        return None
    from repro.core import cost_model
    if not cost_model.enabled():
        return None
    profile = cost_model.get_profile()
    return lambda cfg: profile.predict(cost_fn(cfg))


def _report_refusal(kernel: str, cfg: Config, exc: BaseException) -> None:
    """A candidate the backend refused (a tiling its compiler rejects)
    is skipped, but never silently: kernel, config and the error's
    first line go to stderr."""
    lines = str(exc).strip().splitlines()
    first = lines[0] if lines else ""
    print(f"autotune: {kernel} on {platform()} refused "
          f"{json.dumps(cfg, sort_keys=True, default=str)}: "
          f"{type(exc).__name__}: {first}", file=sys.stderr, flush=True)


def autotune(kernel: str, shape_bucket: str, candidates: Sequence[Config],
             make_fn: Callable[[Config], Callable[[], Any]],
             default: Config, *, timer: Optional[Timer] = None,
             cost_fn: Optional[CostFn] = None) -> Config:
    """Best-measured config for (kernel, backend, shape_bucket).

    Zero-search paths, in priority order: pinned via env, search
    disabled via env, cache hit (memory or disk).  A miss with a
    *sibling* tuned bucket present seeds by cross-shape transfer: the
    nearest bucket's winner is measured once and adopted (unless the
    cost model says it is a bad fit for this shape — >2x the best
    predicted candidate — in which case the search runs).  Otherwise
    candidates (merged over ``default``) are built with ``make_fn``
    and timed — all of them, or only the model's top-K when a
    ``cost_fn`` is supplied (see ``_select_top_k``).  Failing
    candidates (e.g. a tiling the backend rejects) are skipped and
    reported on stderr.  The winner persists to the tune cache."""
    default = dict(default)
    pin = pinned_config(kernel)
    if pin is not None:
        return {**default, **pin}
    if not search_enabled():
        return default

    backend = platform()
    cache = get_tune_cache()
    hit = cache.get(backend, kernel, shape_bucket)
    if hit is not None and isinstance(hit.get("config"), dict):
        return {**default, **hit["config"]}

    tmr = timer or _TIMER_OVERRIDE or _default_timer
    merged = [{**default, **c} for c in candidates]
    predict = _make_predict(cost_fn)

    if transfer_enabled():
        near = nearest_bucket(cache.buckets(backend, kernel), shape_bucket)
        if near is not None:
            near_bkt, near_entry = near
            t_cfg = {**default, **near_entry["config"]}
            fit = True
            if predict is not None and merged:
                # shape-fit guard, *within the transferred config's own
                # impl family*: cross-family predictions are exactly
                # where the model is weakest (that is why the top-K
                # search keeps family coverage), but a sibling's tiling
                # that implies huge padding waste at THIS shape should
                # trigger a real search instead
                fam = t_cfg.get("impl")
                pool = [c for c in merged
                        if c.get("impl") == fam] or merged
                try:
                    best_pred = min(predict(c) for c in pool)
                    fit = predict(t_cfg) <= 2.0 * best_pred
                except Exception:
                    fit = True
            if fit:
                try:
                    t = tmr(make_fn(dict(t_cfg)))
                    cache.put(backend, kernel, shape_bucket, t_cfg,
                              t * 1e6, via=f"transfer:{near_bkt}")
                    return t_cfg
                except Exception as e:      # bad seed: fall back to search
                    _report_refusal(kernel, t_cfg, e)

    k = top_k()
    if predict is not None and k > 0 and len(merged) > k:
        merged = _select_top_k(merged, predict, k)

    best_cfg: Config = default
    best_t = math.inf
    for cfg in merged:
        try:
            t = tmr(make_fn(cfg))
        except Exception as e:
            _report_refusal(kernel, cfg, e)
            continue
        if t < best_t:
            best_t, best_cfg = t, cfg
    if not math.isfinite(best_t):
        # every candidate failed: fall back to the default, don't cache
        return default
    cache.put(backend, kernel, shape_bucket, best_cfg, best_t * 1e6)
    return best_cfg


def cached_or_default(kernel: str, shape_bucket: str, default: Config
                      ) -> Config:
    """Zero-search config resolution: pin > cache hit > default.

    Never times anything, so it is safe inside jitted/vmapped code
    where shapes are tracers — the model layers (models/attention,
    models/moe) resolve their tuned configs this way; the cache is
    warmed by the benchmarks/workloads that run the same shapes
    eagerly."""
    default = dict(default)
    pin = pinned_config(kernel)
    if pin is not None:
        return {**default, **pin}
    if not search_enabled():
        return default
    hit = get_tune_cache().get(platform(), kernel, shape_bucket)
    if hit is not None and isinstance(hit.get("config"), dict):
        return {**default, **hit["config"]}
    return default


def tuned_entry(kernel: str, shape_bucket: str) -> Optional[dict]:
    """Cache entry (config + measured us) if present — benchmark
    reporting helper; never triggers a search."""
    return get_tune_cache().get(platform(), kernel, shape_bucket)
