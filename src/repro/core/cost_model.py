"""Analytic per-kernel cost model over a measured-per-backend
``HardwareProfile``.

The paper derives CPU/GPU work shares "empirically by studying the time
taken by the CPU and the GPU individually" (§4.5), and PR-2's autotuner
extends that empiricism to every kernel config — but at serving scale a
fresh process re-paying probe runs and a brute-force search is the
dominant first-call latency.  This module supplies the *model* side of
a model-then-measure loop (Gharaibeh et al.: a simple performance model
picks near-optimal hybrid partitions without exhaustive measurement):

* ``HardwareProfile`` — peak matmul FLOPs, streaming element-op rate,
  memory bandwidth, dispatch overhead and host-callback bandwidth,
  measured once per backend with ~100 ms of micro-probes and persisted
  in the calibration store (``REPRO_CALIB_CACHE``), replacing the
  hard-coded TPU-v5e constants of ``calibration.static_time_estimate``.
* ``CostTerms`` — per-candidate analytic work terms (flops, bytes
  moved incl. tile padding waste, grid steps, host traffic) that each
  kernel's ``ops.py`` derives from a config + shape.
* ``predict`` — roofline-style time estimate used to (1) rank autotune
  candidates so only the top-K are measured, (2) sanity-check
  cross-shape transfer seeds, and (3) seed work-share plans before any
  probe has run (``HybridExecutor.calibrate(unit_cost=...)``).

``REPRO_COST_MODEL=0`` disables everything model-driven: autotune falls
back to the full measured search and calibration falls back to probe
runs.  The model only *ranks and seeds*; measurement always has the
final word, so a bad prediction costs time, never correctness.
"""
from __future__ import annotations

import os
import threading
from dataclasses import asdict, dataclass
from typing import Dict, Optional

from repro.core.persist import JsonStore, default_calib_path

ENV_DISABLE = "REPRO_COST_MODEL"
PROFILE_VERSION = 2
_SECTION = "hardware"


def enabled() -> bool:
    return os.environ.get(ENV_DISABLE, "1").lower() not in (
        "0", "off", "false", "no")


@dataclass(frozen=True)
class CostTerms:
    """Analytic work of one kernel candidate (or one work unit).

    ``flops``/``bytes`` must include the waste a config implies (tile
    padding, halo re-reads): that waste is exactly what distinguishes
    candidates of the same algorithm.  ``steps`` is the number of grid
    steps / kernel launches (per-step overhead punishes tiny tiles).
    ``compute="matmul"`` rates the flops at the contraction peak,
    anything else at the streaming element-op rate.  ``host_bytes`` is
    traffic through a host callback (e.g. hist's ``host_bincount``).
    ``interpret_steps`` counts grid steps executed via interpret-mode
    Pallas (off-TPU validation mode): each costs a large measured
    per-step overhead on top of the roofline terms — the dominant
    cost of interpret candidates, and what makes the model rank them
    correctly against compiled XLA formulations."""
    flops: float = 0.0
    bytes: float = 0.0
    steps: int = 1
    compute: str = "elementwise"
    host_bytes: float = 0.0
    interpret_steps: int = 0


@dataclass(frozen=True)
class HardwareProfile:
    """Measured per-backend throughput terms (seconds come out of
    ``predict``).  ``measured=False`` marks the static fallback."""
    backend: str
    matmul_flops: float          # contraction peak, FLOP/s
    ew_flops: float              # streaming element-op rate, op/s
    mem_bw: float                # bytes/s, read+write
    dispatch_s: float            # per-call overhead of a trivial op
    host_bw: float               # host-callback bytes/s
    link_bw: float = 50e9        # collective link (static: 1-dev probe)
    interpret_step_s: float = 0.0   # per-grid-step interpret-Pallas cost
    measured: bool = True

    def predict(self, t: CostTerms) -> float:
        """Roofline-style execution-time estimate (seconds)."""
        rate = self.matmul_flops if t.compute == "matmul" else self.ew_flops
        roof = max(t.flops / max(rate, 1.0),
                   t.bytes / max(self.mem_bw, 1.0))
        host = t.host_bytes / max(self.host_bw, 1.0)
        interp = t.interpret_steps * self.interpret_step_s
        # per-grid-step overhead is far below a full dispatch; 1/16 is
        # a ranking heuristic, not a measurement
        return (self.dispatch_s * (1.0 + t.steps / 16.0) + roof + host
                + interp)


def tpu_v5e_profile() -> HardwareProfile:
    """The TPU v5e's published chip constants (Google Cloud, "TPU
    v5e": 197 TFLOP/s bf16, 819 GB/s HBM), for
    ``calibration.static_time_estimate`` and for ``REPRO_COST_MODEL=0``
    runs on a v5e, where nothing may be measured."""
    return HardwareProfile(backend="tpu", matmul_flops=197e12,
                           ew_flops=197e12 / 8, mem_bw=819e9,
                           dispatch_s=2e-6, host_bw=5e9, link_bw=50e9,
                           interpret_step_s=0.0, measured=False)


def is_v5e(device) -> bool:
    """JAX reports a v5e chip as ``TPU v5 lite``."""
    kind = str(getattr(device, "device_kind", "")).lower()
    return device.platform == "tpu" and ("v5 lite" in kind or "v5e" in kind)


def static_profile(device) -> HardwareProfile:
    """The unmeasured profile for ``device``: only the v5e has one.  Any
    other device raises rather than being priced as a v5e."""
    if is_v5e(device):
        return tpu_v5e_profile()
    raise ValueError(
        f"no static hardware profile for {device.platform} device "
        f"{getattr(device, 'device_kind', '?')!r} (only the TPU v5e has "
        f"one); unset {ENV_DISABLE} so the profile is measured")


# ---------------------------------------------------------------------------
# Profile measurement + persistence
# ---------------------------------------------------------------------------
def _measure_profile(backend: str) -> HardwareProfile:
    """~100 ms of micro-probes; paid once per backend per store file."""
    import jax
    import jax.numpy as jnp

    from repro.core.calibration import measure

    n = 512
    a = jnp.ones((n, n), jnp.float32)
    b = jnp.full((n, n), 0.5, jnp.float32)
    mm = jax.jit(lambda a, b: a @ b)
    t = measure(lambda: mm(a, b), warmup=2, iters=3, reduce="min")
    matmul_flops = 2.0 * n ** 3 / max(t, 1e-9)

    m = 1 << 22                                   # 16 MB f32: past cache
    x = jnp.ones((m,), jnp.float32)
    ew = jax.jit(lambda x: x * 1.0000001 + 0.5)
    t = measure(lambda: ew(x), warmup=2, iters=3, reduce="min")
    ew_flops = 2.0 * m / max(t, 1e-9)
    mem_bw = 8.0 * m / max(t, 1e-9)               # read + write

    tiny = jnp.ones((8,), jnp.float32)
    f = jax.jit(lambda x: x + 1.0)
    dispatch_s = measure(lambda: f(tiny), warmup=3, iters=10, reduce="min")

    h = 1 << 18                                   # 1 MB through a callback
    xs = jnp.ones((h,), jnp.float32)
    cb = jax.jit(lambda x: jax.pure_callback(
        lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x))
    t = measure(lambda: cb(xs), warmup=1, iters=3, reduce="min")
    host_bw = 8.0 * h / max(t, 1e-9)
    return HardwareProfile(backend=backend, matmul_flops=matmul_flops,
                           ew_flops=ew_flops, mem_bw=mem_bw,
                           dispatch_s=max(dispatch_s, 1e-9),
                           host_bw=host_bw,
                           interpret_step_s=_probe_interpret_step(backend))


def _probe_interpret_step(backend: str) -> float:
    """Per-grid-step overhead of interpret-mode Pallas (the off-TPU
    validation mode): slope of a trivial kernel's time in its grid
    size.  On TPU the kernels compile, so the term is zero."""
    if backend == "tpu":
        return 0.0
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from repro.core.calibration import measure

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    x = jnp.zeros((128, 128), jnp.float32)

    def timed(grid):
        f = pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            grid=(grid,),
            in_specs=[pl.BlockSpec((128, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0)),
            interpret=True)
        g = jax.jit(f)
        return measure(lambda: g(x), warmup=1, iters=3, reduce="min")

    return max((timed(9) - timed(1)) / 8.0, 0.0)


_STORE: Optional[JsonStore] = None
_STORE_PATH: Optional[str] = None
_PROFILES: Dict[str, HardwareProfile] = {}
_LOCK = threading.Lock()


def _store() -> JsonStore:
    """Hardware-section store; re-resolved when REPRO_CALIB_CACHE
    changes (tests point it at tmp dirs)."""
    global _STORE, _STORE_PATH
    path = default_calib_path()
    with _LOCK:
        if _STORE is None or _STORE_PATH != path:
            _STORE = JsonStore(path)
            _STORE_PATH = path
            _PROFILES.clear()
        return _STORE


def get_profile(device=None) -> HardwareProfile:
    """The profile of ``device`` (default: the device the call runs on,
    ``core.device``), one per platform: memory -> store file ->
    measured on that device (and persisted).  With the model disabled,
    the static profile — never a measurement, and only for a v5e."""
    import jax

    from repro.core.device import current_device
    dev = current_device(device)
    backend = dev.platform
    if not enabled():
        return static_profile(dev)
    store = _store()
    with _LOCK:
        prof = _PROFILES.get(backend)
        if prof is not None:
            return prof
    with store.lock:
        entry = store.data().get(_SECTION, {}).get(backend)
        if (isinstance(entry, dict) and entry.get("v") == PROFILE_VERSION):
            fields = {k: v for k, v in entry.items() if k != "v"}
            try:
                prof = HardwareProfile(**fields)
            except TypeError:
                prof = None
        else:
            prof = None
    if prof is None:
        with jax.default_device(dev):
            prof = _measure_profile(backend)
        with store.lock:
            store.data().setdefault(_SECTION, {})[backend] = {
                **asdict(prof), "v": PROFILE_VERSION}
            store.flush()
    with _LOCK:
        _PROFILES[backend] = prof
    return prof


def reset_profiles() -> None:
    """Forget memoized profiles and the store binding (tests)."""
    global _STORE, _STORE_PATH
    with _LOCK:
        _STORE = None
        _STORE_PATH = None
        _PROFILES.clear()


def predict(terms: CostTerms, device=None) -> float:
    """Convenience: the time estimate on ``device``'s profile."""
    return get_profile(device).predict(terms)


# ---------------------------------------------------------------------------
# LM serving priors (prefill/decode disaggregation)
# ---------------------------------------------------------------------------
def lm_prefill_terms(n_params: float, prompt_len: int) -> CostTerms:
    """Prior for one LM prefill of ``prompt_len`` tokens: ~2*params
    matmul FLOPs per token against one streaming read of the weights —
    compute-bound for any non-trivial prompt, which is why
    disaggregation wants prefill on the fastest-matmul lane."""
    return CostTerms(flops=2.0 * n_params * max(int(prompt_len), 1),
                     bytes=4.0 * n_params, compute="matmul")


def lm_decode_terms(n_params: float, n_steps: int = 1) -> CostTerms:
    """Prior for ``n_steps`` single-token decode steps: each step does
    ~2*params FLOPs but re-reads every weight, so flops ~= bytes/2 and
    the roofline lands on the bandwidth leg — the decode-roofline prior
    ``launch/serve.py`` uses for hybrid LM placement."""
    n = max(int(n_steps), 1)
    return CostTerms(flops=2.0 * n_params * n, bytes=4.0 * n_params * n,
                     steps=n, compute="matmul")
