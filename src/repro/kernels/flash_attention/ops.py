"""Jitted public wrapper for flash attention (GQA-aware), autotuned."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.cost_model import CostTerms
from repro.kernels.autotune import (Config, autotune, bucket,
                                    cached_or_default, default_config,
                                    freeze, is_tracer, pinned_config,
                                    search_enabled, tuned_entry)
from repro.kernels.flash_attention.flash_attention import (
    attention_blocked_xla, flash_attention_pallas)
from repro.kernels.flash_attention.ref import attention_ref

# Seed constants (PR 1).
SEED_CONFIG: Config = {"impl": "pallas", "block_q": 512, "block_k": 512}
# Default when search is disabled: the unblocked oracle.
DEFAULT_CONFIG: Config = {"impl": "xla_ref", "block_q": 512, "block_k": 512}


def candidates(T: int, S: int, d: int, causal: bool = True):
    # block sizes clamp to min(block, T/S) inside the kernels, so any
    # candidate whose blocks both exceed the sequence is a duplicate of
    # the clamped one — prune rather than time it twice.  For causal
    # shapes xla_ref is strictly dominated (it is xla_blocked with one
    # block, minus the causal prefix skip), so it only enters the
    # non-causal search
    cands = [] if causal else [{"impl": "xla_ref"}]
    for bq in (128, 256, 512):
        if bq // 2 < T:
            cands.append({"impl": "xla_blocked", "block_q": bq})
    for bq in (256, 512):
        for bk in (256, 512):
            if bq // 2 < T or bk // 2 < S:
                cands.append({"impl": "pallas", "block_q": bq,
                              "block_k": bk})
    if not cands:
        # tiny causal shapes prune everything above; a single-block
        # xla_blocked (block_q clamps to T) IS the reference
        cands.append({"impl": "xla_blocked", "block_q": 128})
    return cands


@functools.partial(jax.jit, static_argnames=("causal", "cfg"))
def _attn_cfg(qf, kf, vf, causal: bool, cfg):
    c = dict(cfg)
    impl = c.get("impl", "pallas")
    if impl == "xla_ref":
        return attention_ref(qf, kf, vf, causal=causal)
    if impl == "xla_blocked":
        return attention_blocked_xla(qf, kf, vf, causal=causal,
                                     block_q=int(c.get("block_q", 256)))
    return flash_attention_pallas(qf, kf, vf, causal=causal,
                                  block_q=int(c.get("block_q", 512)),
                                  block_k=int(c.get("block_k", 512)))


def shape_bucket(BH: int, T: int, S: int, d: int, causal: bool) -> str:
    # causal is part of the key: xla_blocked wins on causal inputs by
    # skipping ~half the FLOPs, a win that does not transfer to
    # causal=False calls of the same shape
    return f"BH{bucket(BH)}_T{bucket(T)}_S{bucket(S)}_D{d}_c{int(causal)}"


def _flatten_gqa(q, k, v):
    B, T, H, d = q.shape
    S, Kv = k.shape[1], k.shape[2]
    rep = H // Kv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    return qf, kf, vf


def _granularity(block: int) -> float:
    """Contraction-efficiency penalty for small blocks: matmuls under
    ~256 wide stop amortizing per-block overheads (measured: blocked
    attention at block_q=128 runs ~20% slower than 256 despite fewer
    FLOPs)."""
    return min(1.0, block / 256.0)


def cost_terms(cfg: Config, BH: int, T: int, S: int, d: int,
               causal: bool) -> CostTerms:
    """Analytic work of one candidate (ranks the autotune search)."""
    impl = cfg.get("impl", "pallas")
    base = 4.0 * BH * T * S * d                    # QK^T + PV
    if impl == "xla_ref":
        # full score matrix materialized, causal or not
        return CostTerms(flops=base,
                         bytes=4.0 * BH * (2 * T * S + 2 * (T + 2 * S) * d),
                         compute="matmul")
    if impl == "xla_blocked":
        bq = min(max(int(cfg.get("block_q", 256)), 1), T)
        nb = -(-T // bq)
        # exact causal prefix-block factor: block i attends i+1 blocks,
        # so sum(bq * klim_i) = T*S*(nb+1)/(2*nb) — finer blocks skip
        # more of the triangle but lose contraction granularity
        cf = (nb + 1) / (2.0 * nb) if causal else 1.0
        return CostTerms(flops=base * cf / _granularity(bq),
                         bytes=4.0 * BH * (2 * T * S * cf
                                           + 2 * (T + 2 * S) * d),
                         steps=nb, compute="matmul")
    bq = min(max(int(cfg.get("block_q", 512)), 1), T)
    bk = min(max(int(cfg.get("block_k", 512)), 1), S)
    nq, nk = -(-T // bq), -(-S // bk)
    from repro.kernels.common import default_interpret
    # online softmax: no score matrix in memory, K/V re-read per Q block
    return CostTerms(flops=4.0 * BH * (nq * bq) * (nk * bk) * d
                     / min(_granularity(bq), _granularity(bk)),
                     bytes=4.0 * BH * (2 * T * d + nq * 2 * S * d),
                     steps=nq * nk, compute="matmul",
                     interpret_steps=nq * nk if default_interpret() else 0)


def _tuned_config_flat(qf, kf, vf, causal: bool) -> Config:
    BH, T, d = qf.shape
    S = kf.shape[1]
    default = default_config(SEED_CONFIG, DEFAULT_CONFIG)
    if is_tracer(qf):
        return cached_or_default(
            "flash_attention", shape_bucket(BH, T, S, d, causal), default)
    return autotune(
        "flash_attention", shape_bucket(BH, T, S, d, causal),
        candidates(T, S, d, causal),
        lambda cfg: lambda: _attn_cfg(qf, kf, vf, causal, freeze(cfg)),
        default,
        cost_fn=lambda cfg: cost_terms(cfg, BH, T, S, d, causal))


def tuned_config(q, k, v, *, causal: bool = True) -> Config:
    return _tuned_config_flat(*_flatten_gqa(q, k, v), causal)


def _differentiable(cfg: Config, causal: bool) -> Config:
    """Pallas kernels define no VJP; model layers that are
    differentiated map a pallas winner onto the nearest differentiable
    XLA formulation (the blocked causal path keeps most of the win)."""
    if cfg.get("impl") == "pallas":
        return {**cfg, "impl": "xla_blocked" if causal else "xla_ref"}
    return cfg


def model_config(q, k, v, *, causal: bool = True) -> Optional[Config]:
    """The resolved differentiable config when a pin or cache hit
    exists for this shape bucket, else None — pure lookup, tracer-safe.
    Model layers route through the kernel path only on a hit: the sdpa
    flattening repeats GQA K/V heads (extra bandwidth the grouped
    einsum never pays), a cost worth paying only for a config that
    measured as a win.  Pass the result to ``sdpa(config=...)`` so the
    lookup happens once per trace."""
    default = default_config(SEED_CONFIG, DEFAULT_CONFIG)
    pin = pinned_config("flash_attention")
    if pin is not None:
        return _differentiable({**default, **pin}, causal)
    if not search_enabled():
        return None
    B, T, H, d = q.shape
    S = k.shape[1]
    hit = tuned_entry("flash_attention",
                      shape_bucket(B * H, T, S, d, causal))
    if hit is None or not isinstance(hit.get("config"), dict):
        return None
    return _differentiable({**default, **hit["config"]}, causal)


def sdpa(q, k, v, *, causal: bool = True,
         config: Optional[Config] = None):
    """Model-layer attention through the tuned config.

    q: (B, T, H, d); k/v: (B, S, Kv, d) with H % Kv == 0; plain causal
    (or no) masking only — sliding windows, softcaps and decode ring
    buffers stay on the layers' einsum path.  ``config`` comes from
    ``model_config`` (or None to re-resolve: cache-hit-or-default,
    never a timed search, restricted to differentiable impls), so
    jitted train/prefill steps can call it directly.
    Returns (B, T, H, d)."""
    B, T, H, d = q.shape
    qf, kf, vf = _flatten_gqa(q, k, v)
    BH, S = qf.shape[0], kf.shape[1]
    if config is None:
        config = _differentiable(cached_or_default(
            "flash_attention", shape_bucket(BH, T, S, d, causal),
            default_config(SEED_CONFIG, DEFAULT_CONFIG)), causal)
    of = _attn_cfg(qf, kf, vf, causal, freeze(config))
    return of.reshape(B, H, T, d).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal: bool = True, use_kernel: bool = True,
                    config: Optional[Config] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """q: (B, T, H, d); k/v: (B, S, Kv, d) with H % Kv == 0.

    config=None -> autotuned; explicit block_q/block_k force the Pallas
    path with those blocks (legacy API).  Returns (B, T, H, d)."""
    B, T, H, d = q.shape
    qf, kf, vf = _flatten_gqa(q, k, v)
    if not use_kernel:
        of = _attn_cfg(qf, kf, vf, causal, freeze({"impl": "xla_ref"}))
    else:
        if config is None:
            if block_q is not None or block_k is not None:
                config = {"impl": "pallas",
                          "block_q": block_q or SEED_CONFIG["block_q"],
                          "block_k": block_k or SEED_CONFIG["block_k"]}
            else:
                config = _tuned_config_flat(qf, kf, vf, causal)
        of = _attn_cfg(qf, kf, vf, causal, freeze(config))
    return of.reshape(B, H, T, d).transpose(0, 2, 1, 3)
