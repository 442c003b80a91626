"""Core layers: norms, linear, MLP/GLU, embeddings, RoPE."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.param import (dense_init, embed_init, ones_init,
                                zeros_init)
from repro.parallel.sharding import shard_act

ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
}


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_norm(cfg, dim: int = 0):
    d = dim or cfg.d_model
    p = {"scale": ones_init((d,), (None,))}
    if cfg.norm_type == "layernorm":
        p["bias"] = zeros_init((d,), (None,))
    return p


def norm(params, x, cfg):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + cfg.norm_eps)
    out = x * params["scale"].astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        out = out + params["bias"].astype(jnp.float32)
    return out.astype(dtype)


def rms_norm_simple(x, scale, eps: float = 1e-6):
    """Scale-only RMS norm over the last dim (for QK-norm etc.)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Linear
# ---------------------------------------------------------------------------
def init_linear(key, d_in: int, d_out: int, axes, use_bias: bool = False,
                scale: float = 1.0):
    p = {"w": dense_init(key, (d_in, d_out), axes, scale=scale)}
    if use_bias:
        p["b"] = zeros_init((d_out,), (axes[1],))
    return p


def linear(params, x):
    w = params["w"].astype(x.dtype)
    y = x @ w
    if "b" in params:
        y = y + params["b"].astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# MLP (gated or plain)
# ---------------------------------------------------------------------------
def init_mlp(key, cfg, d_ff: int = 0):
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "up": init_linear(k1, cfg.d_model, d_ff, ("embed", "mlp"), cfg.use_bias),
        "down": init_linear(k2, d_ff, cfg.d_model, ("mlp", "embed"), cfg.use_bias),
    }
    if cfg.mlp_gated:
        p["gate"] = init_linear(k3, cfg.d_model, d_ff, ("embed", "mlp"), cfg.use_bias)
    return p


def mlp(params, x, cfg):
    act = ACTS[cfg.act]
    h = linear(params["up"], x)
    if "gate" in params:
        h = h * act(linear(params["gate"], x))
    else:
        h = act(h)
    h = shard_act(h, ("batch", None, "mlp"))
    return linear(params["down"], h)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def init_embedding(key, cfg):
    return {"table": embed_init(key, (cfg.vocab_size, cfg.d_model),
                                ("vocab", "embed"))}


def embed(params, token_ids, cfg):
    return params["table"].astype(jnp.bfloat16)[token_ids].astype(jnp.bfloat16)


def init_unembed(key, cfg):
    return {"w": dense_init(key, (cfg.d_model, cfg.vocab_size),
                            ("embed", "vocab"), fan_in=cfg.d_model)}


def unembed(params, x, cfg, embed_params=None):
    if cfg.tie_embeddings and embed_params is not None:
        w = embed_params["table"].astype(x.dtype).T
    else:
        w = params["w"].astype(x.dtype)
    logits = x @ w
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_table(dim: int, max_len: int, theta: float = 10000.0,
               positions: Optional[jnp.ndarray] = None, scaling=None):
    """Paper §4.6 'LUT on the host' analogue: the sin/cos table is a pure
    function of (dim, theta) and is precomputed once (host task) rather
    than re-evaluated per step (see core.host_offload).  ``scaling``
    (a ``YarnConfig``) gives YaRN's frequencies and amplitude."""
    if positions is None:
        positions = jnp.arange(max_len)
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    amp = 1.0
    if scaling is not None:
        freqs = freqs * yarn_freq_factor(dim, theta, scaling)
        amp = (yarn_mscale(scaling.factor, scaling.mscale)
               / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., L, dim/2)
    return jnp.sin(ang) * amp, jnp.cos(ang) * amp


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term ``0.1 * mscale * ln(factor) + 1``
    (1 where the positions are not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freq_factor(dim: int, theta: float, y) -> jnp.ndarray:
    """(dim/2,) multipliers of the plain rotary frequencies: 1 for the
    pairs that turn more than ``beta_fast`` times over the original
    context, ``1/factor`` for those that turn fewer than ``beta_slow``
    times, a linear ramp in pair index between (DeepSeek-V2's rounding of
    the ramp's ends)."""
    def pair(n_rot):
        return (dim * math.log(y.original_max_position_embeddings
                               / (n_rot * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(pair(y.beta_fast)), 0)
    hi = min(math.ceil(pair(y.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) + ramp / y.factor


def softmax_scale(cfg, head_dim: int) -> float:
    """``head_dim ** -0.5``, times YaRN's ``mscale(factor,
    mscale_all_dim) ** 2`` where the config scales its rotary positions
    (DeepSeek-V2)."""
    scale = head_dim ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def apply_rope(x, sin, cos):
    """x: (..., L, H, dh); sin/cos: (L, dh/2) or broadcastable.  Pair i
    is (x[i], x[i + dh/2]): the half-split layout.  A checkpoint that
    pairs adjacent dims (DeepSeek-V2's) maps to it by de-interleaving the
    rope columns of its q and k projections."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    if sin.ndim == 2:  # (L, dh/2) -> broadcast over batch and heads
        sin = sin[None, :, None, :]
        cos = cos[None, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)
