"""Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3).

Train/prefill use the naive (expanded) form; decode uses the *absorbed*
form working directly in the latent space so the cache is just
``(c_kv, k_rope)`` — the memory-term win that makes MLA interesting for
the roofline analysis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import (apply_rope, init_linear, linear,
                                 rms_norm_simple, softmax_scale)
from repro.models.param import ones_init
from repro.parallel.sharding import shard_act


def _dims(cfg):
    m = cfg.mla
    return m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim, m.kv_lora_rank


def init_mla(key, cfg):
    dn, dr, dv, kvl = _dims(cfg)
    H = cfg.n_heads
    ks = jax.random.split(key, 6)
    p = {}
    if cfg.mla.q_lora_rank:
        p["wq_a"] = init_linear(ks[0], cfg.d_model, cfg.mla.q_lora_rank,
                                ("embed", "q_lora"))
        p["q_norm"] = ones_init((cfg.mla.q_lora_rank,), (None,))
        p["wq_b"] = init_linear(ks[1], cfg.mla.q_lora_rank, H * (dn + dr),
                                ("q_lora", "q_hidden"))
    else:
        p["wq"] = init_linear(ks[0], cfg.d_model, H * (dn + dr),
                              ("embed", "q_hidden"))
    p["wkv_a"] = init_linear(ks[2], cfg.d_model, kvl + dr, ("embed", None))
    p["kv_norm"] = ones_init((kvl,), (None,))
    p["wkv_b"] = init_linear(ks[3], kvl, H * (dn + dv), ("kv_lora", "q_hidden"))
    p["wo"] = init_linear(ks[4], H * dv, cfg.d_model, ("q_hidden", "embed"))
    return p


def _queries(params, x, cfg, sin, cos):
    dn, dr, dv, kvl = _dims(cfg)
    B, T, _ = x.shape
    H = cfg.n_heads
    if cfg.mla.q_lora_rank:
        ql = rms_norm_simple(linear(params["wq_a"], x), params["q_norm"],
                             cfg.norm_eps)
        q = linear(params["wq_b"], ql)
    else:
        q = linear(params["wq"], x)
    q = q.reshape(B, T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, sin, cos)
    return q_nope, q_rope


def _latent_kv(params, x, cfg, sin, cos):
    dn, dr, dv, kvl = _dims(cfg)
    kv = linear(params["wkv_a"], x)
    c_kv, k_rope = kv[..., :kvl], kv[..., kvl:]
    c_kv = rms_norm_simple(c_kv, params["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], sin, cos)[:, :, 0]  # shared head
    return c_kv, k_rope


@jax.named_scope("mla")
def mla_attention(params, x, cfg, *, sin=None, cos=None,
                  make_cache_len: int = 0, kv_repeat: int = 1):
    """Naive (expanded) MLA for train/prefill. Returns (y, cache)."""
    dn, dr, dv, kvl = _dims(cfg)
    B, T, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope = _queries(params, x, cfg, sin, cos)
    c_kv, k_rope = _latent_kv(params, x, cfg, sin, cos)
    kv = linear(params["wkv_b"], c_kv).reshape(B, T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]

    scale = softmax_scale(cfg, dn + dr)
    s = (jnp.einsum("bthd,bshd->bhts", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope,
                      preferred_element_type=jnp.float32)) * scale
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(mask[None, None], s, -1e30)
    w = jax.nn.softmax(s.astype(jnp.float32), -1).astype(x.dtype)
    out = jnp.einsum("bhts,bshd->bthd", w, v).reshape(B, T, H * dv)
    y = linear(params["wo"], out)
    cache = None
    if make_cache_len:
        pad = make_cache_len - T
        cache = {"ckv": jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
                 "kr": jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))}
    return y, cache


def init_mla_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    dn, dr, dv, kvl = _dims(cfg)
    return {"ckv": jnp.zeros((batch, max_len, kvl), dtype),
            "kr": jnp.zeros((batch, max_len, dr), dtype)}


@jax.named_scope("mla")
def mla_decode(params, x, cfg, cache, position, *, sin=None, cos=None,
               kv_repeat: int = 1):
    """Absorbed-form single-token decode against the latent cache."""
    dn, dr, dv, kvl = _dims(cfg)
    B, T, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope = _queries(params, x, cfg, sin, cos)   # (B,1,H,dn/dr)
    c_kv, k_rope = _latent_kv(params, x, cfg, sin, cos)   # (B,1,kvl),(B,1,dr)
    ckv = jax.lax.dynamic_update_slice(cache["ckv"], c_kv, (0, position, 0))
    kr = jax.lax.dynamic_update_slice(cache["kr"], k_rope, (0, position, 0))
    ckv = shard_act(ckv, ("batch", "seq_kv", None))

    wkv_b = params["wkv_b"]["w"].astype(x.dtype).reshape(kvl, H, dn + dv)
    wk, wv = wkv_b[..., :dn], wkv_b[..., dn:]
    # absorb: q_lat[b,h,l] = sum_d q_nope[b,h,d] * wk[l,h,d]
    q_lat = jnp.einsum("bthd,lhd->bthl", q_nope, wk)
    scale = softmax_scale(cfg, dn + dr)
    s = (jnp.einsum("bthl,bsl->bhts", q_lat, ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bthd,bsd->bhts", q_rope, kr,
                      preferred_element_type=jnp.float32)) * scale
    L = ckv.shape[1]
    valid = jnp.arange(L) <= position
    s = jnp.where(valid[None, None, None], s, -1e30)
    w = jax.nn.softmax(s.astype(jnp.float32), -1).astype(x.dtype)
    ctx = jnp.einsum("bhts,bsl->bthl", w, ckv)            # latent context
    out = jnp.einsum("bthl,lhd->bthd", ctx, wv).reshape(B, T, H * dv)
    y = linear(params["wo"], out)
    return y, {"ckv": ckv, "kr": kr}
