"""ArchConfig -> model functions (init / forward / prefill / decode).

A single functional interface over decoder-only LMs (dense, MoE, SSM,
xLSTM, hybrid, stub-frontend VLM/audio) and encoder-decoder models.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeCell
from repro.models import encdec as encdec_mod
from repro.models import transformer as tf_mod
from repro.models import param as param_mod


# ---------------------------------------------------------------------------
def init(cfg: ArchConfig, key) -> Any:
    """Returns a P-tree (value + logical axes). Use param.values()/axes()."""
    if cfg.is_encoder_decoder:
        return encdec_mod.init_encdec(key, cfg)
    return tf_mod.init_lm(key, cfg)


def forward(cfg: ArchConfig, params, batch: Dict[str, jnp.ndarray],
            *, tp: int = 1):
    """Training/prefill forward. Returns (logits, aux_loss)."""
    if cfg.is_encoder_decoder:
        enc_out = encdec_mod.encode(params, batch["frames"], cfg, tp=tp)
        logits, _ = encdec_mod.decode_train(params, enc_out,
                                            batch["dec_tokens"], cfg, tp=tp)
        return logits, jnp.zeros((), jnp.float32)
    inputs = batch.get("embeds", batch.get("tokens"))
    logits, _, aux, _ = tf_mod.lm_forward(params, inputs, cfg, tp=tp)
    return logits, aux


def prefill(cfg: ArchConfig, params, batch, cache_len: int, *, tp: int = 1,
            with_route: bool = False):
    """Prefill pass that also materializes decode caches.  Returns
    (logits, caches), and with ``with_route`` the expert counts after
    them (``transformer.lm_forward``; None for a model without
    experts)."""
    if cfg.is_encoder_decoder:
        enc_out = encdec_mod.encode(params, batch["frames"], cfg, tp=tp)
        logits, _ = encdec_mod.decode_train(params, enc_out,
                                            batch["dec_tokens"], cfg, tp=tp)
        caches = encdec_mod.init_dec_caches(
            params, enc_out, cfg, batch["dec_tokens"].shape[0], cache_len,
            tp=tp)
        return (logits, caches, None) if with_route else (logits, caches)
    inputs = batch.get("embeds", batch.get("tokens"))
    logits, caches, _, route = tf_mod.lm_forward(
        params, inputs, cfg, tp=tp, make_cache_len=cache_len)
    return (logits, caches, route) if with_route else (logits, caches)


def init_caches(cfg: ArchConfig, batch: int, max_len: int, *, tp: int = 1,
                params=None, enc_out=None, dtype=jnp.bfloat16):
    if cfg.is_encoder_decoder:
        assert params is not None and enc_out is not None
        return encdec_mod.init_dec_caches(params, enc_out, cfg, batch,
                                          max_len, tp=tp, dtype=dtype)
    return tf_mod.init_lm_caches(cfg, batch, max_len, tp=tp, dtype=dtype)


def decode_step(cfg: ArchConfig, params, token, caches, position,
                *, tp: int = 1, with_route: bool = False):
    """One-token decode. Returns (logits, new_caches), and with
    ``with_route`` the expert counts after them, as ``prefill``."""
    if cfg.is_encoder_decoder:
        out = encdec_mod.decode_step(params, token, cfg, caches, position,
                                     tp=tp)
        return out + (None,) if with_route else out
    out = tf_mod.lm_decode_step(params, token, cfg, caches, position, tp=tp)
    return out if with_route else out[:2]


# ---------------------------------------------------------------------------
# ShapeDtypeStruct stand-ins for the dry-run (no allocation)
# ---------------------------------------------------------------------------
def input_specs(cfg: ArchConfig, cell: ShapeCell, *, tp: int = 1
                ) -> Dict[str, Any]:
    """Stand-ins for every model input of this (arch x shape) cell."""
    B, T = cell.global_batch, cell.seq_len
    sds = jax.ShapeDtypeStruct
    i32, bf16 = jnp.int32, jnp.bfloat16

    if cell.kind in ("train", "prefill"):
        if cfg.is_encoder_decoder:
            return {"frames": sds((B, T, cfg.d_model), bf16),
                    "dec_tokens": sds((B, T), i32),
                    "labels": sds((B, T), i32)}
        if cfg.frontend != "none":
            return {"embeds": sds((B, T, cfg.d_model), bf16),
                    "labels": sds((B, T), i32)}
        return {"tokens": sds((B, T), i32), "labels": sds((B, T), i32)}

    # decode: one new token against a cache of T tokens
    token = sds((B, 1), i32)
    position = sds((), i32)
    if cfg.is_encoder_decoder:
        params_sds = jax.eval_shape(
            lambda: param_mod.values(init(cfg, jax.random.key(0))))
        enc_sds = sds((B, T, cfg.d_model), bf16)
        caches = jax.eval_shape(
            lambda p, e: encdec_mod.init_dec_caches(p, e, cfg, B, T, tp=tp),
            params_sds, enc_sds)
    else:
        caches = jax.eval_shape(
            lambda: tf_mod.init_lm_caches(cfg, B, T, tp=tp))
    return {"token": token, "caches": caches, "position": position}


def param_specs(cfg: ArchConfig):
    """ShapeDtypeStructs + logical axes for the parameter tree."""
    ptree = jax.eval_shape(lambda: init(cfg, jax.random.key(0)))
    vals = param_mod.values(ptree)
    axes = param_mod.axes(ptree)
    return vals, axes


def count_params(cfg: ArchConfig) -> int:
    vals, _ = param_specs(cfg)
    import numpy as np
    return int(sum(int(np.prod(x.shape)) for x in jax.tree.leaves(vals)))
