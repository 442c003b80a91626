"""Decoder-only LM assembled from the block stack."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import blocks
from repro.models.layers import (embed, init_embedding, init_norm,
                                 init_unembed, norm, rope_table, unembed)
from repro.parallel.sharding import shard_act


def _rope_dim(cfg) -> int:
    if cfg.attn_type == "mla" and cfg.mla is not None:
        return cfg.mla.qk_rope_head_dim
    return cfg.head_dim_()


def _has_attn(cfg) -> bool:
    kinds, _, _ = blocks.group_layout(cfg)
    return any(k in ("attn", "mla") for k in kinds)


def init_lm(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "embed": init_embedding(k1, cfg),
        "stack": blocks.init_stack(k2, cfg),
        "final_norm": init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = init_unembed(k3, cfg)
    return p


def _inputs_to_h(params, inputs, cfg):
    if jnp.issubdtype(inputs.dtype, jnp.floating):
        # modality-frontend stub: precomputed patch/frame embeddings
        return inputs
    return embed(params["embed"], inputs, cfg)


def lm_forward(params, inputs, cfg, *, tp: int = 1, make_cache_len: int = 0,
               positions: Optional[jnp.ndarray] = None):
    """inputs: (B, T) int tokens or (B, T, d) stub embeddings.

    Returns (logits, caches, aux_loss, route).  With ``make_cache_len``
    (a serving prefill) expert layers drop nothing, and an expert
    model's ``route`` counts each row's assignments per expert
    (``blocks.apply_stack``); else it is None."""
    x = _inputs_to_h(params, inputs, cfg).astype(jnp.bfloat16)
    x = shard_act(x, ("batch", None, "embed"))
    sin = cos = None
    if _has_attn(cfg):
        T = x.shape[1]
        pos = positions if positions is not None else jnp.arange(T)
        sin, cos = rope_table(_rope_dim(cfg), T, cfg.rope_theta, pos,
                              cfg.rope_scaling)
    kv_rep = attn_mod.kv_repeat_for(cfg, tp)
    x, caches, aux, route = blocks.apply_stack(
        params["stack"], x, cfg, sin=sin, cos=cos, kv_repeat=kv_rep,
        make_cache_len=make_cache_len)
    logits = shard_act(_head(params, x, cfg), ("batch", None, "vocab"))
    return logits, caches, aux, route


def init_lm_caches(cfg, batch: int, max_len: int, tp: int = 1,
                   dtype=jnp.bfloat16):
    kv_rep = attn_mod.kv_repeat_for(cfg, tp)
    return blocks.init_stack_caches(cfg, batch, max_len, kv_rep, dtype)


def lm_decode_step(params, inputs, cfg, caches, position, *, tp: int = 1):
    """inputs: (B, 1) token ids (or (B, 1, d) embeds); position: scalar.

    Returns (logits (B, 1, V), new_caches, route), ``route`` as in
    ``lm_forward``."""
    x = _inputs_to_h(params, inputs, cfg).astype(jnp.bfloat16)
    sin = cos = None
    if _has_attn(cfg):
        pos = jnp.asarray(position)[None]
        sin, cos = rope_table(_rope_dim(cfg), 1, cfg.rope_theta, pos,
                              cfg.rope_scaling)
    kv_rep = attn_mod.kv_repeat_for(cfg, tp)
    x, new_caches, route = blocks.apply_stack_decode(
        params["stack"], x, cfg, caches, position, sin=sin, cos=cos,
        kv_repeat=kv_rep)
    return _head(params, x, cfg), new_caches, route


def _head(params, x, cfg):
    """Final norm and unembedding: logits over the vocabulary."""
    with jax.named_scope("head"):
        x = norm(params["final_norm"], x, cfg)
        return unembed(params.get("unembed"), x, cfg,
                       embed_params=params["embed"])
