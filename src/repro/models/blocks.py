"""Block assembly: (attn | mla | mamba | mlstm | slstm) + (mlp | moe).

Layers are organized into *groups* (a group is the repeating unit — one
layer for homogeneous stacks, 8 layers for jamba's attn:mamba interleave,
``slstm_every`` layers for xLSTM) and the stack is a ``lax.scan`` over
stacked group parameters, keeping HLO size independent of depth.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import init_mlp, init_norm, mlp, norm
from repro.models.param import stack_layers
from repro.parallel.sharding import shard_act


# ---------------------------------------------------------------------------
# Group layout per architecture
# ---------------------------------------------------------------------------
def group_layout(cfg) -> Tuple[List[str], List[bool], int]:
    """Returns (kinds, moe_flags, n_groups) for the scanned group."""
    if cfg.block_pattern == "jamba":
        g = cfg.attn_every
        kinds = ["attn" if i == cfg.attn_offset else "mamba" for i in range(g)]
        moe_flags = [cfg.moe is not None and i % cfg.moe.every == 1
                     for i in range(g)]
        return kinds, moe_flags, cfg.n_layers // g
    if cfg.block_pattern == "xlstm":
        g = cfg.xlstm.slstm_every
        kinds = ["slstm" if i == g - 1 else "mlstm" for i in range(g)]
        return kinds, [False] * g, cfg.n_layers // g
    kind = "mla" if cfg.attn_type == "mla" else "attn"
    n_dense = cfg.moe.n_dense_layers if cfg.moe else 0
    return [kind], [cfg.moe is not None], cfg.n_layers - n_dense


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------
def init_layer(key, cfg, kind: str, use_moe: bool):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: dict = {"norm1": init_norm(cfg)}
    if kind == "attn":
        p["mix"] = attn_mod.init_attention(k1, cfg)
    elif kind == "mla":
        p["mix"] = mla_mod.init_mla(k1, cfg)
    elif kind == "mamba":
        p["mix"] = ssm_mod.init_mamba(k1, cfg)
    elif kind == "mlstm":
        p["mix"] = xlstm_mod.init_mlstm_block(k1, cfg)
    elif kind == "slstm":
        p["mix"] = xlstm_mod.init_slstm_block(k1, cfg)
    else:
        raise ValueError(kind)
    if cfg.d_ff or use_moe:
        p["norm2"] = init_norm(cfg)
        p["ffn"] = moe_mod.init_moe(k2, cfg) if use_moe else init_mlp(k3, cfg)
    return p


def init_layer_cache(cfg, kind: str, batch: int, max_len: int,
                     kv_repeat: int = 1, dtype=jnp.bfloat16):
    if kind == "attn":
        return attn_mod.init_cache(cfg, batch, max_len, kv_repeat, dtype)
    if kind == "mla":
        return mla_mod.init_mla_cache(cfg, batch, max_len, dtype)
    if kind == "mamba":
        return ssm_mod.init_mamba_cache(cfg, batch, dtype)
    if kind == "mlstm":
        return xlstm_mod.init_mlstm_cache(cfg, batch, dtype)
    if kind == "slstm":
        return xlstm_mod.init_slstm_cache(cfg, batch)
    raise ValueError(kind)


def apply_layer(params, x, cfg, kind: str, use_moe: bool, *, sin, cos,
                kv_repeat: int = 1, make_cache_len: int = 0):
    """Full-sequence layer. Returns (x, cache, aux_loss, route).  With
    ``make_cache_len`` (a serving prefill) an expert layer drops no
    assignment and ``route`` is its (B, E) expert counts; else None."""
    h = norm(params["norm1"], x, cfg)
    cache = None
    if kind == "attn":
        y, cache = attn_mod.attention(
            params["mix"], h, cfg, sin=sin, cos=cos, kv_repeat=kv_repeat,
            make_cache_len=make_cache_len)
    elif kind == "mla":
        y, cache = mla_mod.mla_attention(
            params["mix"], h, cfg, sin=sin, cos=cos,
            make_cache_len=make_cache_len)
    elif kind == "mamba":
        y, cache = ssm_mod.mamba(params["mix"], h, cfg,
                                 make_cache=make_cache_len > 0)
    elif kind == "mlstm":
        y, cache = xlstm_mod.mlstm_block(params["mix"], h, cfg,
                                         make_cache=make_cache_len > 0)
    elif kind == "slstm":
        y, st = xlstm_mod.slstm_block(params["mix"], h, cfg)
        cache = st if make_cache_len > 0 else None
    else:
        raise ValueError(kind)
    seq_ax = "seq" if cfg.parallel.seq_parallel else None
    x = x + y
    x = shard_act(x, ("batch", seq_ax, "embed"))
    x = jax.ad_checkpoint.checkpoint_name(x, "blk_attn_out")
    aux = jnp.zeros((), jnp.float32)
    route = None
    if "ffn" in params:
        h = norm(params["norm2"], x, cfg)
        if use_moe and make_cache_len:
            y, route = moe_mod.moe_serve(params["ffn"], h, cfg)
        elif use_moe:
            y, aux = moe_mod.moe_ffn(params["ffn"], h, cfg)
        else:
            y = mlp(params["ffn"], h, cfg)
        x = x + y
        x = shard_act(x, ("batch", seq_ax, "embed"))
        x = jax.ad_checkpoint.checkpoint_name(x, "blk_ffn_out")
    return x, cache, aux, route


def apply_layer_decode(params, x, cfg, kind: str, use_moe: bool, cache,
                       position, *, sin, cos, kv_repeat: int = 1):
    """Single-token layer step. Returns (x, new_cache, route): an expert
    layer's (B, E) expert counts, else None."""
    h = norm(params["norm1"], x, cfg)
    if kind == "attn":
        y, cache = attn_mod.attention_decode(
            params["mix"], h, cfg, cache, position, sin=sin, cos=cos,
            kv_repeat=kv_repeat)
    elif kind == "mla":
        y, cache = mla_mod.mla_decode(params["mix"], h, cfg, cache, position,
                                      sin=sin, cos=cos)
    elif kind == "mamba":
        y, cache = ssm_mod.mamba_decode(params["mix"], h, cfg, cache)
    elif kind == "mlstm":
        y, cache = xlstm_mod.mlstm_block(params["mix"], h, cfg,
                                         decode_state=cache)
    elif kind == "slstm":
        y, cache = xlstm_mod.slstm_block(params["mix"], h, cfg, state=cache)
    else:
        raise ValueError(kind)
    x = x + y
    route = None
    if "ffn" in params:
        h = norm(params["norm2"], x, cfg)
        if use_moe:
            y, route = moe_mod.moe_serve(params["ffn"], h, cfg)
        else:
            y = mlp(params["ffn"], h, cfg)
        x = x + y
    return x, cache, route


# ---------------------------------------------------------------------------
# Group (repeating unit) and scanned stack
# ---------------------------------------------------------------------------
def init_group(key, cfg):
    kinds, moe_flags, _ = group_layout(cfg)
    keys = jax.random.split(key, len(kinds))
    return {f"l{i}": init_layer(keys[i], cfg, kinds[i], moe_flags[i])
            for i in range(len(kinds))}


def init_group_cache(cfg, batch: int, max_len: int, kv_repeat: int = 1,
                     dtype=jnp.bfloat16):
    kinds, _, _ = group_layout(cfg)
    return {f"l{i}": init_layer_cache(cfg, kinds[i], batch, max_len,
                                      kv_repeat, dtype)
            for i in range(len(kinds))}


def apply_group(params, x, cfg, *, sin, cos, kv_repeat=1, make_cache_len=0):
    """Returns (x, caches, aux, routes): ``routes`` maps each expert
    layer of the group to its counts on the serving path, else None."""
    kinds, moe_flags, _ = group_layout(cfg)
    caches, aux, routes = {}, jnp.zeros((), jnp.float32), {}
    for i, (kind, mf) in enumerate(zip(kinds, moe_flags)):
        x, c, a, r = apply_layer(params[f"l{i}"], x, cfg, kind, mf, sin=sin,
                                 cos=cos, kv_repeat=kv_repeat,
                                 make_cache_len=make_cache_len)
        caches[f"l{i}"] = c
        aux = aux + a
        if r is not None:
            routes[f"l{i}"] = r
    return x, (caches if make_cache_len else None), aux, routes or None


def apply_group_decode(params, x, cfg, caches, position, *, sin, cos,
                       kv_repeat=1):
    """Returns (x, new_caches, routes), as ``apply_group``."""
    kinds, moe_flags, _ = group_layout(cfg)
    new_caches, routes = {}, {}
    for i, (kind, mf) in enumerate(zip(kinds, moe_flags)):
        x, c, r = apply_layer_decode(params[f"l{i}"], x, cfg, kind, mf,
                                     caches[f"l{i}"], position, sin=sin,
                                     cos=cos, kv_repeat=kv_repeat)
        new_caches[f"l{i}"] = c
        if r is not None:
            routes[f"l{i}"] = r
    return x, new_caches, routes or None


def _remat_wrap(fn, cfg):
    if cfg.parallel.remat == "none":
        return fn
    if cfg.parallel.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    if cfg.parallel.remat == "dots_names":
        # §Perf: like "dots" but additionally pins the MoE a2a results
        # so the backward never re-runs the forward all_to_all
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.checkpoint_dots,
            jax.checkpoint_policies.save_only_these_names(
                "moe_a2a_in", "moe_a2a_out"))
        return jax.checkpoint(fn, policy=policy)
    if cfg.parallel.remat == "full_names":
        policy = jax.checkpoint_policies.save_only_these_names(
            "moe_a2a_in", "moe_a2a_out")
        return jax.checkpoint(fn, policy=policy)
    if cfg.parallel.remat == "boundaries":
        # §Perf (dense TP + SP): pin the post-collective residuals so
        # the backward recompute never re-runs TP collectives; with
        # seq_parallel those tensors are 1/TP-sized
        policy = jax.checkpoint_policies.save_only_these_names(
            "blk_attn_out", "blk_ffn_out")
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def init_stack(key, cfg):
    """Stacked group params (leading 'layers' axis) + unrolled dense prefix."""
    _, _, n_groups = group_layout(cfg)
    keys = jax.random.split(key, n_groups)
    stacked = jax.vmap(lambda k: init_group(k, cfg))(keys)
    stacked = stack_layers(stacked)
    p = {"groups": stacked}
    n_dense = cfg.moe.n_dense_layers if cfg.moe else 0
    if n_dense and cfg.block_pattern == "attn":
        kind = "mla" if cfg.attn_type == "mla" else "attn"
        dkeys = jax.random.split(jax.random.fold_in(key, 777), n_dense)
        # dense prefix uses the dense d_ff (no MoE)
        p["prefix"] = [init_layer(dkeys[i], cfg, kind, False)
                       for i in range(n_dense)]
    return p


def init_stack_caches(cfg, batch: int, max_len: int, kv_repeat: int = 1,
                      dtype=jnp.bfloat16):
    kinds, _, n_groups = group_layout(cfg)
    one = init_group_cache(cfg, batch, max_len, kv_repeat, dtype)
    stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape), one)
    out = {"groups": stacked}
    n_dense = cfg.moe.n_dense_layers if cfg.moe else 0
    if n_dense and cfg.block_pattern == "attn":
        kind = "mla" if cfg.attn_type == "mla" else "attn"
        out["prefix"] = [init_layer_cache(cfg, kind, batch, max_len,
                                          kv_repeat, dtype)
                         for _ in range(n_dense)]
    return out


def apply_stack(params, x, cfg, *, sin, cos, kv_repeat=1, make_cache_len=0):
    """Returns (x, caches, aux, route).  With ``make_cache_len``, an
    expert model's ``route`` holds the call's expert counts: per expert
    layer of the group, (n_groups, B, E); else None."""
    kinds0 = ("mla" if cfg.attn_type == "mla" else "attn")
    prefix_caches = []
    aux = jnp.zeros((), jnp.float32)
    for lp in params.get("prefix", []):
        x, c, a, _ = apply_layer(lp, x, cfg, kinds0, False, sin=sin,
                                 cos=cos, kv_repeat=kv_repeat,
                                 make_cache_len=make_cache_len)
        prefix_caches.append(c)
        aux = aux + a

    def body(carry, gparams):
        x, aux = carry
        x, cache, a, route = apply_group(gparams, x, cfg, sin=sin, cos=cos,
                                         kv_repeat=kv_repeat,
                                         make_cache_len=make_cache_len)
        return (x, aux + a), (cache, route)

    body = _remat_wrap(body, cfg)
    if cfg.parallel.scan_layers:
        (x, aux), (gcaches, routes) = jax.lax.scan(body, (x, aux),
                                                   params["groups"])
    else:
        # unrolled python loop (probe mode: makes every layer's FLOPs
        # visible to XLA cost analysis, which counts scan bodies once)
        _, _, n_groups = group_layout(cfg)
        cl = []
        for i in range(n_groups):
            gp = jax.tree.map(lambda a: a[i], params["groups"])
            (x, aux), c = body((x, aux), gp)
            cl.append(c)
        gcaches, routes = (jax.tree.map(lambda *xs: jnp.stack(xs), *cl)
                           if make_cache_len else (None, None))
    caches = None
    if make_cache_len:
        caches = {"groups": gcaches}
        if prefix_caches:
            caches["prefix"] = prefix_caches
    return x, caches, aux, routes


def apply_stack_decode(params, x, cfg, caches, position, *, sin, cos,
                       kv_repeat=1):
    """Returns (x, new_caches, route), ``route`` as in ``apply_stack``."""
    kinds0 = ("mla" if cfg.attn_type == "mla" else "attn")
    new_prefix = []
    for lp, c in zip(params.get("prefix", []), caches.get("prefix", [])):
        x, c2, _ = apply_layer_decode(lp, x, cfg, kinds0, False, c, position,
                                      sin=sin, cos=cos, kv_repeat=kv_repeat)
        new_prefix.append(c2)

    def body(x, xs):
        gparams, gcache = xs
        x, c2, route = apply_group_decode(gparams, x, cfg, gcache, position,
                                          sin=sin, cos=cos,
                                          kv_repeat=kv_repeat)
        return x, (c2, route)

    if cfg.parallel.scan_layers:
        x, (gcaches, routes) = jax.lax.scan(
            body, x, (params["groups"], caches["groups"]))
    else:
        _, _, n_groups = group_layout(cfg)
        cl = []
        for i in range(n_groups):
            xs_i = jax.tree.map(lambda a: a[i],
                                (params["groups"], caches["groups"]))
            x, c = body(x, xs_i)
            cl.append(c)
        gcaches, routes = jax.tree.map(lambda *xs: jnp.stack(xs), *cl)
    new_caches = {"groups": gcaches}
    if new_prefix:
        new_caches["prefix"] = new_prefix
    return x, new_caches, routes
