"""Serving steps: batched prefill + single-token decode (greedy / sampled).

``decode_*`` / ``long_*`` dry-run cells lower ``serve_step`` — one new
token against a KV cache of ``seq_len`` — exactly as assigned.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import model_zoo


def make_prefill_step(cfg: ArchConfig, *, tp: int = 1, cache_len: int = 0):
    def prefill_step(params, batch):
        logits, caches = model_zoo.prefill(
            cfg, params, batch, cache_len or batch_len(batch), tp=tp)
        next_tok = jnp.argmax(logits[:, -1:], axis=-1, keepdims=False)
        return next_tok, caches

    return prefill_step


def batch_len(batch: Dict) -> int:
    x = batch.get("tokens", batch.get("embeds", batch.get("dec_tokens")))
    return x.shape[1]


def make_serve_step(cfg: ArchConfig, *, tp: int = 1,
                    temperature: float = 0.0, with_route: bool = False):
    """serve_step(params, token, caches, position[, key]) ->
    (next_token, new_caches), and with ``with_route`` the expert counts
    after them (``model_zoo.decode_step``)."""

    def serve_step(params, token, caches, position, key=None):
        logits, new_caches, route = model_zoo.decode_step(
            cfg, params, token, caches, position, tp=tp, with_route=True)
        logits = logits[:, 0].astype(jnp.float32)
        if temperature > 0.0 and key is not None:
            next_tok = jax.random.categorical(key, logits / temperature)
        else:
            next_tok = jnp.argmax(logits, axis=-1)
        next_tok = next_tok[:, None].astype(jnp.int32)
        if with_route:
            return next_tok, new_caches, route
        return next_tok, new_caches

    return serve_step


def make_slot_step(cfg: ArchConfig, *, tp: int = 1):
    """Slot-batched decode step for the continuous-batching engine.

    ``slot_step(params, tokens, slot_caches, positions) ->
    (next_tokens, new_slot_caches, route)`` where every array carries a leading
    *slot* axis of fixed size S: ``tokens``/``positions`` are ``(S,)``
    int32 and ``slot_caches`` is a per-row cache pytree stacked on a new
    slot axis.  Built as ``vmap`` of the single-request ``serve_step``
    so each slot decodes exactly the math it would decode alone — rows
    are independent, which is what makes join/evict bit-identical to
    solo decode (dead slots compute garbage that nothing reads).

    Cache pytrees are NOT uniformly batched: the ``"groups"`` leaves
    carry the layer-group scan axis at 0 and the batch axis at 1, while
    the optional ``"prefix"`` per-layer caches carry batch at 0 — the
    in/out axes pytree below maps each accordingly.  Per-slot positions
    let rows sit at different decode depths inside one kernel call.
    ``route`` is each slot's expert counts per expert layer, (S,
    n_groups, E) leaves (``lm_decode_step``), or None for a model
    without experts.
    """
    step = make_serve_step(cfg, tp=tp, with_route=True)

    def _add_b(caches):
        out = {"groups": jax.tree.map(lambda a: a[:, None],
                                      caches["groups"])}
        if "prefix" in caches:
            out["prefix"] = [jax.tree.map(lambda a: a[None], c)
                             for c in caches["prefix"]]
        return out

    def _drop_b(caches):
        out = {"groups": jax.tree.map(lambda a: a[:, 0],
                                      caches["groups"])}
        if "prefix" in caches:
            out["prefix"] = [jax.tree.map(lambda a: a[0], c)
                             for c in caches["prefix"]]
        return out

    def _row(params, tok, cache_row, pos):
        nxt, new, route = step(params, tok[None, None], _add_b(cache_row),
                               pos)
        route = jax.tree.map(lambda a: a[:, 0], route)
        return nxt[0, 0], _drop_b(new), route

    def _axes(caches):
        axes = {"groups": 1}
        if "prefix" in caches:
            axes["prefix"] = 0
        return axes

    @jax.jit
    @jax.named_scope("decode")
    def slot_step(params, tokens, slot_caches, positions):
        axes = _axes(slot_caches)
        return jax.vmap(_row, in_axes=(None, 0, axes, 0),
                        out_axes=(0, axes, 0))(params, tokens, slot_caches,
                                               positions)

    return slot_step


def generate(cfg: ArchConfig, params, prompt: jnp.ndarray, n_new: int,
             *, tp: int = 1, cache_len: Optional[int] = None,
             temperature: float = 0.0, key=None):
    """Greedy/sampled generation loop (prefill + lax.scan decode)."""
    B, P = prompt.shape
    L = cache_len or (P + n_new)
    logits, caches = model_zoo.prefill(cfg, params, {"tokens": prompt},
                                       cache_len=L, tp=tp)
    first = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)[:, None]
    step = make_serve_step(cfg, tp=tp, temperature=temperature)

    def body(carry, t):
        tok, caches, k = carry
        k, sub = (jax.random.split(k) if k is not None else (None, None))
        nxt, caches = step(params, tok, caches, P + t, sub)
        return (nxt, caches, k), tok

    (last, _, _), toks = jax.lax.scan(
        body, (first.astype(jnp.int32), caches, key), jnp.arange(n_new))
    out = jnp.moveaxis(toks[..., 0], 0, 1)  # (B, n_new)
    return jnp.concatenate([out, last], axis=1)[:, :n_new + 1]
