"""Mixture-of-Experts with hybrid dense/tail dispatch.

This is the paper's spmv insight (§4.3: dense rows -> GPU, sparse tail ->
CPU) applied to MoE routing: tokens are packed per-expert up to a
*capacity* into a dense grouped-matmul path (MXU-friendly, fully
regular), and the *overflow tail* is re-dispatched through one or more
extra small grouped-matmul passes instead of being dropped.

Dispatch is group-wise (group = batch row) so the dispatch buffers shard
over (pod, data) x (model=expert) with no global resharding.

Serving (``moe_serve``: prefill and decode) drops nothing: a token's
result may not depend on which other tokens share its call.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.gmm.ops import gmm_model
from repro.models.layers import ACTS, init_linear, linear
from repro.models.param import dense_init
from repro.parallel.sharding import active_mesh, shard_act, spec_for


def init_moe(key, cfg):
    m = cfg.moe
    E, dff, d = m.n_routed, m.d_ff, cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": init_linear(ks[0], d, E, ("embed", None)),
        "w_up": dense_init(ks[1], (E, d, dff), ("expert", "embed", "mlp"),
                           fan_in=d),
        "w_gate": dense_init(ks[2], (E, d, dff), ("expert", "embed", "mlp"),
                             fan_in=d),
        "w_down": dense_init(ks[3], (E, dff, d), ("expert", "mlp", "embed"),
                             fan_in=dff),
    }
    if m.n_shared:
        # shared experts fused into one wide dense GLU
        p["shared"] = {
            "up": init_linear(ks[4], d, m.n_shared * dff, ("embed", "mlp")),
            "gate": init_linear(jax.random.fold_in(ks[4], 1), d,
                                m.n_shared * dff, ("embed", "mlp")),
            "down": init_linear(jax.random.fold_in(ks[4], 2),
                                m.n_shared * dff, d, ("mlp", "embed")),
        }
    return p


def _dispatch_indices(flat_expert: jnp.ndarray, E: int):
    """flat_expert: (Nk,) expert id per assignment (one group).

    Returns (sort order, expert id sorted, position-in-expert) — the
    paper's 'sort rows by density then bin' transform.
    """
    order = jnp.argsort(flat_expert)
    sorted_e = flat_expert[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    pos = jnp.arange(flat_expert.shape[0]) - starts[sorted_e]
    return order, sorted_e, pos


def _dispatch_onehot(flat_expert: jnp.ndarray, E: int):
    """Sort-free dispatch (§Perf): position-in-expert via a one-hot
    cumsum; no argsort, no un-sort gather.  Returns (expert ids,
    positions) in ORIGINAL assignment order."""
    oh = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)   # (Nk, E)
    pos = (jnp.cumsum(oh, axis=0) - 1)                     # (Nk, E)
    pos = jnp.take_along_axis(pos, flat_expert[:, None], axis=1)[:, 0]
    return flat_expert, pos


def _one_pass(x_sorted, weights, sorted_e, pos, C: int, E: int, cfg):
    """Scatter -> grouped matmul -> gather for one capacity pass.

    x_sorted: (Nk, d) token features in dispatch order (one group).
    Returns per-assignment outputs (Nk, d); assignments with pos >= C
    contribute zeros (handled by later passes).
    """
    d = x_sorted.shape[-1]
    act = ACTS[cfg.act]
    keep = pos < C
    e_idx = jnp.where(keep, sorted_e, E)        # E == drop row
    p_idx = jnp.where(keep, pos, 0)
    buf = jnp.zeros((E + 1, C, d), x_sorted.dtype)
    buf = buf.at[e_idx, p_idx].set(x_sorted, mode="drop")
    buf = buf[:E]
    if cfg.moe.shard_dispatch:
        # keep the dispatch buffer expert-sharded end-to-end (§Perf):
        # under vmap the batch dim is added in front automatically
        buf = shard_act(buf, ("expert", None, None))
    # grouped matmul (dense path — the MXU-friendly "dense rows"),
    # through the autotuned gmm config for this (E, C, D, F) bucket
    # (tracer-safe lookup, differentiable impls only)
    h = gmm_model(buf, weights["w_up"].astype(buf.dtype))
    g = gmm_model(buf, weights["w_gate"].astype(buf.dtype))
    h = h * act(g)
    out = gmm_model(h, weights["w_down"].astype(buf.dtype))
    if cfg.moe.shard_dispatch:
        out = shard_act(out, ("expert", None, None))
    gathered = out[e_idx, p_idx]                # (Nk, d)
    return jnp.where(keep[:, None], gathered, 0.0)


def route(params, x, cfg):
    """Softmax router over the routed experts and its greedy top-k:
    (probs (B,T,E) f32, gates (B,T,k), expert ids (B,T,k)).  Gates are
    renormalised over the top-k only where ``norm_topk_prob``."""
    m = cfg.moe
    with jax.named_scope("moe_router"):
        logits = linear(params["router"], x).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, topk_idx = jax.lax.top_k(probs, m.top_k)
        if m.norm_topk_prob:
            gate_vals = gate_vals / jnp.clip(
                jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    return probs, gate_vals, topk_idx


def _capacity_experts(params, x, topk_idx, gate_vals, C: int,
                      overflow_passes: int, cfg):
    """Routed experts through per-group (batch row) capacity buffers of
    ``C`` slots per expert, plus ``overflow_passes`` tail passes;
    assignments beyond them are dropped."""
    m = cfg.moe
    B, T, d = x.shape
    E, k = m.n_routed, m.top_k

    def per_group(xg, idxg, gateg):
        """xg: (T,d); idxg: (T,k); gateg: (T,k)."""
        flat_e = idxg.reshape(-1)
        xk = jnp.repeat(xg, k, axis=0)          # (T*k, d) feature per assignment
        if m.dispatch == "onehot":
            # sort-free dispatch (§Perf optimized path)
            e_ids, pos = _dispatch_onehot(flat_e, E)
            x_in = xk
        else:
            order, e_ids, pos = _dispatch_indices(flat_e, E)
            x_in = xk[order]
        y_out = _one_pass(x_in, params, e_ids, pos, C, E, cfg)
        # ---- the sparse tail: re-dispatch overflow at C_tail ----
        for p_ in range(overflow_passes):
            C_tail = max(1, C // 4)
            pos_t = pos - C - p_ * C_tail
            y_out = y_out + _one_pass(
                x_in, params, e_ids,
                jnp.where(pos_t >= 0, pos_t, C_tail), C_tail, E, cfg)
        if m.dispatch == "onehot":
            y_flat = y_out.reshape(T, k, d)
        else:
            inv = jnp.argsort(order)            # un-sort
            y_flat = y_out[inv].reshape(T, k, d)
        return jnp.sum(y_flat * gateg[..., None].astype(y_flat.dtype), axis=1)

    with jax.named_scope("moe_experts"):
        y = jax.vmap(per_group)(x, topk_idx, gate_vals)
    return shard_act(y, ("batch", None, None))


def _ragged_local(params, x, topk_idx, gate_vals, cfg, e0=0):
    """The call's assignments to the routed experts whose weights
    ``params`` holds (ids ``e0`` on), sorted by expert, through one
    grouped matmul per weight whose group sizes are the experts' loads
    (``lax.ragged_dot``); assignments to other experts add zero."""
    B, T, d = x.shape
    k = cfg.moe.top_k
    E_loc = params["w_up"].shape[0]
    act = ACTS[cfg.act]
    local = topk_idx.reshape(-1) - e0               # (B*T*k,)
    mine = (local >= 0) & (local < E_loc)
    local = jnp.where(mine, local, E_loc)           # others sort last
    order = jnp.argsort(local)
    xs = x.reshape(B * T, d)[order // k]            # assignments by expert
    sizes = jnp.bincount(local, length=E_loc).astype(jnp.int32)
    h = jax.lax.ragged_dot(xs, params["w_up"].astype(x.dtype), sizes)
    g = jax.lax.ragged_dot(xs, params["w_gate"].astype(x.dtype), sizes)
    out = jax.lax.ragged_dot(h * act(g), params["w_down"].astype(x.dtype),
                             sizes)
    out = jnp.zeros_like(out).at[order].set(out)
    out = jnp.where(mine[:, None], out, 0).reshape(B, T, k, d)
    return jnp.sum(out * gate_vals[..., None].astype(out.dtype), axis=2)


def _mesh_axes(entry) -> Tuple[str, ...]:
    """A PartitionSpec entry as a tuple of mesh axes."""
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)


def _ragged_experts(params, x, topk_idx, gate_vals, cfg):
    """Routed experts with nothing dropped (``_ragged_local``).  Under a
    mesh the weights keep their expert and FFN-width sharding: each
    shard takes the assignments to its own experts, with the tokens
    gathered over the weights' axes, and a psum over those axes adds
    the shards' partial outputs."""
    mesh = active_mesh()
    e_axes = f_axes = None
    if mesh is not None and not mesh.empty:
        spec = tuple(spec_for(("expert", "embed", "mlp"),
                              shape=params["w_up"].shape)) + (None,) * 3
        e_axes, _, f_axes = spec[:3]
    w_axes = _mesh_axes(e_axes) + _mesh_axes(f_axes)
    with jax.named_scope("moe_experts"):
        if not w_axes:
            return _ragged_local(params, x, topk_idx, gate_vals, cfg)
        b_spec = tuple(spec_for(("batch",), shape=x.shape[:1])) or (None,)
        rows = tuple(a for a in _mesh_axes(b_spec[0]) if a not in w_axes)
        row = P(rows or None, None, None)
        up, down = P(e_axes, None, f_axes), P(e_axes, f_axes, None)

        def shard(x, idx, gate, w_up, w_gate, w_down):
            e0 = (jax.lax.axis_index(_mesh_axes(e_axes)) * w_up.shape[0]
                  if e_axes else 0)
            y = _ragged_local({"w_up": w_up, "w_gate": w_gate,
                               "w_down": w_down}, x, idx, gate, cfg, e0)
            return jax.lax.psum(y, w_axes)

        return jax.shard_map(shard, mesh=mesh,
                             in_specs=(row,) * 3 + (up, up, down),
                             out_specs=row, check_vma=False)(
            x, topk_idx, gate_vals, params["w_up"], params["w_gate"],
            params["w_down"])


def _shared(params, x, cfg):
    """The always-on shared experts (fused into one wide GLU); zero for a
    layer without them."""
    if "shared" not in params:
        return 0.0
    with jax.named_scope("moe_shared"):
        sp = params["shared"]
        h = linear(sp["up"], x) * ACTS[cfg.act](linear(sp["gate"], x))
        return linear(sp["down"], h)


def moe_ffn(params, x, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, T, d). Returns (y, aux_loss).  The training path: each
    expert keeps at most ``capacity_factor * T * k / E`` assignments of a
    batch row, plus the overflow passes; serving calls ``moe_serve``."""
    m = cfg.moe
    if m.shard_mode == "smap":
        if active_mesh() is not None:
            from repro.models.moe_shard_map import moe_ffn_shard_map
            return moe_ffn_shard_map(params, x, cfg)
    B, T, d = x.shape
    E, k = m.n_routed, m.top_k
    probs, gate_vals, topk_idx = route(params, x, cfg)

    # ---- load-balancing aux loss (Switch-style) ----
    me = jnp.mean(probs, axis=(0, 1))                               # (E,)
    one_hot = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)
    ce = jnp.mean(jnp.sum(one_hot, axis=2), axis=(0, 1)) / k        # (E,)
    aux = m.aux_loss_coef * E * jnp.sum(me * ce)

    C = max(1, int(T * k / E * m.capacity_factor))
    y = _capacity_experts(params, x, topk_idx, gate_vals, C,
                          m.overflow_passes, cfg)
    return y + _shared(params, x, cfg), aux


def moe_serve(params, x, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, T, d). Returns (y, counts): the layer with no assignment
    dropped at any load, and each batch row's assignments per routed
    expert, (B, E) int32.  One token a row (a decode step) goes through
    one capacity slot per expert, which its k distinct experts cannot
    overflow: the slot step vmaps that call over weights its rows share,
    which ``lax.ragged_dot`` has no batching rule for, and on the host
    the capacity pass is also the faster (PERF.md section 6).  Longer
    rows go through ``_ragged_experts``."""
    B, T, d = x.shape
    E = cfg.moe.n_routed
    _, gate_vals, topk_idx = route(params, x, cfg)
    counts = jax.vmap(lambda i: jnp.bincount(i.reshape(-1), length=E))(
        topk_idx).astype(jnp.int32)
    if T == 1:
        y = _capacity_experts(params, x, topk_idx, gate_vals, 1, 0, cfg)
    else:
        y = _ragged_experts(params, x, topk_idx, gate_vals, cfg)
    return y + _shared(params, x, cfg), counts
