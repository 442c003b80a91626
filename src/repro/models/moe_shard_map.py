"""shard_map MoE (§Perf optimized path, shard_mode="smap").

Deterministic collective schedule instead of GSPMD propagation:

  * expert weights sharded E over the 'data' axis, FFN dim over 'model'
    (hierarchical EP x TP — fits the 1T kimi config in 8 GB/chip);
  * tokens stay sharded over (pod, data) and replicated over 'model',
    so routing + capacity dispatch are entirely LOCAL;
  * one all_to_all over 'data' ships each expert's capacity buffer to
    its owner (and back);
  * the f-contraction partial sums fold into ONE activation-sized psum
    over 'model' (combine is linear, so the psum commutes past it).

Per-layer collective bytes (deepseek train_4k, per device):
  a2a 2 x (E,C,d)/16 + psum (B_loc,T,d)  ~= 0.8 GB  vs ~58 GB baseline.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map

from repro.models.layers import ACTS
from repro.models.moe import _dispatch_indices, _dispatch_onehot, route
from repro.parallel.sharding import active_mesh


def _local_moe(params, x_loc, cfg, data_ax: str, model_ax: str,
               n_data: int):
    """Per-device computation. x_loc: (B_loc, T, d)."""
    m = cfg.moe
    B_loc, T, d = x_loc.shape
    E, k = m.n_routed, m.top_k
    E_loc = E // n_data
    act = ACTS[cfg.act]
    C = max(1, int(T * k / E * m.capacity_factor))

    probs, gate, topk_idx = route(params, x_loc, cfg)

    # aux loss over the full batch: local means + pmean over data
    me = jax.lax.pmean(jnp.mean(probs, axis=(0, 1)), data_ax)
    oh = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)
    ce = jax.lax.pmean(jnp.mean(jnp.sum(oh, 2), (0, 1)) / k, data_ax)
    aux = m.aux_loss_coef * E * jnp.sum(me * ce)

    # ONE dispatch group per device (not per batch row): the capacity
    # averages over all local tokens (law of large numbers), shrinking
    # the a2a payload ~2.5x vs per-row buffers (§Perf iteration 4).
    N = B_loc * T
    C_dev = max(1, int(N * k / E * m.capacity_factor))
    # paper's sparse-tail: overflow slots appended on the capacity axis
    # so ONE a2a + ONE grouped matmul covers both passes
    C_tail = max(1, C_dev // 4) if m.overflow_passes else 0
    Ct = C_dev + C_tail * m.overflow_passes

    flat_e = topk_idx.reshape(-1)                          # (N*k,)
    xk = jnp.repeat(x_loc.reshape(N, d), k, axis=0)
    if m.dispatch == "onehot":
        e_ids, pos = _dispatch_onehot(flat_e, E)
        x_in, order = xk, None
    else:
        order, e_ids, pos = _dispatch_indices(flat_e, E)
        x_in = xk[order]
    keep = pos < Ct
    ei = jnp.where(keep, e_ids, E)
    pi = jnp.where(keep, pos, 0)
    buf = jnp.zeros((E + 1, Ct, d), x_loc.dtype)
    buf = buf.at[ei, pi].set(x_in, mode="drop")[:E]        # (E, Ct, d)

    # ---- a2a over data: ship buffers to expert owners ----
    buf_x = jax.lax.all_to_all(buf, data_ax, split_axis=0, concat_axis=1,
                               tiled=True)                 # (E_loc, nd*Ct, d)
    # name the a2a results so the remat policy can pin them (recomputing
    # the forward a2a inside the backward doubles wire traffic — §Perf)
    buf_x = jax.ad_checkpoint.checkpoint_name(buf_x, "moe_a2a_in")
    wu = params["w_up"].astype(x_loc.dtype)                # (E_loc, d, f_loc)
    wg = params["w_gate"].astype(x_loc.dtype)
    wd = params["w_down"].astype(x_loc.dtype)
    h = jnp.einsum("ecd,edf->ecf", buf_x, wu)
    g = jnp.einsum("ecd,edf->ecf", buf_x, wg)
    out = jnp.einsum("ecf,efd->ecd", h * act(g), wd)       # partial over f
    # ---- a2a back ----
    out = jax.lax.all_to_all(out, data_ax, split_axis=1, concat_axis=0,
                             tiled=True)                   # (E, Ct, d)
    out = jax.ad_checkpoint.checkpoint_name(out, "moe_a2a_out")

    gathered = out[jnp.minimum(ei, E - 1), pi]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    if m.dispatch != "onehot":
        gathered = gathered[jnp.argsort(order)]
    y = jnp.sum(gathered.reshape(N, k, d)
                * gate.reshape(N, k)[..., None].astype(x_loc.dtype),
                axis=1).reshape(B_loc, T, d)

    if "shared" in params:
        sp = params["shared"]
        h = (x_loc @ sp["up"]["w"].astype(x_loc.dtype)) * act(
            x_loc @ sp["gate"]["w"].astype(x_loc.dtype))
        y = y + h @ sp["down"]["w"].astype(x_loc.dtype)
    # fold the f-contraction partials into one activation psum
    y = jax.lax.psum(y, model_ax)
    return y, aux


def moe_ffn_shard_map(params, x, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in replacement for moe_ffn when a mesh is active."""
    mesh = active_mesh()
    assert mesh is not None, "smap MoE needs an active mesh"
    axes = mesh.axis_names
    data_ax = "data"
    model_ax = "model"
    batch_axes = tuple(a for a in axes if a in ("pod", "data"))
    n_data = mesh.shape["data"]
    E = cfg.moe.n_routed
    assert E % n_data == 0, (E, n_data)

    pspec = {
        "router": {"w": P()},
        "w_up": P("data", None, "model"),
        "w_gate": P("data", None, "model"),
        "w_down": P("data", "model", None),
    }
    if "shared" in params:
        pspec["shared"] = {
            "up": {"w": P(None, "model")},
            "gate": {"w": P(None, "model")},
            "down": {"w": P("model", None)},
        }
    fn = shard_map(
        functools.partial(_local_moe, cfg=cfg, data_ax=data_ax,
                          model_ax=model_ax, n_data=n_data),
        mesh=mesh,
        in_specs=(pspec, P(batch_axes, None, None)),
        out_specs=(P(batch_axes, None, None), P()),
        check_rep=False)
    return fn(params, x)
