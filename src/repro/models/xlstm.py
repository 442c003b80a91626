"""xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory).

mLSTM training/prefill uses the *chunkwise-parallel* form — O(T·C) memory
instead of O(T^2) — with log-space gate stabilization; decode is the O(1)
recurrent update.  ``mlstm_recurrent`` is the step-by-step oracle used by
the tests.  sLSTM is inherently sequential (recurrent gate connections)
and runs under ``lax.scan``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import init_linear, linear
from repro.models.param import dense_init, ones_init, zeros_init
from repro.parallel.sharding import shard_act

NEG = -1e30


def _mdims(cfg):
    d_inner = int(cfg.xlstm.proj_factor * cfg.d_model)
    nh = cfg.n_heads
    dh = d_inner // nh
    return d_inner, nh, dh


# ---------------------------------------------------------------------------
# mLSTM cell — chunkwise parallel
# ---------------------------------------------------------------------------
def mlstm_chunkwise(q, k, v, li, lf, chunk: int):
    """q,k,v: (B,T,nh,dh);  li/lf: (B,T,nh) log input/forget gates.
    Returns h: (B,T,nh,dh) and final (C, n, m) state."""
    B, T, nh, dh = q.shape
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk
    scale = dh ** -0.5

    def resh(x):
        return jnp.moveaxis(x.reshape(B, nc, chunk, *x.shape[2:]), 1, 0)

    qs, ks, vs = resh(q * scale), resh(k), resh(v)          # (nc,B,C,nh,dh)
    lis, lfs = resh(li.astype(jnp.float32)), resh(lf.astype(jnp.float32))

    C0 = jnp.zeros((B, nh, dh, dh), jnp.float32)
    n0 = jnp.zeros((B, nh, dh), jnp.float32)
    m0 = jnp.full((B, nh), NEG, jnp.float32)

    def chunk_step(carry, inp):
        C_st, n_st, m_st = carry
        qc, kc, vc, lic, lfc = inp                          # (B,C,nh,*)
        b = jnp.cumsum(lfc, axis=1)                         # (B,C,nh)
        # intra-chunk log weights D[t,s] = b_t - b_s + li_s   (s <= t)
        D = b[:, :, None] - b[:, None, :] + lic[:, None, :]  # (B,t,s,nh)
        tri = jnp.tril(jnp.ones((qc.shape[1], qc.shape[1]), bool))
        D = jnp.where(tri[None, :, :, None], D, NEG)
        m_intra = jnp.max(D, axis=2)                        # (B,t,nh)
        m_inter = b + m_st[:, None, :]
        m_t = jnp.maximum(m_intra, m_inter)                 # (B,t,nh)
        S = jnp.exp(D - m_t[:, :, None])                    # (B,t,s,nh)
        qk = jnp.einsum("bthd,bshd->btsh", qc.astype(jnp.float32),
                        kc.astype(jnp.float32))
        W = S * qk
        num_intra = jnp.einsum("btsh,bshd->bthd", W, vc.astype(jnp.float32))
        den_intra = jnp.sum(W, axis=2)                      # (B,t,nh)
        c_inter = jnp.exp(m_inter - m_t)                    # (B,t,nh)
        num_inter = jnp.einsum("bthd,bhde->bthe", qc.astype(jnp.float32),
                               C_st) * c_inter[..., None]
        den_inter = jnp.einsum("bthd,bhd->bth", qc.astype(jnp.float32),
                               n_st) * c_inter
        num = num_intra + num_inter
        den = den_intra + den_inter
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]

        # ---- state update to end of chunk ----
        G = b[:, -1]                                        # (B,nh)
        a_log = G[:, None] - b + lic                        # (B,s,nh)
        m_new = jnp.maximum(G + m_st, jnp.max(a_log, axis=1))
        a = jnp.exp(a_log - m_new[:, None])
        decay = jnp.exp(G + m_st - m_new)
        C_new = (decay[:, :, None, None] * C_st
                 + jnp.einsum("bshd,bshe->bhde",
                              kc.astype(jnp.float32) * a[..., None],
                              vc.astype(jnp.float32)))
        n_new = decay[:, :, None] * n_st + jnp.sum(
            kc.astype(jnp.float32) * a[..., None], axis=1)
        return (C_new, n_new, m_new), h

    (Cf, nf, mf), hs = jax.lax.scan(
        chunk_step, (C0, n0, m0), (qs, ks, vs, lis, lfs))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, T, nh, dh)
    return h.astype(q.dtype), (Cf, nf, mf)


def mlstm_recurrent(q, k, v, li, lf, state=None):
    """Step-by-step oracle / decode. Shapes as above (any T)."""
    B, T, nh, dh = q.shape
    scale = dh ** -0.5
    if state is None:
        state = (jnp.zeros((B, nh, dh, dh), jnp.float32),
                 jnp.zeros((B, nh, dh), jnp.float32),
                 jnp.full((B, nh), NEG, jnp.float32))

    def step(carry, inp):
        C, n, m = carry
        qt, kt, vt, lit, lft = inp                          # (B,nh,dh)/(B,nh)
        m_new = jnp.maximum(lft + m, lit)
        f_ = jnp.exp(lft + m - m_new)[..., None]
        i_ = jnp.exp(lit - m_new)[..., None]
        C = f_[..., None] * C + i_[..., None] * (
            kt.astype(jnp.float32)[..., :, None]
            * vt.astype(jnp.float32)[..., None, :])
        n = f_ * n + i_ * kt.astype(jnp.float32)
        qf = qt.astype(jnp.float32) * scale
        num = jnp.einsum("bhd,bhde->bhe", qf, C)
        den = jnp.einsum("bhd,bhd->bh", qf, n)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
        return (C, n, m_new), h

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in
               (q, k, v, li.astype(jnp.float32), lf.astype(jnp.float32)))
    state, hs = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(hs, 0, 1).astype(q.dtype), state


# ---------------------------------------------------------------------------
# mLSTM block
# ---------------------------------------------------------------------------
def init_mlstm_block(key, cfg):
    d_inner, nh, dh = _mdims(cfg)
    ks = jax.random.split(key, 8)
    conv_w = cfg.xlstm.conv_width
    return {
        "up": init_linear(ks[0], cfg.d_model, 2 * d_inner, ("embed", "inner")),
        "conv_w": dense_init(ks[1], (conv_w, d_inner), ("conv", "inner"),
                             fan_in=conv_w),
        "conv_b": zeros_init((d_inner,), ("inner",)),
        "wq": init_linear(ks[2], d_inner, d_inner, ("inner", None)),
        "wk": init_linear(ks[3], d_inner, d_inner, ("inner", None)),
        "wv": init_linear(ks[4], d_inner, d_inner, ("inner", None)),
        "wi": init_linear(ks[5], cfg.d_model, nh, ("embed", None), use_bias=True),
        "wf": init_linear(ks[6], cfg.d_model, nh, ("embed", None), use_bias=True),
        "gn_scale": ones_init((d_inner,), ("inner",)),
        "down": init_linear(ks[7], d_inner, cfg.d_model, ("inner", "embed")),
    }


def _causal_conv(x, w, b):
    K = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + x.shape[1]] * w.astype(x.dtype)[i] for i in range(K))
    return out + b.astype(x.dtype)


def _group_norm(h, scale, nh, eps=1e-6):
    """Per-head RMS-style group norm. h: (B,T,nh,dh) -> (B,T,nh*dh)."""
    B, T, _, dh = h.shape
    hf = h.astype(jnp.float32)
    mu = jnp.mean(hf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(hf - mu), axis=-1, keepdims=True)
    hn = (hf - mu) * jax.lax.rsqrt(var + eps)
    return (hn.reshape(B, T, -1) * scale.astype(jnp.float32)).astype(h.dtype)


def mlstm_block(params, x, cfg, *, make_cache: bool = False, decode_state=None):
    """x: (B,T,d). If decode_state is given, runs the recurrent path."""
    with jax.named_scope("mlstm"):
        return _mlstm_block(params, x, cfg, make_cache, decode_state)


def _mlstm_block(params, x, cfg, make_cache, decode_state):
    d_inner, nh, dh = _mdims(cfg)
    B, T, _ = x.shape
    xz = linear(params["up"], x)
    xm, z = jnp.split(xz, 2, axis=-1)
    decode = decode_state is not None
    if decode:
        window = jnp.concatenate([decode_state["conv"].astype(xm.dtype), xm], 1)
        w = params["conv_w"]
        xc = jnp.einsum("bkd,kd->bd", window, w.astype(xm.dtype))[:, None] \
            + params["conv_b"].astype(xm.dtype)
        xc = jax.nn.silu(xc)
        new_conv = window[:, 1:]
    else:
        xc = jax.nn.silu(_causal_conv(xm, params["conv_w"], params["conv_b"]))
        xc = shard_act(xc, ("batch", None, "inner"))
    q = linear(params["wq"], xc).reshape(B, T, nh, dh)
    k = linear(params["wk"], xc).reshape(B, T, nh, dh)
    v = linear(params["wv"], xm).reshape(B, T, nh, dh)
    li = linear(params["wi"], x)                            # (B,T,nh) raw
    lf = jax.nn.log_sigmoid(linear(params["wf"], x).astype(jnp.float32))
    if decode:
        h, state = mlstm_recurrent(q, k, v, li, lf, decode_state["state"])
        new_state = {"conv": new_conv, "state": state}
    else:
        h, state = mlstm_chunkwise(q, k, v, li, lf,
                                   min(cfg.xlstm.chunk_size, T))
        new_state = None
        if make_cache:
            K = params["conv_w"].shape[0]
            conv = xm[:, -(K - 1):] if T >= K - 1 else jnp.pad(
                xm, ((0, 0), (K - 1 - T, 0), (0, 0)))
            new_state = {"conv": conv, "state": state}
    hn = _group_norm(h, params["gn_scale"], nh)
    out = linear(params["down"], hn * jax.nn.silu(z))
    return out, new_state


def init_mlstm_cache(cfg, batch: int, dtype=jnp.bfloat16):
    d_inner, nh, dh = _mdims(cfg)
    K = cfg.xlstm.conv_width
    return {"conv": jnp.zeros((batch, K - 1, d_inner), dtype),
            "state": (jnp.zeros((batch, nh, dh, dh), jnp.float32),
                      jnp.zeros((batch, nh, dh), jnp.float32),
                      jnp.full((batch, nh), NEG, jnp.float32))}


# ---------------------------------------------------------------------------
# sLSTM block (scalar memory, recurrent gates)
# ---------------------------------------------------------------------------
def init_slstm_block(key, cfg):
    nh = cfg.n_heads
    dh = cfg.d_model // nh
    ks = jax.random.split(key, 4)
    return {
        # 4 gates (i, f, z, o), input part
        "wx": init_linear(ks[0], cfg.d_model, 4 * cfg.d_model,
                          ("embed", "inner"), use_bias=True),
        # recurrent part: block-diagonal per head
        "r": dense_init(ks[1], (nh, dh, 4 * dh), (None, None, None),
                        fan_in=dh),
        "gn_scale": ones_init((cfg.d_model,), ("embed",)),
        "out": init_linear(ks[2], cfg.d_model, cfg.d_model,
                           ("embed", "embed2")),
    }


def slstm_block(params, x, cfg, state=None):
    """x: (B,T,d). Sequential scan (recurrent gate connections)."""
    with jax.named_scope("slstm"):
        return _slstm_block(params, x, cfg, state)


def _slstm_block(params, x, cfg, state):
    B, T, d = x.shape
    nh = cfg.n_heads
    dh = d // nh
    gx = linear(params["wx"], x).reshape(B, T, nh, 4 * dh)
    r = params["r"].astype(jnp.float32)
    if state is None:
        state = (jnp.zeros((B, nh, dh), jnp.float32),) * 3 + (
            jnp.full((B, nh, dh), NEG, jnp.float32),)

    def step(carry, gxt):
        c, n, h, m = carry
        rec = jnp.einsum("bhd,hde->bhe", h, r)              # (B,nh,4dh)
        g = gxt.astype(jnp.float32) + rec
        gi, gf, gz, go = jnp.split(g, 4, axis=-1)
        m_new = jnp.maximum(jax.nn.log_sigmoid(gf) + m, gi)
        i_ = jnp.exp(gi - m_new)
        f_ = jnp.exp(jax.nn.log_sigmoid(gf) + m - m_new)
        c = f_ * c + i_ * jnp.tanh(gz)
        n = f_ * n + i_
        h = jax.nn.sigmoid(go) * c / jnp.maximum(n, 1e-6)
        return (c, n, h, m_new), h

    state, hs = jax.lax.scan(step, state, jnp.moveaxis(gx, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, T, nh, dh)
    hn = _group_norm(h, params["gn_scale"], nh)
    return linear(params["out"], hn.astype(x.dtype)), state


def init_slstm_cache(cfg, batch: int):
    nh = cfg.n_heads
    dh = cfg.d_model // nh
    z = jnp.zeros((batch, nh, dh), jnp.float32)
    return (z, z, z, jnp.full((batch, nh, dh), NEG, jnp.float32))
