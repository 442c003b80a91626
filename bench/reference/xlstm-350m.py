"""Plain reference for the ``xlstm-350m`` configuration (arXiv:2405.04517).

A float32 forward pass in straightforward ``jax.numpy``, written from the
xLSTM paper's equations and the configuration file, sharing no code with
the served program: pre-norm residual blocks, seven mLSTM blocks and one
sLSTM block per group of eight, RMS norms, an untied output head.

* mLSTM in the paper's parallel form: log gate matrix
  ``D[t, s] = F_t - F_s + i_s`` (``F`` the cumulative log forget gate),
  stabilised by its row maximum ``m_t``, ``h_t = sum_s C[t, s] v_s /
  max(|sum_s C[t, s]|, exp(-m_t))``, ``C = (q k^T / sqrt(d)) * exp(D - m)``.
* sLSTM as the paper's recurrence, head-wise recurrent weights, under a
  ``lax.scan`` over time.

The structure is the served program's, which departs from the paper's
in the ways the configuration states, each a key of its file
(``STRUCTURE``): dense q, k and v projections, no learnable skip, mLSTM
gates that read the block's normed input; no convolution before the
sLSTM's gates, dense gate weights, and a linear instead of a feed-forward
after it.  A configuration that states another structure is refused.

``weights(seed, cfg)`` draws the benchmark's weights from the seed, in
the parameter layout the served program consumes.  ``logits(params,
tokens, cfg, rounding)`` gives float32 logits at every position; a
``rounding`` function applied at every activation boundary computes the
reference at a lower precision (the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30

# the structure this reference computes, as the configuration states it
STRUCTURE = {"mlstm_qkv_blockdiagonal": False, "mlstm_skip": False,
             "mlstm_gate_input": "block_input", "slstm_gate_conv": False,
             "slstm_gate_headwise": False, "slstm_mlp": False}


def _dims(cfg):
    d = cfg["d_model"]
    nh = cfg["n_heads"]
    d_inner = int(cfg["proj_factor"] * d)
    return d, nh, d_inner, d_inner // nh, d // nh


def weights(key, cfg):
    """Parameter tree from ``key``: normal weights scaled by 1/sqrt(fan-in),
    the branch outputs by a further 1/sqrt(2 * n_layers) (so the residual
    stream's scale does not grow with depth), forget-gate biases spread over
    [3, 6] (the paper's initialisation) and zero input-gate biases."""
    stated = {k: cfg.get(k) for k in STRUCTURE}
    if stated != STRUCTURE:
        raise ValueError(f"the reference computes {STRUCTURE}; the "
                         f"configuration states {stated}")
    d, nh, di, dhm, dhs = _dims(cfg)
    V, L, K = cfg["vocab_size"], cfg["n_layers"], cfg["conv_width"]
    g = cfg["slstm_every"]
    n_groups = L // g
    branch = cfg["branch_scale"] / np.sqrt(2.0 * L)
    keys = iter(jax.random.split(key, 8 + n_groups * g * 16))

    def normal(shape, fan_in, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * jnp.float32(scale / np.sqrt(fan_in)))

    def mlstm():
        return {
            "up": {"w": normal((d, 2 * di), d)},
            "conv_w": normal((K, di), K),
            "conv_b": jnp.zeros((di,), jnp.float32),
            "wq": {"w": normal((di, di), di)},
            "wk": {"w": normal((di, di), di)},
            "wv": {"w": normal((di, di), di)},
            "wi": {"w": normal((d, nh), d),
                   "b": jnp.full((nh,), cfg["igate_bias"], jnp.float32)},
            "wf": {"w": normal((d, nh), d),
                   "b": jnp.linspace(*cfg["fgate_bias"], nh,
                                     dtype=jnp.float32)},
            "gn_scale": jnp.ones((di,), jnp.float32),
            "down": {"w": normal((di, d), di, branch)},
        }

    def slstm():
        bias = jnp.zeros((nh, 4, dhs), jnp.float32)
        fgate = jnp.linspace(*cfg["fgate_bias"], nh)
        bias = bias.at[:, 1].set(fgate[:, None])
        return {
            "wx": {"w": normal((d, 4 * d), d),
                   "b": bias.reshape(4 * d)},
            "r": normal((nh, dhs, 4 * dhs), dhs, cfg["recurrent_scale"]),
            "gn_scale": jnp.ones((d,), jnp.float32),
            "out": {"w": normal((d, d), d, branch)},
        }

    groups = []
    for _ in range(n_groups):
        layer = {}
        for i in range(g):
            mix = slstm() if i == g - 1 else mlstm()
            layer[f"l{i}"] = {"norm1": {"scale": jnp.ones((d,), jnp.float32)},
                              "mix": mix}
        groups.append(layer)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *groups)
    return {
        "embed": {"table": normal((V, d), 1.0, cfg["embed_std"])},
        "stack": {"groups": stacked},
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "unembed": {"w": normal((d, V), d)},
    }


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _head_norm(h, scale):
    """h: (B, T, nh, dh): per-head mean/variance norm, flattened."""
    mu = jnp.mean(h, -1, keepdims=True)
    var = jnp.mean((h - mu) ** 2, -1, keepdims=True)
    hn = (h - mu) / jnp.sqrt(var + 1e-6)
    return hn.reshape(h.shape[0], h.shape[1], -1) * scale


def _mlstm(p, x, cfg, q_):
    d, nh, di, dh, _ = _dims(cfg)
    B, T, _ = x.shape
    xz = q_(x) @ q_(p["up"]["w"])
    xm, z = xz[..., :di], xz[..., di:]
    K = p["conv_w"].shape[0]
    pad = jnp.concatenate([jnp.zeros((B, K - 1, di), x.dtype), xm], 1)
    conv = sum(pad[:, j:j + T] * p["conv_w"][j] for j in range(K))
    xc = jax.nn.silu(conv + p["conv_b"])
    q = (q_(xc) @ q_(p["wq"]["w"])).reshape(B, T, nh, dh)
    k = (q_(xc) @ q_(p["wk"]["w"])).reshape(B, T, nh, dh)
    v = (q_(xm) @ q_(p["wv"]["w"])).reshape(B, T, nh, dh)
    li = x @ p["wi"]["w"] + p["wi"]["b"]                   # (B, T, nh)
    lf = jax.nn.log_sigmoid(x @ p["wf"]["w"] + p["wf"]["b"])
    F = jnp.cumsum(lf, axis=1)
    logd = (F[:, :, None, :] - F[:, None, :, :]
            + li[:, None, :, :])                           # (B, t, s, nh)
    causal = jnp.tril(jnp.ones((T, T), bool))[None, :, :, None]
    logd = jnp.where(causal, logd, NEG)
    m = jnp.max(logd, axis=2)                              # (B, t, nh)
    dmat = jnp.exp(logd - m[:, :, None, :])
    qk = jnp.einsum("bthd,bshd->btsh", q, k) / np.sqrt(dh)
    c = qk * dmat
    num = jnp.einsum("btsh,bshd->bthd", c, v)
    den = jnp.sum(c, axis=2)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m))[..., None]
    hn = _head_norm(h, p["gn_scale"])
    return q_(hn * jax.nn.silu(z)) @ q_(p["down"]["w"])


def _slstm(p, x, cfg, q_):
    d, nh, _, _, dh = _dims(cfg)
    B, T, _ = x.shape
    gx = (q_(x) @ q_(p["wx"]["w"]) + p["wx"]["b"]).reshape(B, T, nh, 4 * dh)
    r = p["r"]

    def step(carry, g_t):
        c, n, h, m = carry
        g = g_t + jnp.einsum("bhd,hde->bhe", h, r)
        gi, gf, gz, go = (g[..., j * dh:(j + 1) * dh] for j in range(4))
        logf = jax.nn.log_sigmoid(gf)
        m_new = jnp.maximum(logf + m, gi)
        i = jnp.exp(gi - m_new)
        f = jnp.exp(logf + m - m_new)
        c = f * c + i * jnp.tanh(gz)
        n = f * n + i
        h = jax.nn.sigmoid(go) * c / jnp.maximum(n, 1e-6)
        return (c, n, h, m_new), h

    zero = jnp.zeros((B, nh, dh), jnp.float32)
    init = (zero, zero, zero, jnp.full((B, nh, dh), NEG, jnp.float32))
    _, hs = jax.lax.scan(step, init, jnp.moveaxis(gx, 1, 0))
    hn = _head_norm(jnp.moveaxis(hs, 0, 1), p["gn_scale"])
    return q_(hn) @ q_(p["out"]["w"])


def _layer(p, x, cfg, kind, q_):
    h = _rms(x, p["norm1"]["scale"], cfg["norm_eps"])
    mix = _mlstm if kind == "mlstm" else _slstm
    return q_(x + mix(p["mix"], h, cfg, q_))


def logits(params, tokens, cfg, rounding=None, start=0,
           precision="highest"):
    """(B, T) int tokens -> (B, T - start, V) float32 logits of positions
    ``start`` onwards, at ``precision`` (``"highest"``: float32 matmuls);
    ``rounding`` (a function on arrays) lowers the precision of every
    activation boundary and matmul operand.  Each layer is one jitted
    program."""
    q_ = rounding or (lambda a: a)
    g = cfg["slstm_every"]
    hi = jax.default_matmul_precision(precision)
    layer = {kind: jax.jit(lambda p, x, kind=kind: _layer(p, x, cfg, kind,
                                                          q_))
             for kind in ("mlstm", "slstm")}

    @jax.jit
    def head(p, x):
        x = _rms(x[:, start:], p["final_norm"]["scale"], cfg["norm_eps"])
        return q_(x) @ q_(p["unembed"]["w"])

    with hi:
        x = q_(params["embed"]["table"][tokens])
        groups = params["stack"]["groups"]
        n_groups = jax.tree.leaves(groups)[0].shape[0]
        for gi in range(n_groups):
            for i in range(g):
                p = jax.tree.map(lambda a: a[gi], groups[f"l{i}"])
                x = layer["slstm" if i == g - 1 else "mlstm"](p, x)
        return head(params, x)


def token_gaps(params, seqs, served, cfg, prompt_len, rounding=None,
               precision=None):
    """Teacher-forced over ``seqs`` (B, T): each prompt and its served
    tokens but the last.  At each served position, the gap by which the
    logit of the token taken lies below the reference's best there, as a
    share of the reference's largest |logit| at that position.  The tokens
    taken are ``served`` (B, T - prompt_len + 1), or with ``rounding`` or
    ``precision`` the ones the reference computed at that lower precision
    puts first.  Returns the mean gap (``lm_gap_mean``), the widest
    (``lm_gap_max``) and the share of tokens that are not the reference's
    best (``lm_not_best``)."""
    ref = np.asarray(logits(params, seqs, cfg, start=prompt_len - 1),
                     np.float64)
    if rounding is None and precision is None:
        tok = np.asarray(served)
    else:
        low = logits(params, seqs, cfg, rounding, start=prompt_len - 1,
                     precision=precision or "highest")
        tok = np.asarray(jnp.argmax(low, -1))
    if tok.shape != ref.shape[:2]:
        return {"lm_gap_mean": float("inf"), "lm_gap_max": float("inf"),
                "lm_not_best": 1.0}
    best = ref.max(-1)
    got = np.take_along_axis(ref, tok[..., None], -1)[..., 0]
    gap = (best - got) / np.abs(ref).max(-1)
    return {"lm_gap_mean": float(gap.mean()), "lm_gap_max": float(gap.max()),
            "lm_not_best": float(np.mean(gap > 0))}


def fp8(a):
    """The control's precision: float8 (e4m3), the step below the
    bfloat16 activations the configuration states."""
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


# The control, and a further reading: the reference at the TPU's default
# matmul precision (one bfloat16 pass per float32 matmul), the step the
# served model would take without "highest".
CONTROLS = {"fp8": {"rounding": fp8}, "default": {"precision": "default"}}

# The limit of each compared number (``token_gaps``); PERF.md gives the
# readings it was set from.
LIMITS = {"lm_gap_mean": 7e-4}
