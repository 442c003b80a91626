"""Plain reference for the ``deepseek-v2-lite`` configuration
(arXiv:2405.04434; https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite,
``config.json`` and its ``modeling_deepseek.py``).

A float32 forward pass in straightforward ``jax.numpy``, written from
the published configuration and the DeepSeek-V2 modelling code, sharing
no code with the served program: pre-norm residual blocks with RMS norms
(eps ``rms_norm_eps``), a leading dense SwiGLU layer
(``first_k_dense_replace``), then expert layers; an untied output head.

* Attention: multi-head latent attention in the expanded form, no
  q-LoRA: ``q = x Wq`` split into a no-rope part (``qk_nope_head_dim``)
  and a rope part (``qk_rope_head_dim``); ``[c, k_pe] = x Wkv_a``, ``c``
  RMS-normed (``kv_lora_rank``), ``[k_nope, v] = c Wkv_b`` per head;
  ``k_pe`` one rotary key shared by every head; causal softmax at scale
  ``(nope + rope) ** -0.5 * mscale ** 2``.
* Positions: YaRN as the modelling code's ``DeepseekV2YarnRotaryEmbedding``
  builds it from ``rope_scaling``: frequency pairs ramp from the plain
  ``theta ** (-2i / dim)`` to it divided by ``factor`` between the
  pairs that turn ``beta_fast`` and ``beta_slow`` times over
  ``original_max_position_embeddings``; cos/sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``; the
  softmax's ``mscale = 0.1 * mscale_all_dim * ln(factor) + 1``.  Pair
  ``i`` of a head's rope dims is (``i``, ``i + rope/2``).  The published
  code first de-interleaves adjacent dims; with weights drawn at random
  that is a fixed permutation of the rope columns of ``Wq`` and
  ``Wkv_a``, and this reference, like the program, takes the columns as
  already de-interleaved.
* Experts: a softmax router over ``n_routed_experts``, greedy top
  ``num_experts_per_tok``, gates renormalised only where
  ``norm_topk_prob``, times ``routed_scaling_factor``.  Every routed
  expert is computed for every token and weighted by its gate (zero
  where it was not chosen), so nothing can be dropped; the
  ``n_shared_experts`` shared experts are one SwiGLU of width
  ``n_shared_experts * moe_intermediate_size``, added.

``weights(key, cfg)`` draws the benchmark's weights from the seed, in
bfloat16 (the published dtype) and in the parameter layout the served
program consumes; the reference upcasts those same values.
``logits(params, tokens, cfg, rounding, start, precision)`` gives
float32 logits, one row at a time (the chip's memory); a ``rounding``
function applied at every activation boundary and matmul operand
computes the reference at a lower precision (the control).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
F32 = jnp.float32

# the configuration's one departure from the source, as the benchmark
# states it: the depth served (the source has 27 layers).  The forward
# below takes any depth.
STRUCTURE = {"n_layers": 5}

# what the source fixes and this reference computes
SOURCE = {"scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
          "topk_group": 1, "moe_layer_freq": 1, "hidden_act": "silu",
          "attention_bias": False, "tie_word_embeddings": False,
          "q_lora_rank": None}


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"])


def weights(key, cfg):
    """Parameter tree from ``key``: normal weights scaled by 1/sqrt(fan-in),
    the branch outputs (attention out, feed-forward and expert down) by a
    further 1/sqrt(2 * n_layers), so that the residual stream's scale does
    not grow with depth; embedding std 1; norms 1.  All bfloat16."""
    stated = {k: cfg.get(k) for k in SOURCE}
    if stated != SOURCE:
        raise ValueError(f"the reference computes {SOURCE}; the "
                         f"configuration states {stated}")
    d, H, dn, dr, dv, kvl = _dims(cfg)
    V, L = cfg["vocab_size"], cfg["n_layers"]
    E, Fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    Fs = cfg["n_shared_experts"] * Fe
    n_dense = cfg["first_k_dense_replace"]
    G = L - n_dense
    branch = 1.0 / np.sqrt(2.0 * L)
    keys = iter(jax.random.split(key, 64))
    bf16 = jnp.bfloat16

    def normal(shape, fan_in, scale=1.0):
        return (jax.random.normal(next(keys), shape, bf16)
                * jnp.asarray(scale / np.sqrt(fan_in), bf16))

    def ones(*shape):
        return jnp.ones(shape, bf16)

    def mla(*g):
        return {"wq": {"w": normal(g + (d, H * (dn + dr)), d)},
                "wkv_a": {"w": normal(g + (d, kvl + dr), d)},
                "kv_norm": ones(*g, kvl),
                "wkv_b": {"w": normal(g + (kvl, H * (dn + dv)), kvl)},
                "wo": {"w": normal(g + (H * dv, d), H * dv, branch)}}

    def glu(width, *g):
        return {"up": {"w": normal(g + (d, width), d)},
                "gate": {"w": normal(g + (d, width), d)},
                "down": {"w": normal(g + (width, d), width, branch)}}

    prefix = [{"norm1": {"scale": ones(d)}, "mix": mla(),
               "norm2": {"scale": ones(d)}, "ffn": glu(cfg["intermediate_size"])}
              for _ in range(n_dense)]
    groups = {"l0": {
        "norm1": {"scale": ones(G, d)}, "mix": mla(G),
        "norm2": {"scale": ones(G, d)},
        "ffn": {"router": {"w": normal((G, d, E), d)},
                "w_up": normal((G, E, d, Fe), d),
                "w_gate": normal((G, E, d, Fe), d),
                "w_down": normal((G, E, Fe, d), Fe, branch),
                "shared": glu(Fs, G)}}}
    return {"embed": {"table": normal((V, d), 1.0, cfg["embed_std"])},
            "stack": {"groups": groups, "prefix": prefix},
            "final_norm": {"scale": ones(d)},
            "unembed": {"w": normal((d, V), d)}}


def _f32(a):
    return a.astype(F32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope(cfg, positions):
    """(sin, cos), each (T, rope/2), and the softmax scale, from the
    configuration's ``rope_theta`` and ``rope_scaling`` (YaRN)."""
    dr, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    y = cfg["rope_scaling"]
    inv = 1.0 / base ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
    amp, scale = 1.0, (cfg["qk_nope_head_dim"] + dr) ** -0.5
    if y is not None:
        if y["type"] != "yarn":
            raise ValueError(f"rope_scaling {y['type']!r} is not YaRN")
        f, orig = float(y["factor"]), y["original_max_position_embeddings"]

        def turn_dim(n_rot):
            return (dr * math.log(orig / (n_rot * 2 * math.pi))
                    / (2 * math.log(base)))

        lo = max(math.floor(turn_dim(y["beta_fast"])), 0)
        hi = min(math.ceil(turn_dim(y["beta_slow"])), dr - 1)
        if lo == hi:
            hi += 0.001
        ramp = np.clip((np.arange(dr // 2) - lo) / (hi - lo), 0.0, 1.0)
        keep = 1.0 - ramp                 # the share of the plain frequency
        inv = inv / f * (1.0 - keep) + inv * keep
        amp = (_yarn_mscale(f, y["mscale"])
               / _yarn_mscale(f, y["mscale_all_dim"]))
        scale *= _yarn_mscale(f, y["mscale_all_dim"]) ** 2
    ang = np.asarray(positions, np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.sin(ang) * amp, F32),
            jnp.asarray(np.cos(ang) * amp, F32), scale)


def _rotate(x, sin, cos):
    """x (B, T, heads, rope): pair i is (x[i], x[i + rope/2])."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    s, c = sin[None, :, None, :], cos[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _mla(p, x, cfg, q_, sin, cos, scale):
    d, H, dn, dr, dv, kvl = _dims(cfg)
    B, T, _ = x.shape
    q = (q_(x) @ q_(_f32(p["wq"]["w"]))).reshape(B, T, H, dn + dr)
    kv = q_(x) @ q_(_f32(p["wkv_a"]["w"]))
    c = _rms(kv[..., :kvl], _f32(p["kv_norm"]), cfg["rms_norm_eps"])
    k_pe = _rotate(kv[..., None, kvl:], sin, cos)           # (B, T, 1, dr)
    kvb = (q_(c) @ q_(_f32(p["wkv_b"]["w"]))).reshape(B, T, H, dn + dv)
    qf = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], sin, cos)], -1)
    k = jnp.concatenate([kvb[..., :dn],
                         jnp.broadcast_to(k_pe, (B, T, H, dr))], -1)
    s = jnp.einsum("bthd,bshd->bhts", q_(qf), q_(k)) * scale
    causal = jnp.tril(jnp.ones((T, T), bool))
    w = jax.nn.softmax(jnp.where(causal, s, NEG), -1)
    o = jnp.einsum("bhts,bshd->bthd", q_(w), q_(kvb[..., dn:]))
    return q_(o.reshape(B, T, H * dv)) @ q_(_f32(p["wo"]["w"]))


def _glu(p, x, q_):
    xq = q_(x)
    h = jax.nn.silu(xq @ q_(_f32(p["gate"]["w"]))) * (xq @ q_(_f32(p["up"]["w"])))
    return q_(h) @ q_(_f32(p["down"]["w"]))


def _experts(p, x, cfg, q_):
    """The routed experts, every one for every token, each weighted by its
    gate (zero where not among the top k), and the shared experts."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(q_(x) @ q_(_f32(p["router"]["w"])), -1)
    gates, idx = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * cfg["routed_scaling_factor"]
    weight = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32) * gates[..., None],
                     axis=-2)                                # (B, T, E)
    xq = q_(x)

    def expert(acc, w):
        up, gate, down, g = w
        h = jax.nn.silu(xq @ q_(_f32(gate))) * (xq @ q_(_f32(up)))
        return acc + g[..., None] * (q_(h) @ q_(_f32(down))), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (p["w_up"], p["w_gate"], p["w_down"], jnp.moveaxis(weight, -1, 0)))
    return routed + _glu(p["shared"], x, q_)


def _layer(p, x, cfg, q_, sin, cos, scale, moe):
    eps = cfg["rms_norm_eps"]
    h = _rms(x, _f32(p["norm1"]["scale"]), eps)
    x = q_(x + _mla(p["mix"], h, cfg, q_, sin, cos, scale))
    h = _rms(x, _f32(p["norm2"]["scale"]), eps)
    ffn = _experts if moe else (lambda pf, h, cfg, q_: _glu(pf, h, q_))
    return q_(x + ffn(p["ffn"], h, cfg, q_))


def logits(params, tokens, cfg, rounding=None, start=0,
           precision="highest"):
    """(B, T) int tokens -> (B, T - start, V) float32 logits of positions
    ``start`` onwards, at ``precision`` (``"highest"``: float32 matmuls);
    ``rounding`` (a function on arrays) lowers the precision of every
    activation boundary and matmul operand.  Each layer is one jitted
    program, run one row at a time."""
    q_ = rounding or (lambda a: a)
    tokens = np.asarray(tokens)
    sin, cos, scale = rope(cfg, np.arange(tokens.shape[1]))
    layer = {moe: jax.jit(lambda p, x, moe=moe: _layer(p, x, cfg, q_, sin,
                                                       cos, scale, moe))
             for moe in (False, True)}

    @jax.jit
    def head(p, x):
        x = _rms(x[:, start:], _f32(p["final_norm"]["scale"]),
                 cfg["rms_norm_eps"])
        return q_(x) @ q_(_f32(p["unembed"]["w"]))

    groups = params["stack"]["groups"]["l0"]
    n_groups = jax.tree.leaves(groups)[0].shape[0]
    with jax.default_matmul_precision(precision):
        xs = [q_(_f32(params["embed"]["table"][row[None]])) for row in tokens]
        for p in params["stack"]["prefix"]:
            xs = [layer[False](p, x) for x in xs]
        for g in range(n_groups):
            p = jax.tree.map(lambda a: a[g], groups)
            xs = [layer[True](p, x) for x in xs]
        return np.concatenate([np.asarray(head(params, x)) for x in xs], 0)


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
        tok = np.asarray(np.argmax(low, -1))
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
    bfloat16 the configuration states."""
    return a.astype(jnp.float8_e4m3fn).astype(F32)


# The control, and a further reading: the reference at the TPU's default
# matmul precision (one bfloat16 pass per float32 matmul).
CONTROLS = {"fp8": {"rounding": fp8}, "default": {"precision": "default"}}

# The limit of each compared number (``token_gaps``); PERF.md gives the
# readings it was set from.
LIMITS = {"lm_gap_mean": 7e-4}
