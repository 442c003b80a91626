"""DeepSeek-V2-Lite served by the program against the benchmark's plain
reference (``bench/reference/deepseek-v2-lite.py``), at a small size on
the CPU with seeded random bfloat16 weights: prefill logits, prefill then
slot-step decodes with slots at different depths, a router that sends
every token to the same experts, YaRN's table and softmax scale, and
gates left unnormalised.

Tolerances are relative to the reference's largest |logit| at a position.
The program keeps its activations in bfloat16 (8 significant bits); over
five layers its logits were seen to move up to 0.03 of that scale here,
so prefill and decode logits are held to ``TOL`` = 0.08.  The same
reference computed in float8 (e4m3, 4 significant bits), the step below,
moves 0.21 and must fail it (``test_fp8_fails_the_tolerance``).  Served
tokens are held to a logit gap of ``GAP_TOL`` below the reference's best
(the benchmark's ``lm_gap_mean``, per token).
"""
import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import layers, model_zoo, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 0.08
GAP_TOL = 0.02
PROMPT, NEW, CACHE = 12, 6, 24

# the published widths cut small; YaRN, the router and the layer pattern
# are the configuration's own
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
            v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=16, num_experts_per_tok=6, vocab_size=512)


def _load(kind, name):
    path = os.path.join(ROOT, "bench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"t_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    ref = _load("reference", "deepseek-v2-lite")
    family = _load("families", "deepseek_v2")
    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-v2-lite.json")) as f:
        cfg = dict(json.load(f), **TINY)
    arch = family.resized(registry.get("deepseek-v2-lite-16b"), cfg)
    family.check_program(cfg, arch)
    params = jax.jit(lambda k: ref.weights(k, cfg))(jax.random.key(3))
    return types.SimpleNamespace(ref=ref, cfg=cfg, arch=arch, params=params)


def _tokens(n, length, seed, vocab=TINY["vocab_size"]):
    return np.asarray(jax.random.randint(jax.random.key(seed), (n, length),
                                         0, vocab), np.int32)


def _rel(got, want):
    want = np.asarray(want, np.float64)
    return float((np.abs(np.asarray(got, np.float64) - want)
                  / np.abs(want).max(-1, keepdims=True)).max())


def _skewed(params):
    """A router of zeros: every expert ties, and top-k takes the lowest
    ids, so every token of every row picks experts 0..k-1."""
    groups = params["stack"]["groups"]["l0"]
    router = {"w": jnp.zeros_like(groups["ffn"]["router"]["w"])}
    ffn = dict(groups["ffn"], router=router)
    stack = dict(params["stack"], groups={"l0": dict(groups, ffn=ffn)})
    return dict(params, stack=stack)


@pytest.mark.parametrize("skew", [False, True], ids=["random", "skewed"])
def test_prefill_logits_match_reference(model, skew):
    """Prefill logits at every position equal the reference's, for rows
    prefilled together and alone; the skewed router sends all 3 x 20 x 6
    assignments to 6 of 16 experts, which a capacity of 1.25 x the mean
    per expert would cut."""
    params = _skewed(model.params) if skew else model.params
    toks = _tokens(3, 20, 5)
    want = model.ref.logits(params, toks, model.cfg)
    batch, caches, route = model_zoo.prefill(
        model.arch, params, {"tokens": jnp.asarray(toks)}, cache_len=CACHE,
        with_route=True)
    assert "route" not in caches
    assert _rel(batch, want) < TOL
    alone = model_zoo.prefill(model.arch, params,
                              {"tokens": jnp.asarray(toks[1:2, :16])},
                              cache_len=CACHE)[0]
    assert _rel(alone, want[1:2, :16]) < TOL
    # the call's counts: per expert layer, row and expert
    counts = np.asarray(route["l0"])
    assert counts.shape == (4, 3, 16)
    assert (counts.sum(-1) == 20 * 6).all()
    if skew:
        assert (counts[..., :6] == 20).all()


def test_fp8_fails_the_tolerance(model):
    toks = _tokens(2, 20, 5)
    want = model.ref.logits(model.params, toks, model.cfg)
    low = model.ref.logits(model.params, toks, model.cfg,
                           rounding=model.ref.fp8)
    assert _rel(low, want) > TOL


def _decode_logits(arch, params, tokens, caches, positions):
    """One decode step per row, each at its own position: the rows of
    the slot step, with logits."""
    def row(tok, cache, pos):
        cache = {"groups": jax.tree.map(lambda a: a[:, None],
                                        cache["groups"]),
                 "prefix": [jax.tree.map(lambda a: a[None], c)
                            for c in cache["prefix"]]}
        lg, new = model_zoo.decode_step(arch, params, tok[None, None], cache,
                                        pos)
        new = {"groups": jax.tree.map(lambda a: a[:, 0], new["groups"]),
               "prefix": [jax.tree.map(lambda a: a[0], c)
                          for c in new["prefix"]]}
        return lg[0, 0], new

    axes = {"groups": 1, "prefix": 0}
    return jax.jit(jax.vmap(row, in_axes=(0, axes, 0),
                            out_axes=(0, axes)))(tokens, caches, positions)


@pytest.mark.parametrize("skew", [False, True], ids=["random", "skewed"])
def test_decode_at_different_depths_matches_reference(model, skew):
    """Rows prefilled at different lengths (8, 10, 12) decode together,
    each at its own position, for 5 steps; every step's logits equal the
    reference's full forward over the row's tokens so far."""
    params = _skewed(model.params) if skew else model.params
    toks = _tokens(3, 12 + 5, 7)
    lens = [8, 10, 12]
    rows = []
    for b, n in enumerate(lens):
        _, c = model_zoo.prefill(model.arch, params,
                                 {"tokens": jnp.asarray(toks[b:b + 1, :n])},
                                 cache_len=CACHE)
        rows.append(c)
    caches = {"groups": jax.tree.map(lambda *a: jnp.concatenate(a, 1),
                                     *[r["groups"] for r in rows]),
              "prefix": [jax.tree.map(lambda *a: jnp.concatenate(a, 0), *p)
                         for p in zip(*[r["prefix"] for r in rows])]}
    pos = np.asarray(lens, np.int32)
    want = model.ref.logits(params, toks, model.cfg)
    for _ in range(5):
        step_tok = jnp.asarray(toks[np.arange(3), pos])
        lg, caches = _decode_logits(model.arch, params, step_tok, caches,
                                    jnp.asarray(pos))
        assert _rel(lg, want[np.arange(3), pos]) < TOL
        pos = pos + 1


def test_served_tokens_match_reference(model):
    """The continuous engine's stepper: rows join a shape-built slot
    state at different steps and decode side by side; every served
    token's reference logit lies within ``GAP_TOL`` of the reference's
    best, and the routing args read what the calls routed."""
    from repro.serve.continuous import LMStepper

    st = LMStepper(model.arch, model.params, prompt_len=PROMPT,
                   new_tokens=NEW, n_slots=4)
    prompts = _tokens(3, PROMPT, 11)
    state = st.init_slots()
    served = {}
    live = []
    for step in range(NEW + 2):
        if step < 3:
            spec = types.SimpleNamespace(arrays=(prompts[step:step + 1],))
            ((row, first, _),) = st.prefill(spec)
            load = st.route_args("prefill")["expert_load_max"]
            assert 1.0 <= load <= 16 / 6
            state = st.insert(state, step, row)
            served[step] = [first]
            live.append(step)
        state, outs = st.step(state)
        hit = st.route_args("decode", live)["experts_hit"]
        assert 6 <= hit <= min(6 * len(live), 16)
        for slot in live:
            if len(served[slot]) <= NEW:
                served[slot].append(int(outs[slot]))
    seqs = np.stack([np.concatenate([prompts[s], served[s][:NEW]])
                     for s in range(3)])
    got = np.asarray([served[s] for s in range(3)])
    gaps = model.ref.token_gaps(model.params, seqs, got, model.cfg, PROMPT)
    assert gaps["lm_gap_max"] < GAP_TOL, gaps


def test_yarn_table_and_softmax_scale():
    """The published YaRN (factor 40 over 4096 positions, beta 32 / 1,
    rope dim 64): pairs 0-10 keep ``theta^(-2i/64)``, pairs 23-31 are
    divided by 40, a linear ramp lies between; the softmax scale is
    ``192^-0.5 * (0.1 * 0.707 * ln 40 + 1)^2``; cos/sin keep amplitude 1;
    the reference builds the same table."""
    arch = registry.get("deepseek-v2-lite-16b")
    y = arch.rope_scaling
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    factor = np.asarray(layers.yarn_freq_factor(64, 10000.0, y))
    np.testing.assert_allclose(factor[:11], 1.0)
    np.testing.assert_allclose(factor[23:], 1 / 40, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(factor[11:23], 1 - ramp + ramp / 40,
                               rtol=1e-6)
    pos = np.arange(0, 4000, 37)
    sin, cos = layers.rope_table(64, 0, 10000.0, jnp.asarray(pos), y)
    ang = pos[:, None] * (plain * factor)[None]
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=2e-3)
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=2e-3)
    mscale2 = (0.1 * 0.707 * np.log(40) + 1) ** 2
    assert mscale2 == pytest.approx(1.5896, abs=1e-4)
    assert layers.softmax_scale(arch, 192) == pytest.approx(
        192 ** -0.5 * mscale2, rel=1e-9)
    ref = _load("reference", "deepseek-v2-lite")
    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-v2-lite.json")) as f:
        cfg = json.load(f)
    rsin, rcos, rscale = ref.rope(cfg, pos)
    np.testing.assert_allclose(np.asarray(rsin), np.asarray(sin), atol=2e-3)
    np.testing.assert_allclose(np.asarray(rcos), np.asarray(cos), atol=2e-3)
    assert rscale == pytest.approx(layers.softmax_scale(arch, 192), rel=1e-9)


@pytest.mark.parametrize("norm", [False, True])
def test_gates_renormalised_only_where_asked(norm):
    """``norm_topk_prob`` false leaves the top-k gates at their softmax
    probabilities; true divides them by their sum."""
    import dataclasses

    arch = registry.get("deepseek-v2-lite-16b").reduced()
    arch = arch.replace(moe=dataclasses.replace(arch.moe, top_k=2,
                                                norm_topk_prob=norm))
    p = {"router": {"w": jax.random.normal(jax.random.key(0),
                                           (arch.d_model, 8))
                    / np.sqrt(arch.d_model)}}
    x = jax.random.normal(jax.random.key(1), (2, 5, arch.d_model))
    probs, gates, idx = moe.route(p, x, arch)
    top = np.sort(np.asarray(probs), -1)[..., ::-1][..., :2]
    want = top / top.sum(-1, keepdims=True) if norm else top
    np.testing.assert_allclose(np.asarray(gates), want, rtol=1e-6)
    assert (np.asarray(gates).sum(-1) < 1.0 - 1e-3).all() != norm
