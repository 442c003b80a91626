"""The DeepSeek-V2 family, as the benchmark sees it: the configuration's
numbers (the published ``config.json``'s keys) that must equal the
program's own, the program at the configuration's sizes, and the
benchmark's own count of a token's parameters and operations, from
shapes alone.

A configuration names its family (``"family": "deepseek_v2"``); the
``lm`` system and the ``lm_mfu`` reader find this file by that name.
The served depth is ``n_layers``; ``num_hidden_layers`` keeps the
source's.
"""
from __future__ import annotations

import dataclasses

# what the program's router computes, whatever its configuration says
ROUTER = {"scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
          "topk_group": 1, "moe_layer_freq": 1, "routed_scaling_factor": 1}
YARN = ("factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "mscale", "mscale_all_dim")


def _arch_numbers(cfg: dict) -> dict:
    """The configuration in the program's names: ``ArchConfig`` fields,
    and the MLA, MoE and YaRN groups as dicts."""
    y = cfg["rope_scaling"]
    return {
        "n_layers": cfg["n_layers"], "d_model": cfg["hidden_size"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "d_ff": cfg["intermediate_size"], "vocab_size": cfg["vocab_size"],
        "head_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        "norm_eps": cfg["rms_norm_eps"], "rope_theta": cfg["rope_theta"],
        "tie_embeddings": cfg["tie_word_embeddings"],
        "act": cfg["hidden_act"], "use_bias": cfg["attention_bias"],
        "attn_type": "mla",
        "mla": {"kv_lora_rank": cfg["kv_lora_rank"],
                "q_lora_rank": cfg["q_lora_rank"] or 0,
                "qk_nope_head_dim": cfg["qk_nope_head_dim"],
                "qk_rope_head_dim": cfg["qk_rope_head_dim"],
                "v_head_dim": cfg["v_head_dim"]},
        "moe": {"n_routed": cfg["n_routed_experts"],
                "n_shared": cfg["n_shared_experts"],
                "top_k": cfg["num_experts_per_tok"],
                "d_ff": cfg["moe_intermediate_size"],
                "n_dense_layers": cfg["first_k_dense_replace"],
                "norm_topk_prob": cfg["norm_topk_prob"]},
        "rope_scaling": ({k: y[k] for k in YARN} if y is not None
                         else None),
    }


def _program_numbers(arch) -> dict:
    have = {k: getattr(arch, k) for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff", "vocab_size",
        "norm_eps", "rope_theta", "tie_embeddings", "act", "use_bias",
        "attn_type")}
    have["head_dim"] = arch.head_dim_()
    have["mla"] = {k: getattr(arch.mla, k) for k in (
        "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim")}
    have["moe"] = {k: getattr(arch.moe, k) for k in (
        "n_routed", "n_shared", "top_k", "d_ff", "n_dense_layers",
        "norm_topk_prob")}
    y = arch.rope_scaling
    have["rope_scaling"] = ({k: getattr(y, k) for k in YARN}
                            if y is not None else None)
    return have


def check_program(cfg: dict, arch) -> None:
    """Raise unless the program's ``arch`` is the configuration."""
    router = {k: cfg[k] for k in ROUTER}
    want, have = _arch_numbers(cfg), _program_numbers(arch)
    if router != ROUTER or cfg["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"the program routes by {ROUTER} with YaRN "
                         f"positions; configuration {cfg['name']} states "
                         f"{router}, {cfg['rope_scaling']}")
    if want != have:
        raise ValueError(f"configuration {cfg['name']} is not what the "
                         f"program runs as {arch.name}: {want} != {have}")


def resized(arch, cfg: dict):
    """The program's ``arch`` at the configuration's sizes: its depth
    (the benchmark's cut), and every width in the benchmark's own tests,
    which run it tiny."""
    n = _arch_numbers(cfg)
    groups = {k: n.pop(k) for k in ("mla", "moe", "rope_scaling")}
    return arch.replace(
        **n, mla=dataclasses.replace(arch.mla, **groups["mla"]),
        moe=dataclasses.replace(arch.moe, **groups["moe"]),
        rope_scaling=dataclasses.replace(arch.rope_scaling,
                                         **groups["rope_scaling"]))


def _per_layer(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    kvl, Fe = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    E = cfg["n_routed_experts"]
    expert = 3 * d * Fe
    return {
        "mla": d * H * (dn + dr) + d * (kvl + dr) + kvl * H * (dn + dv)
               + H * dv * d,
        "norms": 2 * d + kvl,
        "dense": 3 * d * cfg["intermediate_size"],
        "router": d * E,
        "expert": expert,
        "routed": E * expert,
        "shared": cfg["n_shared_experts"] * expert,
    }


def params(cfg: dict) -> dict:
    """Parameter counts of the served model: ``total``; ``embed`` (gathered,
    not multiplied); ``matmul``, the weights that multiply a token's
    activations (the dense layer, attention, router, its
    ``num_experts_per_tok`` routed experts, the shared experts, the head);
    ``experts``, every routed expert's weights."""
    p = _per_layer(cfg)
    L, d, V = cfg["n_layers"], cfg["hidden_size"], cfg["vocab_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = L - n_dense
    k = cfg["num_experts_per_tok"]
    head = d * V
    matmul = (L * p["mla"] + n_dense * p["dense"]
              + n_moe * (p["router"] + k * p["expert"] + p["shared"]) + head)
    total = (2 * d * V + L * (p["mla"] + p["norms"]) + n_dense * p["dense"]
             + n_moe * (p["router"] + p["routed"] + p["shared"]) + d)
    return {"matmul": matmul, "embed": d * V, "experts": n_moe * p["routed"],
            "total": total}


def token_flops(cfg: dict) -> float:
    """Operations of one token through the model: two per weight that
    multiplies its activations (``params(cfg)["matmul"]``).  The
    attention's score and value products, which grow with the context,
    are left out (at a 1536-token prompt, about 3% of a prefill token)."""
    return 2.0 * params(cfg)["matmul"]
