"""The xLSTM family, as the benchmark sees it: the configuration's numbers
that must equal the program's own, and the benchmark's own count of a
token's operations, from shapes alone.

A configuration names its family (``"family": "xlstm"``); the ``lm``
system and the ``lm_mfu`` reader find this file by that name.
"""
from __future__ import annotations

import dataclasses

# the configuration's numbers that must equal the program's own config
ARCH_KEYS = ("n_layers", "d_model", "n_heads", "vocab_size")
XLSTM_KEYS = ("slstm_every", "proj_factor", "conv_width", "chunk_size")


def check_program(cfg: dict, arch) -> None:
    """Raise unless the program's ``arch`` is the configuration."""
    want = {k: cfg[k] for k in ARCH_KEYS}
    have = {k: getattr(arch, k) for k in ARCH_KEYS}
    for k in ("norm_eps", "tie_embeddings"):
        want[k], have[k] = cfg[k], getattr(arch, k)
    for k in XLSTM_KEYS:
        want[k], have[k] = cfg[k], getattr(arch.xlstm, k)
    if want != have:
        raise ValueError(f"configuration {cfg['name']} is not what the "
                         f"program runs as {arch.name}: {want} != {have}")


def resized(arch, cfg: dict):
    """The program's ``arch`` at the configuration's sizes (the
    benchmark's own tests run it tiny)."""
    return arch.replace(
        **{k: cfg[k] for k in ARCH_KEYS},
        head_dim=cfg["d_model"] // cfg["n_heads"],
        xlstm=dataclasses.replace(arch.xlstm,
                                  **{k: cfg[k] for k in XLSTM_KEYS}))


def params(cfg: dict) -> dict:
    """Parameter counts of the xLSTM stack: ``matmul`` (weights that
    multiply an activation every token), ``embed`` (gathered, not
    multiplied) and ``total``."""
    d, nh, V, L = (cfg["d_model"], cfg["n_heads"], cfg["vocab_size"],
                   cfg["n_layers"])
    di = int(cfg["proj_factor"] * d)
    dhs = d // nh
    k = cfg["conv_width"]
    n_s = L // cfg["slstm_every"]
    n_m = L - n_s
    m_mat = d * 2 * di + 3 * di * di + 2 * d * nh + di * d
    m_other = k * di + di + 2 * nh + di + d
    s_mat = d * 4 * d + nh * dhs * 4 * dhs + d * d
    s_other = 4 * d + d + d
    matmul = n_m * m_mat + n_s * s_mat + d * V
    embed = V * d
    total = matmul + embed + n_m * m_other + n_s * s_other + d
    return {"matmul": matmul, "embed": embed, "total": total}


def token_flops(cfg: dict) -> float:
    """Operations of one token through the model in the recurrent form:
    two per matmul weight, plus each mLSTM cell's state update and read
    (outer product into, and a product with, an (nh, dh, dh) state)."""
    d, nh, L = cfg["d_model"], cfg["n_heads"], cfg["n_layers"]
    di = int(cfg["proj_factor"] * d)
    dh = di // nh
    n_m = L - L // cfg["slstm_every"]
    cell = n_m * nh * (2 * dh * dh + 2 * dh * dh + 4 * dh)
    return 2.0 * params(cfg)["matmul"] + cell

