"""The control, at a size a test run holds: the configuration's
reference computed one precision below what it states fails the cell's
compared number, while the program's own answers pass."""
import json
import os

import benchpaths  # noqa: F401
import numpy as np
import pytest

from benchlib import files

XL = files.module("reference", "xlstm-350m")


def _tiny_xlstm():
    cfg = json.load(open(os.path.join(files.BENCH, "configs",
                                      "xlstm-350m.json")))
    cfg.update(n_layers=8, d_model=128, vocab_size=512)
    return cfg


@pytest.mark.parametrize("seed", [5, 17, 2**31 + 3])
def test_xlstm_program_passes_and_control_fails(seed):
    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.models import model_zoo
    cfg = _tiny_xlstm()
    arch = registry.get("xlstm-350m").replace(
        n_layers=8, d_model=128, vocab_size=512, head_dim=32)
    key = jax.random.key(seed % (1 << 31))
    params = jax.jit(lambda k: XL.weights(k, cfg))(key)
    seqs = np.asarray(jax.random.randint(jax.random.fold_in(key, 1),
                                         (4, 96), 0, 512))
    prompt_len = 64
    with jax.default_matmul_precision("highest"):
        logits = model_zoo.forward(arch, params, {"tokens": seqs})[0]
    served = np.asarray(jnp.argmax(logits.astype(jnp.float32), -1)
                        )[:, prompt_len - 1:]
    prog = XL.token_gaps(params, seqs, served, cfg, prompt_len)
    ctl = XL.token_gaps(params, seqs, served, cfg, prompt_len, XL.fp8)
    for name, lim in XL.LIMITS.items():
        assert prog[name] <= lim < ctl[name], (name, prog, ctl)


def test_token_gaps_read_the_tokens_taken():
    import jax
    cfg = _tiny_xlstm()
    params = jax.jit(lambda k: XL.weights(k, cfg))(jax.random.key(3))
    seqs = np.asarray(jax.random.randint(jax.random.key(4), (2, 40), 0, 512))
    ref = np.asarray(XL.logits(params, seqs, cfg, start=31))
    best = ref.argmax(-1)
    exact = XL.token_gaps(params, seqs, best, cfg, 32)
    assert exact == {"lm_gap_mean": 0.0, "lm_gap_max": 0.0,
                     "lm_not_best": 0.0}
    worst = ref.argmin(-1)
    off = XL.token_gaps(params, seqs, worst, cfg, 32)
    assert off["lm_not_best"] == 1.0
    assert 0.5 < off["lm_gap_mean"] <= off["lm_gap_max"] <= 2.0
    short = XL.token_gaps(params, seqs, best[:, :-1], cfg, 32)
    assert short["lm_gap_mean"] == float("inf")


def test_reference_refuses_another_structure():
    import jax
    cfg = _tiny_xlstm()
    cfg["mlstm_qkv_blockdiagonal"] = True
    with pytest.raises(ValueError, match="structure|computes"):
        XL.weights(jax.random.key(0), cfg)
