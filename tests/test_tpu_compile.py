"""The main-path Pallas kernels compile for the TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: slices not aligned to the (8, 128) tiling, more VMEM
than a kernel may use, gathers the TPU cannot lower.  These tests
compile each kernel with ``interpret=False`` for a described ``v5e:2x2``
topology at the widths ``chip_smoke.py`` runs.  Nothing runs, so they
need no chip.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under pytest-xdist only
the worker given this file should.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bilateral.bilateral import bilateral_pallas
from repro.kernels.conv2d.conv2d import conv2d_pallas
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.gmm.gmm import gmm_pallas
from repro.kernels.hist.hist import hist_pallas
from repro.kernels.sort_bitonic.sort_bitonic import sort_rows_pallas
from repro.kernels.spmv.spmv import spmv_ell_pallas


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2; the persistent compile cache is off
    meanwhile (a described chip's executables cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                    # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield topo
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the described v5e:2x2."""
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


CASES = {
    "conv2d": (lambda a, w: conv2d_pallas(a, w, row_tile=64,
                                          interpret=False),
               [(768, 768), (15, 15)]),
    "conv2d_2d_tiles": (lambda a, w: conv2d_pallas(
        a, w, row_tile=128, col_tile=128, interpret=False),
        [(768, 768), (15, 15)]),
    # a §5.4.3 row share of the smoke's conv: 384 rows + halo, under
    # a tiling tuned for the whole image
    "conv2d_row_share": (lambda a, w: conv2d_pallas(
        a, w, row_tile=512, col_tile=256, interpret=False),
        [(391, 768), (15, 15)]),
    "hist": (lambda x: hist_pallas(x, 256, tile=2048, interpret=False),
             [((1 << 21,), jnp.int32)]),
    "spmv": (lambda v, i, x: spmv_ell_pallas(v, i, x, row_tile=256,
                                             interpret=False),
             [(4096, 32), ((4096, 32), jnp.int32), (4096,)]),
    "sort_bitonic": (lambda x: sort_rows_pallas(x, row_tile=256,
                                                interpret=False),
                     [(256, 256)]),
    "bilateral": (lambda a, s, r: bilateral_pallas(a, s, r, row_tile=64,
                                                   interpret=False),
                  [(256, 256), (15, 15), (256,)]),
    "flash_attention": (lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, block_q=256, block_k=256, interpret=False),
        [(16, 512, 64)] * 3),
    "gmm": (lambda x, w: gmm_pallas(x, w, interpret=False),
            [(8, 256, 512), (8, 512, 512)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [_spec(one_chip, *s) if isinstance(s[0], tuple)
            else _spec(one_chip, s) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the Pallas kernel itself reached the chip's compiler
    assert "tpu_custom_call" in compiled.as_text()


def test_conv_tpu_candidates_compile(one_chip, monkeypatch):
    """Every conv config the autotuner may try on the TPU compiles at
    the smoke's width: XLA's own convolution is left out there, and so
    are tilings whose halo window overflows VMEM."""
    from repro.kernels.conv2d import ops
    from repro.kernels.conv2d.conv2d import conv2d_shift_add

    monkeypatch.setattr(ops, "platform", lambda: "tpu")
    cands = ops.candidates(768, 768, 15)
    assert {"impl": "xla_conv"} not in cands
    assert {"impl": "pallas", "row_tile": 512, "col_tile": 512} not in cands
    args = [_spec(one_chip, (768, 768)), _spec(one_chip, (15, 15))]
    for cfg in cands:
        if cfg["impl"] == "xla_shift":
            fn = conv2d_shift_add
        else:
            fn = functools.partial(conv2d_pallas, row_tile=cfg["row_tile"],
                                   col_tile=cfg["col_tile"],
                                   interpret=False)
        jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("rules", [{}, {"expert": "data"}],
                         ids=["ep", "smap"])
def test_serving_experts_stay_sharded_on_v5e_mesh(topo, rules):
    """A serving prefill through DeepSeek-V2-Lite's expert layer at its
    published widths on a 2 x 2 mesh keeps the expert weights sharded as
    they are given: no all-gather of a weight (the only arrays with the
    FFN width, 1408, or its half), with the experts on the model axis
    ("ep") or on the data axis and the FFN width on the model axis
    ("smap" rules)."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from repro.configs import registry
    from repro.models import moe
    from repro.models.param import axes, values
    from repro.parallel import sharding as ps

    cfg = registry.get("deepseek-v2-lite-16b")
    tree = jax.eval_shape(lambda: moe.init_moe(jax.random.key(0), cfg))
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    with ps.use_mesh(mesh, overrides=rules):
        shard = ps.param_shardings(axes(tree), values(tree), mesh)
        p = jax.tree.map(lambda v, s: jax.ShapeDtypeStruct(
            v.shape, jnp.bfloat16, sharding=s), values(tree), shard)
        x = jax.ShapeDtypeStruct(
            (4, 512, cfg.d_model), jnp.bfloat16, sharding=NamedSharding(
                mesh, ps.spec_for(("batch", None, None))))
        hlo = jax.jit(lambda p, x: moe.moe_serve(p, x, cfg)).lower(
            p, x).compile().as_text()
    gathered = re.findall(r"= \w+\[([\d,]+)\][^ ]* all-gather\(", hlo)
    assert not [g for g in gathered
                if {"1408", "704"} & set(g.split(","))], gathered


@pytest.mark.parametrize("arch,dtype,precision,cache_len", [
    ("xlstm-350m", jnp.float32, "highest", 1024 + 32 + 1),
    ("deepseek-v2-lite-16b", jnp.bfloat16, None, 1536 + 12 + 1),
])
def test_slot_step_compiles_for_v5e(one_chip, arch, dtype, precision,
                                    cache_len):
    """The continuous engine's decode step, ``make_slot_step`` over 8
    slots, compiles for one v5e chip at the model's registry widths and
    its benchmark cell's weights, precision and cache length, cut to two
    layers: one mLSTM and one sLSTM block, or the dense layer and one
    expert layer.  It returns the slot state it was given, shape for
    shape."""
    import dataclasses

    from repro.configs import registry
    from repro.models import model_zoo, param
    from repro.serve.serve_step import make_slot_step

    slots = 8
    cfg = registry.get(arch).replace(n_layers=2)
    if cfg.block_pattern == "xlstm":
        cfg = cfg.replace(xlstm=dataclasses.replace(cfg.xlstm,
                                                    slstm_every=2))

    def on_chip(a, dt=None):
        return _spec(one_chip, a.shape, dt or a.dtype)

    params = jax.tree.map(
        lambda a: on_chip(a, dtype if jnp.issubdtype(a.dtype, jnp.floating)
                          else None),
        jax.eval_shape(lambda: param.values(
            model_zoo.init(cfg, jax.random.key(0)))))
    _, caches = jax.eval_shape(
        lambda p, t: model_zoo.prefill(cfg, p, {"tokens": t},
                                       cache_len=cache_len),
        params, jax.ShapeDtypeStruct((slots, 16), jnp.int32))
    caches = jax.tree.map(on_chip, caches)
    tokens = _spec(one_chip, (slots,), jnp.int32)
    step = make_slot_step(cfg)
    with jax.default_matmul_precision(precision):
        step.lower(params, tokens, caches, tokens).compile()
        _, out, _ = jax.eval_shape(step, params, tokens, caches, tokens)
    assert jax.tree.structure(out) == jax.tree.structure(caches)
    for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(caches)):
        assert (got.shape, got.dtype) == (want.shape, want.dtype)
