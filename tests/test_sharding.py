"""Sharding-rule unit tests + cache-axes/structure congruence (the class
of bug that breaks multi-pod dry-runs)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec

from repro.configs import registry
from repro.launch import shardings as sh
from repro.models import model_zoo
from repro.parallel import sharding as ps


def test_spec_for_divisibility_drop():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    rules = ps.default_rules(("data", "model"))
    # everything divides by 1 -> mapping kept
    spec = ps.spec_for(("batch", None, "heads", None),
                       shape=(8, 4, 8, 16), mesh=mesh, rules=rules)
    assert spec == PartitionSpec(("data",), None, "model")


def test_spec_for_duplicate_axis_dropped():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    rules = ps.default_rules(("data", "model"))
    spec = ps.spec_for(("mlp", "vocab"), shape=(4, 4), mesh=mesh,
                       rules=rules)
    # both map to "model"; second occurrence must drop
    assert spec == PartitionSpec("model")


def test_shard_act_noop_without_mesh():
    x = jnp.ones((4, 4))
    assert ps.shard_act(x, ("batch", None)) is x


@pytest.mark.parametrize("arch_id", registry.ARCH_IDS)
def test_cache_axes_structure_matches_caches(arch_id):
    """cache_axes(cfg) must be tree-congruent with the real cache pytree
    for every arch (decode in_shardings depend on it)."""
    cfg = registry.get(arch_id)
    if not cfg.supports_decode:
        pytest.skip("no decode")
    from repro.configs.base import ShapeCell
    cell = ShapeCell("t", 64, 2, "decode")
    specs = model_zoo.input_specs(cfg, cell, tp=1)
    axes = sh.cache_axes(cfg)
    t1 = jax.tree_util.tree_structure(
        jax.tree.map(lambda x: 0, specs["caches"]))
    t2 = jax.tree_util.tree_structure(
        jax.tree.map(lambda x: 0, axes, is_leaf=sh.is_axes))
    assert t1 == t2, f"{arch_id}: cache axes tree != cache tree"
    # and every axes tuple has the right rank
    flat_ax = jax.tree.leaves(axes, is_leaf=sh.is_axes)
    flat_sd = jax.tree.leaves(specs["caches"])
    for ax, sd in zip(flat_ax, flat_sd):
        assert len(ax) == len(sd.shape), (arch_id, ax, sd.shape)


@pytest.mark.parametrize("arch_id", ["minitron-8b", "deepseek-v2-lite-16b"])
def test_param_shardings_build(arch_id):
    cfg = registry.get(arch_id)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    vals, axes = model_zoo.param_specs(cfg)
    with ps.use_mesh(mesh, fsdp=cfg.parallel.fsdp):
        shard = sh.tree_shardings(axes, vals, mesh)
    assert jax.tree_util.tree_structure(shard) == \
        jax.tree_util.tree_structure(vals)


def test_opt_state_axes_adafactor_ranks():
    cfg = registry.get("minitron-8b")
    vals, axes = model_zoo.param_specs(cfg)
    oax = sh.opt_state_axes(axes, vals, "adafactor")
    flat_v = jax.tree.leaves(vals)
    flat_vr = jax.tree.leaves(oax["vr"], is_leaf=sh.is_axes)
    for sd, ax in zip(flat_v, flat_vr):
        want = len(sd.shape) - 1 if (len(sd.shape) >= 2 and
                                     sd.shape[-1] > 1 and
                                     sd.shape[-2] > 1) else len(sd.shape)
        assert len(ax) == want


@pytest.mark.parametrize("arch_id", [a for a in registry.ARCH_IDS
                                     if registry.get(a).moe is not None])
def test_decode_step_caches_match_cache_axes(arch_id):
    """The dry-run's decode step returns caches of the tree that
    ``cache_axes`` gives its out_shardings, for the expert models too
    (their routing counts are not part of the caches)."""
    from repro.configs.base import ShapeCell
    from repro.serve.serve_step import make_serve_step
    cfg = registry.get(arch_id)
    specs = model_zoo.input_specs(cfg, ShapeCell("t", 64, 2, "decode"),
                                  tp=1)
    pvals, _ = model_zoo.param_specs(cfg)
    _, caches = jax.eval_shape(make_serve_step(cfg), pvals, specs["token"],
                               specs["caches"], specs["position"])
    want = jax.tree_util.tree_structure(
        jax.tree.map(lambda x: 0, sh.cache_axes(cfg), is_leaf=sh.is_axes))
    assert jax.tree_util.tree_structure(
        jax.tree.map(lambda x: 0, caches)) == want
