"""shard_map MoE (§Perf optimized path) must match the dense reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MoEConfig, ParallelConfig
from repro.models import moe as moe_mod
from repro.models.param import values
from repro.parallel import sharding as ps

BASE = MoEConfig(n_routed=8, n_shared=1, top_k=2, d_ff=32,
                 capacity_factor=8.0, overflow_passes=0)
CFG = ArchConfig(name="m", family="moe", n_layers=1, d_model=16, n_heads=2,
                 n_kv_heads=2, d_ff=32, vocab_size=16, moe=BASE,
                 parallel=ParallelConfig(remat="none"))


@pytest.mark.parametrize("dispatch", ["sort", "onehot"])
def test_smap_matches_dense(dispatch):
    cfg_s = CFG.replace(moe=dataclasses.replace(
        BASE, shard_mode="smap", dispatch=dispatch))
    p = values(moe_mod.init_moe(jax.random.key(0), CFG))
    x = jax.random.normal(jax.random.key(1), (2, 12, 16))
    y0, a0 = moe_mod.moe_ffn(p, x, CFG)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with ps.use_mesh(mesh):
        y1, a1 = jax.jit(lambda p, x: moe_mod.moe_ffn(p, x, cfg_s))(p, x)
    np.testing.assert_allclose(np.asarray(y0, np.float32),
                               np.asarray(y1, np.float32),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(float(a0), float(a1), rtol=1e-5)


def test_smap_grads_finite_and_match():
    cfg_s = CFG.replace(moe=dataclasses.replace(
        BASE, shard_mode="smap", dispatch="onehot"))
    p = values(moe_mod.init_moe(jax.random.key(0), CFG))
    x = jax.random.normal(jax.random.key(1), (2, 8, 16))

    def loss_dense(p):
        return jnp.sum(moe_mod.moe_ffn(p, x, CFG)[0] ** 2)

    def loss_smap(p):
        return jnp.sum(moe_mod.moe_ffn(p, x, cfg_s)[0] ** 2)

    g0 = jax.grad(loss_dense)(p)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with ps.use_mesh(mesh):
        g1 = jax.jit(jax.grad(loss_smap))(p)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-4, rtol=1e-3)


def test_optimized_presets_build():
    from repro.configs import registry
    from repro.models import model_zoo
    from repro.launch.shardings import is_axes
    for aid in ("deepseek-v2-lite-16b", "command-r-35b", "xlstm-350m"):
        cfg = registry.get_optimized(aid)
        # shapes still resolve (eval_shape, no allocation) and every
        # param leaf carries a rank-matching axes tuple
        vals, axes = model_zoo.param_specs(cfg)
        flat_v = jax.tree.leaves(vals)
        flat_a = jax.tree.leaves(axes, is_leaf=is_axes)
        assert len(flat_v) == len(flat_a)
        for v, a in zip(flat_v, flat_a):
            assert len(a) == len(v.shape)


_MESH_SCRIPT = r'''
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding
assert len(jax.devices()) == 4     # the backend starts before dryrun's flag
from repro.configs import registry
from repro.configs.base import MLAConfig, ShapeCell
from repro.launch import dryrun
from repro.models import moe
from repro.models.param import axes, values
from repro.parallel import sharding as ps

big = registry.get("deepseek-v2-lite-16b")
cfg = big.replace(
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
    vocab_size=256, head_dim=24,
    mla=MLAConfig(kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16),
    moe=dataclasses.replace(big.moe, n_routed=8, top_k=2, d_ff=32))
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
out = {}

tree = moe.init_moe(jax.random.key(0), cfg)
p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), values(tree))
x = jax.random.normal(jax.random.key(1), (4, 16, 64), jnp.bfloat16)
serve = lambda p, x: moe.moe_serve(p, x, cfg)
y0, c0 = jax.jit(serve)(p, x)
for name, over in (("ep", {}), ("smap", {"expert": "data"})):
    with ps.use_mesh(mesh, overrides=over):
        shard = ps.param_shardings(axes(tree), p, mesh)
        xs = NamedSharding(mesh, ps.spec_for(("batch", None, None)))
        y1, c1 = jax.jit(serve, in_shardings=(shard, xs))(p, x)
    out[name] = {
        "err": float(jnp.abs(y1.astype(jnp.float32)
                             - y0.astype(jnp.float32)).max()
                     / jnp.abs(y0.astype(jnp.float32)).max()),
        "counts": bool((c1 == c0).all())}

for kind in ("prefill", "decode"):
    jfn, args, rules = dryrun.build_cell(cfg, ShapeCell("t", 16, 4, kind),
                                         mesh)
    with ps.use_mesh(mesh, rules=rules):
        jfn.lower(*args).compile()
    out[kind] = "compiled"
print("RESULT" + json.dumps(out))
'''


def test_serving_experts_on_a_mesh():
    """On a 2 x 2 CPU mesh (a subprocess forces the devices), the
    serving layer with its weights sharded, the experts on the model
    axis ("ep") or on the data axis ("smap" rules), matches the
    one-device result to bfloat16's rounding of a psum of shards and
    routes the same counts; and the dry-run's prefill and decode cells
    of an expert model lower and compile, the decode step's caches
    under its cache shardings.  (That the weights stay sharded shows in
    the TPU compiler's program: ``tests/test_tpu_compile.py``.)"""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    res = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], cwd=root,
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("RESULT")][-1]
    out = json.loads(line[len("RESULT"):])
    for name in ("ep", "smap"):
        # bfloat16 keeps 8 significant bits: a sum split over two shards
        # rounds differently by a few units of 2^-8 of the largest output
        assert out[name]["err"] < 2e-2, out
        assert out[name]["counts"], out
    assert out["prefill"] == out["decode"] == "compiled", out
