"""Quickstart: the hybrid engine + a tiny LM in ~60 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ParallelConfig
from repro.core.hybrid_executor import HybridExecutor
from repro.core.task_graph import TaskGraph
from repro.core.work_sharing import plan_work
from repro.models import model_zoo, param
from repro.workloads import conv

# --- 1. the paper's work-sharing rule -------------------------------------
plan = plan_work(total_units=100, throughputs=[4.0, 1.0])
print("work plan:", plan.summary())

# --- 2. a task graph, HEFT-scheduled (paper Fig. 5 style) -----------------
g = (TaskGraph()
     .add("prng", {"cpu": 0.5, "tpu": 2.0}, output_bytes=512e6)
     .add("fis", {"tpu": 0.6}, deps=["prng"])
     .add("rank", {"tpu": 1.0, "cpu": 8.0}, deps=["fis"]))
sched = g.schedule({"cpu0": "cpu", "tpu0": "tpu"})
print("schedule makespan:", round(sched.makespan, 3),
      "critical path:", sched.critical_path)

# --- 3. a hybrid workload end-to-end --------------------------------------
ex = HybridExecutor(simulated_ratio=4.0)
out = conv.run_hybrid(ex, size=256, ksize=9)
print("hybrid conv:", out.result.row())

# --- 4. a tiny LM forward + loss ------------------------------------------
cfg = ArchConfig(name="tiny", family="dense", n_layers=2, d_model=128,
                 n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=512,
                 head_dim=32, parallel=ParallelConfig(remat="none"))
params = param.values(model_zoo.init(cfg, jax.random.key(0)))
tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, 512)
logits, _ = model_zoo.forward(cfg, params, {"tokens": tokens})
print("tiny LM logits:", logits.shape, "finite:",
      bool(jnp.isfinite(logits.astype(jnp.float32)).all()))
