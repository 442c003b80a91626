"""The ``dsv2-lite-batch`` cell at a tiny size on the CPU, chip check
skipped: a sound run reads correct and reports the routing's per-layer
metrics; with the timed path broken as ``cpu_run.py`` breaks it, it
reads above the limit.  And the DeepSeek-V2 family's own counts and its
check of the program, and the routing readers on hand-made spans."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import benchpaths  # noqa: F401
import pytest

from benchlib import files

HERE = os.path.dirname(os.path.abspath(__file__))
DS = files.module("families", "deepseek_v2")
CFG = files.config(files.cell(files.benchmark(), "dsv2-lite-batch")[1])

# the published widths cut small; the router, YaRN and the depth are the
# configuration's own
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_routed_experts": 16, "num_experts_per_tok": 6, "vocab_size": 512,
        "n_slots": 2, "check_requests": 3}

RUN = r"""
import json, sys, time
T = time.monotonic()
import cpu_run
from benchlib import files, harness
bench = files.benchmark()
# lm_mfu needs a chip's published peaks: this run is on the CPU
bench["per_layer"] = [m for m in bench["per_layer"] if m["name"] != "lm_mfu"]
fault = sys.argv[2]
hook = {"token": cpu_run.break_tokens, "state": cpu_run.break_state}.get(fault)
res = harness.run_cell(
    "dsv2-lite-batch", 2**31 + 79, 3.0, not fault, T, require_chip=False,
    config_overrides=json.loads(sys.argv[3]),
    traffic_overrides={"prompt_len": 32, "new_tokens": 8, "clients": 4},
    hook=hook, state_dir=sys.argv[1], bench=bench)
print(json.dumps(res), flush=True)
"""


@pytest.mark.parametrize("fault", ["", "token", "state"])
def test_run_reads_the_fault(fault, tmp_path):
    """Sound (traced): correct, with both routing metrics; ``token``
    alters every served token, ``state`` has each step return the slots'
    state unchanged: both read above the limit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_AUTOTUNE="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    res = subprocess.run(
        [sys.executable, "-c", RUN, str(tmp_path), fault, json.dumps(TINY)],
        capture_output=True, text=True, timeout=280, env=env, cwd=HERE)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is (fault == ""), out["compared"]
    if fault:
        assert any(c["value"] > c["limit"] for c in out["compared"])
        return
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # a 32-token prompt routes 192 assignments over 16 experts
    assert 1.0 <= m["expert_load_max.moe"] <= 16 / 6
    # 1-2 live slots, each choosing 6 distinct experts
    assert 6.0 <= m["experts_hit.moe"] <= 12.0
    assert m["engine_init_s.lm"] < 5.0


def span(name, **args):
    return {"name": name, "track": "lane:host", "t0": 10.0, "t1": 11.0,
            "args": args}


def test_routing_readers_on_hand_made_spans():
    spans = [span("prefill_call", rows=1, expert_load_max=1.5),
             span("prefill_call", rows=1, expert_load_max=1.25),
             span("prefill_call", rows=1, expert_load_max=2.0),
             span("decode", n_live=8, experts_hit=30.0),
             span("decode", n_live=8, experts_hit=36.0),
             span("engine_insert", slot=0)]
    ctx = SimpleNamespace(spans=spans)
    load = files.module("metrics", "expert_load_max.moe").read(ctx)
    hit = files.module("metrics", "experts_hit.moe").read(ctx)
    assert load == pytest.approx(1.5) and hit == pytest.approx(33.0)
    # the parent program, or a model without experts: no such args
    bare = SimpleNamespace(spans=[span("prefill_call", rows=1),
                                  span("decode", n_live=8)])
    for name in ("expert_load_max.moe", "experts_hit.moe"):
        assert files.module("metrics", name).read(bare) is None


def test_params_and_flops_by_hand():
    """2,839.8M parameters at 5 layers; a token multiplies the dense
    layer, five attentions, four routers, 4 x 6 routed and 4 x 2 shared
    experts and the head."""
    d, V = 2048, 102400
    mla = (d * 16 * 192 + d * (512 + 64) + 512 * 16 * 256 + 16 * 128 * d)
    expert = 3 * d * 1408
    dense = 3 * d * 10944
    n = DS.params(CFG)
    total = (2 * V * d + 5 * (mla + 2 * d + 512) + dense
             + 4 * (d * 64 + 64 * expert + 2 * expert) + d)
    assert n["total"] == total
    assert n["total"] == pytest.approx(2839.8e6, abs=0.05e6)
    matmul = 5 * mla + dense + 4 * (d * 64 + 6 * expert + 2 * expert) + d * V
    assert n["matmul"] == matmul
    assert DS.token_flops(CFG) == 2 * matmul
    # the experts are 44% of a token's operations, the head 34%
    assert 4 * 8 * expert / matmul == pytest.approx(0.445, abs=0.005)
    assert d * V / matmul == pytest.approx(0.337, abs=0.005)


def test_params_match_the_program():
    from repro.configs import registry
    from repro.models import model_zoo
    arch = DS.resized(registry.get("deepseek-v2-lite-16b"), CFG)
    assert DS.params(CFG)["total"] == model_zoo.count_params(arch)


def test_program_check():
    """The program's registry config is the published one at its 27
    layers; the benchmark's cut changes the depth alone."""
    from repro.configs import registry
    arch = registry.get("deepseek-v2-lite-16b")
    DS.check_program(dict(CFG, n_layers=CFG["num_hidden_layers"]), arch)
    cut = DS.resized(arch, CFG)
    DS.check_program(CFG, cut)
    assert cut == arch.replace(n_layers=5)
    for bad in ({"n_layers": 6}, {"rms_norm_eps": 1e-5},
                {"norm_topk_prob": True}, {"routed_scaling_factor": 2}):
        with pytest.raises(ValueError):
            DS.check_program(dict(CFG, **bad), cut)
    tiny = dict(CFG, **TINY)
    DS.check_program(tiny, DS.resized(arch, tiny))
