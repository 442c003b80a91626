"""Cost-model subsystem: HardwareProfile measurement + persistence,
prediction properties, predicted-vs-measured rank agreement on real
autotune candidate lists, top-K / family-coverage search, cross-shape
transfer seeding (parity vs full search), persistent-calibration JSON
round-trip (corrupt-file tolerance, concurrent merge), the
measure(warmup=0) cold-timing path, and zero-probe fresh-process
planning."""
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cost_model
from repro.core.calibration import CalibrationCache, measure
from repro.core.cost_model import CostTerms, HardwareProfile
from repro.kernels import autotune as at

KEY = jax.random.key(0)


@pytest.fixture
def stores(tmp_path, monkeypatch):
    """Fresh calibration store + tune cache + search enabled, isolated
    from the suite-wide conftest settings."""
    monkeypatch.setenv("REPRO_CALIB_CACHE",
                       str(tmp_path / "calibration.json"))
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "autotune.json"))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.setenv("REPRO_COST_MODEL", "1")
    cost_model.reset_profiles()
    at.reset_tune_cache()
    yield tmp_path
    cost_model.reset_profiles()
    at.reset_tune_cache()


# ---------------------------------------------------------------- profile
def test_profile_measured_and_persisted(stores):
    p = cost_model.get_profile()
    assert p.measured and p.backend == jax.default_backend()
    assert p.matmul_flops > 0 and p.mem_bw > 0 and p.dispatch_s > 0
    data = json.loads((stores / "calibration.json").read_text())
    entry = data["hardware"][p.backend]
    assert entry["v"] == cost_model.PROFILE_VERSION
    # a "fresh process" (cleared memo) loads from disk, never re-measures
    cost_model.reset_profiles()

    def boom(backend):
        raise AssertionError("profile re-measured despite disk entry")

    orig = cost_model._measure_profile
    cost_model._measure_profile = boom
    try:
        p2 = cost_model.get_profile()
    finally:
        cost_model._measure_profile = orig
    assert p2.matmul_flops == pytest.approx(p.matmul_flops)


def test_profile_static_fallback_when_disabled(stores, monkeypatch):
    """With the model off, only a v5e gets the static profile; any
    other device raises instead of being priced as a v5e."""
    monkeypatch.setenv("REPRO_COST_MODEL", "0")
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    p = cost_model.get_profile(v5e)
    assert not p.measured
    assert p.matmul_flops == 197e12          # the v5e's published peak
    with pytest.raises(ValueError, match="no static hardware profile"):
        cost_model.get_profile()             # this process's CPU
    v4 = SimpleNamespace(platform="tpu", device_kind="TPU v4")
    with pytest.raises(ValueError, match="TPU v4"):
        cost_model.get_profile(v4)


def test_predict_properties():
    p = HardwareProfile(backend="x", matmul_flops=1e12, ew_flops=1e10,
                        mem_bw=1e11, dispatch_s=1e-6, host_bw=1e9,
                        interpret_step_s=1e-3)
    base = CostTerms(flops=1e9, bytes=1e8)
    assert p.predict(CostTerms(flops=2e9, bytes=1e8)) > p.predict(base)
    # bytes must push past the flops term to move the roofline max
    assert p.predict(CostTerms(flops=1e9, bytes=2e10)) > p.predict(base)
    assert p.predict(CostTerms(flops=1e9, bytes=1e8, steps=1000)) \
        > p.predict(base)
    # same flops rate differently: matmul peak >> elementwise rate
    assert p.predict(CostTerms(flops=1e9, compute="matmul")) \
        < p.predict(CostTerms(flops=1e9))
    assert p.predict(CostTerms(host_bytes=1e8)) > p.predict(CostTerms())
    assert p.predict(CostTerms(interpret_steps=10)) \
        == pytest.approx(p.predict(CostTerms()) + 10 * 1e-3)


def test_static_time_estimate_shim_matches_v5e():
    from repro.core.calibration import static_time_estimate
    with pytest.warns(DeprecationWarning):
        t = static_time_estimate(197e12, 0.0)
    assert t == pytest.approx(1.0)
    with pytest.warns(DeprecationWarning):
        t = static_time_estimate(0.0, 819e9, chips=1)
    assert t == pytest.approx(1.0)


# ------------------------------------------------- predicted-vs-measured
def test_conv_cost_terms_rank_padding_waste():
    """A tile that pads 64 rows to 100 must predict slower than the
    exact-fit tile (same impl, same backend terms)."""
    from repro.kernels.conv2d.ops import cost_terms
    p = HardwareProfile(backend="x", matmul_flops=1e12, ew_flops=1e10,
                        mem_bw=1e11, dispatch_s=1e-6, host_bw=1e9)
    fit = {"impl": "pallas", "row_tile": 64, "col_tile": 0}
    waste = {"impl": "pallas", "row_tile": 100, "col_tile": 0}
    assert p.predict(cost_terms(waste, 64, 64, 5)) \
        > p.predict(cost_terms(fit, 64, 64, 5))


def test_predicted_rank_agrees_with_measured_on_hist(stores):
    """Rank correlation between model predictions and real measurements
    over the hist candidate list.  The list spans ~100x (bincount vs
    one-hot interpret pallas), so a weak threshold is robust to box
    noise while still catching an inverted or flat model."""
    from repro.kernels.hist import ops
    n, bins = 1 << 16, 256
    x = jax.random.randint(KEY, (n,), 0, bins)
    prof = cost_model.get_profile()
    preds, meas = [], []
    for cand in ops.candidates(n, bins):
        cfg = {**ops.DEFAULT_CONFIG, **cand}
        preds.append(prof.predict(ops.cost_terms(cfg, n, bins)))
        meas.append(measure(
            lambda: ops.histogram(x, bins, config=cfg).block_until_ready(),
            warmup=1, iters=2, reduce="min"))
    rp = np.argsort(np.argsort(preds))
    rm = np.argsort(np.argsort(meas))
    spearman = np.corrcoef(rp, rm)[0, 1]
    assert spearman > 0.3, list(zip(preds, meas))
    # and the extremes must never invert: the cheapest predicted
    # candidate measures faster than the costliest predicted one
    assert meas[int(np.argmin(preds))] < meas[int(np.argmax(preds))]


# ------------------------------------------------------- top-K search
CANDS = [{"impl": "a", "tile": 1}, {"impl": "a", "tile": 2},
         {"impl": "a", "tile": 3}, {"impl": "b", "tile": 1},
         {"impl": "b", "tile": 2}, {"impl": "c", "tile": 1}]
DEFAULT = {"impl": "a", "tile": 0}


def _cost_fn(cfg):
    # family "a" predicted cheapest, larger tile = cheaper within family
    fam = {"a": 1.0, "b": 2.0, "c": 4.0}[cfg.get("impl", "a")]
    return CostTerms(flops=1e9 * fam / max(cfg.get("tile", 1), 1))


def test_topk_measures_family_bests_only(stores, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_TOPK", "3")
    timed = []

    def timer(fn):
        timed.append(1)
        return float(len(timed))          # first measured wins

    def maker(cfg):
        return lambda: None

    cfg = at.autotune("k", "s1", CANDS, maker, DEFAULT, timer=timer,
                      cost_fn=_cost_fn)
    # one candidate per family (a:tile3, b:tile2, c:tile1) — the
    # model's per-family bests — and nothing else at K=3
    assert len(timed) == 3
    assert cfg == {**DEFAULT, "impl": "a", "tile": 3}


def test_topk_zero_means_full_search(stores, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_TOPK", "0")
    timed = []
    cfg = at.autotune("k", "s2", CANDS, lambda c: (lambda: None), DEFAULT,
                      timer=lambda fn: (timed.append(1),
                                        float(len(timed)))[1],
                      cost_fn=_cost_fn)
    assert len(timed) == len(CANDS)
    assert cfg == {**DEFAULT, **CANDS[0]}


def test_model_disabled_means_full_search(stores, monkeypatch):
    monkeypatch.setenv("REPRO_COST_MODEL", "0")
    timed = []
    at.autotune("k", "s3", CANDS, lambda c: (lambda: None), DEFAULT,
                timer=lambda fn: (timed.append(1), float(len(timed)))[1],
                cost_fn=_cost_fn)
    assert len(timed) == len(CANDS)


# -------------------------------------------------- cross-shape transfer
def test_transfer_seeds_from_nearest_bucket(stores):
    winner_tile = 2

    def timer_full(fn):
        # candidate order: deterministic stub making a:tile2 the winner
        timer_full.i += 1
        return 0.1 if timer_full.i == 2 else 1.0 + timer_full.i
    timer_full.i = 0

    cfg_a = at.autotune("k", "N128_B16", CANDS, lambda c: (lambda: None),
                        DEFAULT, timer=timer_full, cost_fn=None)
    assert cfg_a["tile"] == winner_tile
    # sibling bucket: exactly ONE measurement, sibling's winner adopted
    timed = []
    cfg_b = at.autotune("k", "N256_B16", CANDS, lambda c: (lambda: None),
                        DEFAULT,
                        timer=lambda fn: (timed.append(1), 0.5)[1],
                        cost_fn=_cost_fn)
    assert len(timed) == 1
    assert cfg_b == cfg_a
    entry = at.get_tune_cache().get(jax.default_backend(), "k", "N256_B16")
    assert entry["via"] == "transfer:N128_B16"
    # parity vs the full search under the same deterministic stub: the
    # same candidate wins either way
    timer_full.i = 0
    os.environ["REPRO_TUNE_TRANSFER"] = "0"
    try:
        cfg_b_full = at.autotune("k", "N512_B16", CANDS,
                                 lambda c: (lambda: None), DEFAULT,
                                 timer=timer_full, cost_fn=None)
    finally:
        os.environ.pop("REPRO_TUNE_TRANSFER")
    assert cfg_b_full == cfg_b


def test_transfer_fit_guard_rejects_bad_shapes(stores):
    """A sibling winner whose tiling implies huge waste at the new
    shape (per the model) must trigger a real search instead."""
    at.get_tune_cache().put(jax.default_backend(), "k2", "N128_B16",
                            {"impl": "a", "tile": 64}, 10.0)

    def cost_fn(cfg):
        # tile=64 is predicted 10x worse than the best candidate here
        return CostTerms(flops=1e12 if cfg.get("tile") == 64 else 1e9)

    timed = []
    at.autotune("k2", "N256_B16", CANDS, lambda c: (lambda: None), DEFAULT,
                timer=lambda fn: (timed.append(1), float(len(timed)))[1],
                cost_fn=cost_fn)
    assert len(timed) > 1                     # searched, did not transfer


def test_transfer_ignores_incompatible_bucket_names(stores):
    at.get_tune_cache().put(jax.default_backend(), "k3", "H128_W128_K5",
                            {"impl": "b", "tile": 1}, 10.0)
    near = at.nearest_bucket(
        at.get_tune_cache().buckets(jax.default_backend(), "k3"),
        "N256_B16")
    assert near is None                       # different dimension names


def test_transfer_never_crosses_boolean_flag_dims(stores):
    """attention's causal bit is encoded as c0/c1: a causal winner must
    not seed the non-causal bucket (different candidate spaces)."""
    buckets = {"BH8_T1024_S1024_D64_c1": {"config": {"impl": "x"},
                                          "us": 1.0}}
    assert at.nearest_bucket(buckets, "BH8_T1024_S1024_D64_c0") is None
    # same flag, different size: a normal transfer candidate
    near = at.nearest_bucket(buckets, "BH8_T512_S512_D64_c1")
    assert near is not None and near[0] == "BH8_T1024_S1024_D64_c1"


def test_json_store_leaf_entries_win_wholesale(stores):
    """A rewritten leaf entry must not inherit stale sub-keys (e.g. a
    'via' transfer tag) from the on-disk version during merge-on-write."""
    from repro.core.persist import JsonStore

    path = str(stores / "merge.json")
    s1 = JsonStore(path)
    with s1.lock:
        s1.data()["cpu"] = {"k": {"b1": {"config": {"impl": "p"},
                                         "us": 1.0, "via": "transfer:x"}}}
        s1.flush()
    s2 = JsonStore(path)                      # fresh process re-tunes b1
    with s2.lock:
        s2.data()["cpu"]["k"]["b1"] = {"config": {"impl": "q"}, "us": 2.0}
        s2.data()["cpu"]["k"]["b2"] = {"config": {"impl": "r"}, "us": 3.0}
        s2.flush()
    got = json.loads((stores / "merge.json").read_text())
    assert got["cpu"]["k"]["b1"] == {"config": {"impl": "q"}, "us": 2.0}
    assert "via" not in got["cpu"]["k"]["b1"]
    assert got["cpu"]["k"]["b2"]["us"] == 3.0  # grouping levels merge


# --------------------------------------- persistent calibration cache
def test_calibration_cache_roundtrip(stores):
    path = str(stores / "calib2.json")
    c1 = CalibrationCache(path=path)
    c1.put("wl", "accel", 0.01)
    c1.put("wl", "host", 0.04, slowdown=4.0)
    # fresh instance (fresh process): reads the persisted unit times
    c2 = CalibrationCache(path=path)
    assert c2.get("wl", "accel") == pytest.approx(0.01)
    assert c2.get("wl", "host", 4.0) == pytest.approx(0.04)
    assert c2.get("wl", "host") is None       # slowdown is part of the key
    # loaded entries calibrate the plan but do NOT claim jit warmth
    assert not c2.warmed_in_process("wl", "accel")
    assert c1.warmed_in_process("wl", "accel")
    c2.put("wl", "accel", 0.01)
    assert c2.warmed_in_process("wl", "accel")


def test_calibration_cache_corrupt_file(stores):
    path = stores / "calib3.json"
    path.write_text("{not json")
    c = CalibrationCache(path=str(path))
    assert c.get("wl", "accel") is None
    c.put("wl", "accel", 0.02)
    assert json.loads(path.read_text())       # repaired by the write
    assert CalibrationCache(path=str(path)).get("wl", "accel") \
        == pytest.approx(0.02)


def test_calibration_cache_concurrent_merge(stores):
    path = str(stores / "calib4.json")
    c1 = CalibrationCache(path=path)
    c2 = CalibrationCache(path=path)
    c1.put("wl_a", "accel", 0.01)
    c2.put("wl_b", "host", 0.03)              # must not clobber wl_a
    c3 = CalibrationCache(path=path)
    assert c3.get("wl_a", "accel") == pytest.approx(0.01)
    assert c3.get("wl_b", "host") == pytest.approx(0.03)


def test_calibration_clear_wipes_disk(stores):
    path = str(stores / "calib5.json")
    c1 = CalibrationCache(path=path)
    c1.put("wl", "accel", 0.01)
    c1.clear()
    assert CalibrationCache(path=path).get("wl", "accel") is None


def test_calibration_clear_preserves_sibling_sections(stores):
    """clear() wipes unit_times only — the hardware-profile section,
    possibly written by cost_model's SIBLING JsonStore after this
    cache last read the file, must survive on disk."""
    from repro.core.persist import JsonStore

    path = str(stores / "calib6.json")
    cache = CalibrationCache(path=path)
    cache.put("wl", "accel", 0.01)            # loads + writes the file
    sibling = JsonStore(path)                 # cost_model's view
    with sibling.lock:
        sibling.data().setdefault("hardware", {})["cpu"] = {
            "matmul_flops": 1e12, "v": 1}
        sibling.flush()
    cache.clear()                             # stale _mem lacks "hardware"
    data = json.loads((stores / "calib6.json").read_text())
    assert data["hardware"]["cpu"]["matmul_flops"] == 1e12
    assert "unit_times" not in data


# ------------------------------------------------ measure(warmup=0)
def test_measure_pure_cold_timing():
    calls = []

    def fn():
        calls.append(1)
        return jnp.zeros(())

    t = measure(fn, warmup=0, iters=1)
    assert len(calls) == 1 and t >= 0.0
    calls.clear()
    measure(fn, warmup=0, iters=0)            # iters clamps to >= 1
    assert len(calls) == 1


# ------------------------------- fresh-process zero-probe planning
def test_fresh_process_plans_without_probes(stores, monkeypatch):
    from repro.core import hybrid_executor as hx

    path = str(stores / "calib_exec.json")
    probes = {"n": 0}
    orig_measure = hx.measure

    def counting_measure(fn, **kw):
        probes["n"] += 1
        return orig_measure(fn, **kw)

    monkeypatch.setattr(hx, "measure", counting_measure)

    def run_share(g, s, k):
        # deterministic, meaningful duration: a trivial payload would
        # make the post-run EWMA (which persists) scheduling noise, and
        # the fresh-process plan would wobble by more than a chunk
        import time as _t
        _t.sleep(k * 2e-4)
        return list(range(s, s + k))

    def combine(outs):
        return [x for o in outs for x in o]

    def run_process(cache):
        monkeypatch.setattr(hx, "get_calibration_cache", lambda: cache)
        ex = hx.HybridExecutor(simulated_ratio=4.0, n_chunks=8)
        ex.calibrate(lambda g, k: run_share(g, 0, k), probe_units=8,
                     workload="t")
        out = ex.run_work_shared("t", 64, run_share, combine)
        plan = {}
        for c in out.trace.chunks:
            plan[c.owner] = plan.get(c.owner, 0) + c.units
        return out, plan

    out1, plan1 = run_process(CalibrationCache(path=path))
    assert probes["n"] > 0                    # cold: probed
    probes["n"] = 0
    # "fresh process": new cache instance, same file
    out2, plan2 = run_process(CalibrationCache(path=path))
    assert probes["n"] == 0, "persisted calibration must skip probes"
    assert out2.value == list(range(64))
    chunk_units = 64 // 8
    for g in set(plan1) | set(plan2):
        assert abs(plan1.get(g, 0) - plan2.get(g, 0)) <= chunk_units


def test_model_priors_plan_without_probes(stores, monkeypatch):
    """unit_cost + enabled model: even a never-measured workload plans
    with zero probe runs (the model's seconds/unit seeds the split)."""
    from repro.core import hybrid_executor as hx

    probes = {"n": 0}
    orig_measure = hx.measure
    monkeypatch.setattr(
        hx, "measure",
        lambda fn, **kw: (probes.__setitem__("n", probes["n"] + 1),
                          orig_measure(fn, **kw))[1])
    cache = CalibrationCache(path=None)
    monkeypatch.setattr(hx, "get_calibration_cache", lambda: cache)
    ex = hx.HybridExecutor(simulated_ratio=4.0, n_chunks=8)
    ex.calibrate(lambda g, k: None, probe_units=8, workload="m",
                 unit_cost=CostTerms(flops=1e6, bytes=1e5))
    assert probes["n"] == 0
    thr = ex.tracker.throughputs([g.name for g in ex.groups])
    assert all(t > 0 for t in thr)
    # simulated pair: the model seeds the slowdown-scaled ratio
    assert thr[0] / thr[1] == pytest.approx(4.0, rel=1e-3)


# --------------------------------------- model-layer tuned wiring
def test_sdpa_matches_reference_and_uses_pinned_config(stores,
                                                       monkeypatch):
    from repro.kernels.flash_attention import ops as flash_ops

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 64, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 64, 2, 32), jnp.float32)
    ref = flash_ops.flash_attention(q, k, v, causal=True,
                                    use_kernel=False)
    out = flash_ops.sdpa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # a pallas pin maps onto the differentiable blocked path: grads
    # must flow (pallas defines no VJP) and values stay correct
    monkeypatch.setenv("REPRO_TUNE_PIN_FLASH_ATTENTION",
                       '{"impl": "pallas", "block_q": 32, "block_k": 32}')
    out2 = flash_ops.sdpa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda q_: flash_ops.sdpa(q_, k, v, causal=True)
                 .astype(jnp.float32).sum())(q)
    assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0


def test_model_attention_routes_through_tuned_path(stores):
    from repro.configs.base import ArchConfig, ParallelConfig
    from repro.models import attention as attn_mod

    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=32,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                     parallel=ParallelConfig(remat="none"))
    assert attn_mod._can_use_tuned_sdpa(cfg, causal=True)
    assert not attn_mod._can_use_tuned_sdpa(
        cfg.replace(sliding_window=8), causal=True)
    assert attn_mod._can_use_tuned_sdpa(
        cfg.replace(sliding_window=8), causal=False)
    assert not attn_mod._can_use_tuned_sdpa(
        cfg.replace(logit_softcap=30.0), causal=True)
    params = attn_mod.init_attention(KEY, cfg)
    from repro.models.param import values
    x = jax.random.normal(jax.random.key(3), (2, 16, 32))
    y, _ = attn_mod.attention(values(params), x, cfg)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())


def test_moe_gmm_model_parity_and_grads(stores, monkeypatch):
    from repro.kernels.gmm.ops import gmm_model

    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (4, 32, 16), jnp.float32)
    w = jax.random.normal(ks[1], (4, 16, 24), jnp.float32)
    ref = jnp.einsum("ecd,edf->ecf", x, w)
    np.testing.assert_allclose(np.asarray(gmm_model(x, w)),
                               np.asarray(ref), rtol=2e-5, atol=2e-5)
    # under vmap+jit (the MoE call pattern) and with a pallas pin the
    # differentiable filter must keep grads flowing
    monkeypatch.setenv("REPRO_TUNE_PIN_GMM", '{"impl": "pallas"}')
    f = jax.jit(jax.vmap(gmm_model))
    xb = x[None].repeat(2, axis=0)
    wb = w[None].repeat(2, axis=0)
    np.testing.assert_allclose(np.asarray(f(xb, wb)[0]), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda x_: gmm_model(x_, w).sum())(x)
    assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0


# -------------------------------------------- tracer-safe resolution
def test_tuned_config_is_tracer_safe(stores):
    from repro.kernels.conv2d import ops as conv_ops

    boom = at.set_timer(
        lambda fn: pytest.fail("search ran under jit tracing"))
    try:
        @jax.jit
        def f(img, w):
            return conv_ops.conv2d(img, w)    # config=None -> tuned path

        img = jax.random.normal(KEY, (16, 16))
        w = jax.random.normal(jax.random.key(1), (3, 3))
        out = f(img, w)
        ref = conv_ops.conv2d(img, w, use_kernel=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
    finally:
        at.set_timer(boom)
