"""Lane map and per-lane device identity.

``detect_platform`` is checked against fake device lists (objects with
``platform``/``device_kind``), so the TPU-host layout is tested without a
chip.  The interpret flag, the autotune default and the tune-cache key
must follow the device a call runs on (``jax.default_device``), not the
process's default backend.
"""
from types import SimpleNamespace

import jax
import pytest

from repro.core import device as device_mod
from repro.core import hybrid_executor as hx
from repro.kernels import autotune as at
from repro.kernels.common import default_interpret


def _dev(platform, i=0, kind=None):
    return SimpleNamespace(platform=platform, id=i,
                           device_kind=kind or f"{platform} device")


def _fake_devices(monkeypatch, default, cpus):
    def devices(backend=None):
        return list(cpus if backend == "cpu" else default)
    monkeypatch.setattr(jax, "devices", devices)


def test_tpu_host_lanes_are_the_tpu_and_the_host_cpu(monkeypatch):
    tpu = _dev("tpu", kind="TPU v5 lite")
    cpus = [_dev("cpu", i) for i in range(8)]
    _fake_devices(monkeypatch, [tpu], cpus)
    groups, simulated = hx.detect_platform()
    assert not simulated
    assert [g.name for g in groups] == ["accel", "host"]
    assert groups[0].devices == [tpu] and groups[0].device is tpu
    assert groups[1].devices == [cpus[0]]
    assert [g.slowdown for g in groups] == [1.0, 1.0]


@pytest.mark.parametrize("n_cpu,expect_sim", [(1, True), (2, False),
                                              (4, False)])
def test_cpu_only_keeps_todays_groups(monkeypatch, n_cpu, expect_sim):
    cpus = [_dev("cpu", i) for i in range(n_cpu)]
    _fake_devices(monkeypatch, cpus, cpus)
    groups, simulated = hx.detect_platform(simulated_ratio=4.0)
    assert simulated == expect_sim
    if expect_sim:
        # one device: the simulated pair with the host slowed down
        assert groups[0].devices == groups[1].devices == cpus[:1]
        assert [g.slowdown for g in groups] == [1.0, 4.0]
    else:
        half = n_cpu // 2
        assert groups[0].devices == cpus[:half]
        assert groups[1].devices == cpus[half:]


def test_several_tpus_raise_and_name_the_fleet_path(monkeypatch):
    tpus = [_dev("tpu", i, "TPU v5 lite") for i in range(4)]
    _fake_devices(monkeypatch, tpus, [_dev("cpu")])
    with pytest.raises(RuntimeError, match="one worker process per chip"):
        hx.detect_platform()


def test_interpret_flag_and_tune_key_follow_default_device(
        monkeypatch, tmp_path):
    cpu = jax.devices("cpu")[0]                 # the real host device
    tpu = _dev("tpu", kind="TPU v5 lite")
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    at.reset_tune_cache()
    cache = at.get_tune_cache()
    cache.put("tpu", "k", "b", {"impl": "pallas"}, 1.0)
    cache.put("cpu", "k", "b", {"impl": "xla"}, 1.0)
    seed, safe = {"impl": "pallas", "t": 1}, {"impl": "xla", "t": 1}
    # a process whose first device is a TPU: no lane context -> the TPU
    _fake_devices(monkeypatch, [tpu], [cpu])
    try:
        assert device_mod.platform() == "tpu"
        assert default_interpret() is False
        assert at.default_config(seed, safe) == seed
        assert at.cached_or_default("k", "b", safe)["impl"] == "pallas"
        # the host lane's context: the same process, now the CPU
        with jax.default_device(cpu):
            assert device_mod.current_device() is cpu
            assert default_interpret() is True
            assert at.default_config(seed, safe) == safe
            assert at.cached_or_default("k", "b", seed)["impl"] == "xla"
            assert at.tuned_entry("k", "b")["config"] == {"impl": "xla"}
    finally:
        at.reset_tune_cache()


def test_chip_env_gives_each_worker_one_chip():
    from repro.serve.transport import chip_env
    env = chip_env(2)
    # the platforms are named: a chip that fails to open is an error,
    # not a quiet fall back to the CPU
    assert env["JAX_PLATFORMS"] == "tpu,cpu"
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_ADDRESSES"] == f"localhost:{env['TPU_PROCESS_PORT']}"
    # a worker beyond the host's chips runs on the CPU
    assert chip_env(None) == {"JAX_PLATFORMS": "cpu"}


def test_device_report_names_this_process_device():
    from repro.serve.transport import device_report
    rep = device_report()
    d = jax.devices()[0]
    assert (rep["platform"], rep["kind"], rep["id"]) == (
        d.platform, d.device_kind, d.id)
    # the CPU opens no accelerator device file
    assert rep["files"] == []


def test_router_keeps_each_workers_reported_device():
    """Workers report their device in every heartbeat; the router
    keeps the latest, so a fleet can prove each worker's chip."""
    from repro.serve.router import Router
    from repro.serve.transport import HeartbeatMsg

    class Beating:
        transport_alive = True

        def __init__(self, name):
            self.name = name

        def start(self, on_result, on_heartbeat):
            self.on_heartbeat = on_heartbeat

        def submit(self, msg):
            return False

        def shutdown(self, timeout=10.0):
            pass

    ws = [Beating("a"), Beating("b")]
    router = Router(ws, hb_timeout_s=60.0).start()
    try:
        for i, w in enumerate(ws):
            w.on_heartbeat(w.name, HeartbeatMsg(
                0.0, device={"platform": "tpu", "files": [f"/dev/vfio/{i}"]}))
        devs = router.worker_devices()
        assert devs["a"]["files"] == ["/dev/vfio/0"]
        assert devs["b"]["files"] == ["/dev/vfio/1"]
        # a heartbeat without a device report leaves the entry empty
        ws[0].on_heartbeat("a", HeartbeatMsg(0.0))
        assert router.worker_devices()["a"] == {}
    finally:
        router.shutdown(timeout=5)


def test_router_process_imports_no_jax():
    """The fleet's parent must hold no chip: importing the router and
    the worker transport pulls in no JAX."""
    import os
    import subprocess
    import sys
    code = ("import sys; import repro.serve.router, repro.serve.transport; "
            "print('jax' in sys.modules)")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env={**os.environ, "PYTHONPATH": src})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir_is_fixed(monkeypatch, tmp_path, from_env):
    from repro.core import compile_cache
    was = jax.config.jax_compilation_cache_dir
    was_min = jax.config.jax_persistent_cache_min_compile_time_secs
    if from_env:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    else:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        path = compile_cache.enable()
        # fast compiles are cached too
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        if from_env:
            # JAX reads the variable itself; nothing else is set
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == was
        else:
            assert path == compile_cache.DEFAULT_DIR
            assert path.endswith(".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was_min)
