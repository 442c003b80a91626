"""A whole run of a cell on the CPU at a tiny size, chip check skipped:
sound, it reads correct; with the timed path broken underneath (a token
altered where it is produced, a decode step that returns its state
unchanged), it reads not correct.
And the command refuses to run without a chip or without the program."""
import json
import os
import shutil
import subprocess
import sys

import benchpaths
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


@pytest.mark.parametrize("cell,fault", [
    ("xlstm-batch", ""), ("xlstm-batch", "token"), ("xlstm-batch", "state")])
def test_run_reads_the_fault(cell, fault, tmp_path):
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_run.py"), cell,
         str(tmp_path), fault],
        capture_output=True, text=True, timeout=280, env=_env())
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compared"], out
    assert out["correct"] is (fault == ""), out["compared"]
    if fault:
        assert any(c["value"] > c["limit"] for c in out["compared"])


def _a_cell():
    with open(os.path.join(benchpaths.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)["workloads"][0]["name"]


def test_no_chip_no_result(tmp_path):
    res = subprocess.run(
        [sys.executable, os.path.join(benchpaths.BENCH, "run.py"),
         "--workload", _a_cell(), "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        env=_env(), cwd=benchpaths.ROOT)
    assert res.returncode == 3
    assert res.stdout.strip() == ""


def test_no_program_no_result(tmp_path):
    shutil.copy(os.path.join(benchpaths.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(benchpaths.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", _a_cell(),
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, env=_env(),
        cwd=tmp_path)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
