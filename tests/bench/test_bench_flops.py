"""The benchmark's peaks table, its own count of a token's operations,
and the family's check of the program's architecture."""
import json
import os

import benchpaths  # noqa: F401
import pytest

from benchlib import files, peaks

XL = files.module("families", "xlstm")

XLSTM = json.load(open(os.path.join(files.BENCH, "configs",
                                    "xlstm-350m.json")))


def test_peaks_known_and_unknown():
    pk = peaks.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in pk["source"]
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_xlstm_params_match_the_program():
    from repro.configs import registry
    from repro.models import model_zoo
    n = XL.params(XLSTM)
    assert n["total"] == model_zoo.count_params(registry.get("xlstm-350m"))
    assert n["total"] == pytest.approx(518.7e6, rel=1e-3)
    assert n["matmul"] + n["embed"] < n["total"]


def test_xlstm_step_counts():
    per_token = XL.token_flops(XLSTM)
    assert 2 * XL.params(XLSTM)["matmul"] < per_token
    assert per_token < 2.2 * XL.params(XLSTM)["matmul"]
    # the mLSTM cells' state work is the rest: 21 cells of 4 heads
    cell = per_token - 2 * XL.params(XLSTM)["matmul"]
    assert cell == 21 * 4 * (4 * 512 * 512 + 4 * 512)


def test_xlstm_program_check():
    from repro.configs import registry
    arch = registry.get("xlstm-350m")
    XL.check_program(XLSTM, arch)
    with pytest.raises(ValueError):
        XL.check_program(dict(XLSTM, n_layers=12), arch)
    tiny = dict(XLSTM, n_layers=8, d_model=128, vocab_size=512)
    XL.check_program(tiny, XL.resized(arch, tiny))
