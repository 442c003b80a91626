"""Every configuration, traffic mix, reference, system and metric that
BENCHMARK.json names is found by its name, and the file keeps the
benchmark's contract on names, units and keys."""
import os
import re

import benchpaths  # noqa: F401
import pytest

from benchlib import files

BENCH = files.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = {w["name"] for w in BENCH["workloads"]}
E2E = {m["name"] for m in BENCH["end_to_end"]}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(files.ROOT, p))
    assert os.path.isfile(os.path.join(files.ROOT, BENCH["command"][1]))
    assert os.path.getsize(os.path.join(files.ROOT, "BENCHMARK.json")) < 65536


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_found_by_name(entry):
    assert NAME.match(entry["name"])
    cfg = files.config(entry)
    assert cfg["name"] == entry["name"]
    ref = files.module("reference", entry["name"])
    assert hasattr(ref, "LIMITS")
    system = files.module("systems", cfg["system"])
    assert callable(system.setup)
    family = files.module("families", cfg["family"])
    assert callable(family.check_program) and callable(family.token_flops)
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_reduced_names_every_departure(entry):
    """Each key changed from the source is in ``reduced``, states the
    source's value under ``departures``, and is what the reference
    computes; no width is among them."""
    cfg = files.config(entry)
    stated = set(cfg.get("departures", {})) - {"why", "params"}
    assert set(entry["reduced"]) == stated
    ref = files.module("reference", entry["name"])
    for k in entry["reduced"]:
        assert NAME.match(k) and k in cfg
        assert ref.STRUCTURE[k] == cfg[k]
        assert not re.search(r"_dim$|_rank$|hidden|intermediate|latent|"
                             r"state|proj|head_|factor|size", k)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_found_by_name(cell):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    w, cfg_entry = files.cell(BENCH, cell["name"])
    assert w is not None and cfg_entry["name"] == cell["config"]
    tr = files.traffic(cell["traffic"])
    assert tr["loop"] in ("open", "closed")
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    reported = files.metrics_of(BENCH, cell["name"], layer=False)
    assert "setup_s" in {m["name"] for m in reported}
    assert len(reported) >= 2
    assert files.metrics_of(BENCH, cell["name"], layer=True)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_found_by_name(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert callable(files.module("metrics", metric["name"]).read)
    assert set(metric.get("workloads", [])) <= CELLS
    if metric in BENCH["per_layer"]:
        assert metric["moves"] in E2E
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["workloads"]
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25


def test_no_two_names_alike():
    for key in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[key]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        files.cell(BENCH, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        files.module("metrics", "no_such_metric")
