"""``BENCHMARK.json`` names only what exists under ``bench/``, in the form
the benchmark's contract asks for."""

import json
import re

import pytest

from bench import harness
from bench import run as brun

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    cells = 24
    assert (2 + 14 * cells) * (BENCH["run_seconds"] + 60) + cells * 180 + 1200 <= 43200


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_files(cfg):
    assert cfg["file"].startswith("bench/configs/")
    body = harness.load_json(harness.ROOT / cfg["file"])
    assert (harness.BENCH_DIR / "systems" / f"{body['system']}.py").exists()
    assert (harness.BENCH_DIR / "refs" / f"{body['reference']}.py").exists()
    assert sorted(cfg["reduced"]) == sorted(body.get("reduced", {}))
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_reports_setup_an_end_to_end_and_a_layer_metric(cell):
    c = harness.Cell(BENCH, cell["name"])
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    assert all(m["moves"] in names for m in c.per_layer)
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200


@pytest.mark.parametrize("chips", [1, 4])
def test_a_run_without_the_chips_it_asks_for_exits_3(chips):
    with pytest.raises(SystemExit) as stop:
        brun.require_chips(chips)  # the tests see only the CPU
    assert stop.value.code == 3


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    assert callable(harness.metric_reader(metric["name"]))


def test_layers_are_named_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in x and len(x) <= 200 for x in layers)
    assert len(json.dumps(BENCH)) < 64 * 1024
