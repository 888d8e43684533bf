"""BENCHMARK.json and the files it names: every workload resolves to its
configuration and traffic files, every metric to its reader, every name
and unit keeps to the allowed characters, and each configuration file
states the keys BENCHMARK.json says it changed."""

import json
import re

import pytest

import benchtiny  # noqa: F401  (puts the benchmark and the program on the path)
from chipbench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(wl):
    cs = spec.cell(wl["name"])
    assert cs["config"]["name"] == wl["config"]
    assert cs["traffic"]["rate_rps"] > 0
    assert "setup_s" in cs["end_to_end"] and len(cs["end_to_end"]) >= 2
    assert cs["per_layer"]
    for name in cs["end_to_end"] + cs["per_layer"]:
        assert callable(spec.metric(name))


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(c):
    conf = json.loads((spec.ROOT / c["file"]).read_text())
    assert conf["source"] == c["source"]
    assert sorted(conf["reduced"]) == sorted(c["reduced"])
    assert c["file"].startswith(BENCH["paths"][0] + "/")


def test_names_and_units():
    names = [m["name"] for m in METRICS] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [c["name"] for c in BENCH["configs"]] + \
        [w["traffic"] for w in BENCH["workloads"]] + \
        [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    assert len({m["name"] for m in METRICS}) == len(METRICS)


def test_per_layer_metrics_name_their_cells_and_arrow():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v4")
