"""BENCHMARK.json against the contract, and the lookup by name: every cell
resolves to a configuration file, a traffic file and its metric readers with
no table in code. Parametrised over the manifest's entries, so a cell, a
configuration or a metric added later is covered without an edit."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}
ALL_METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def cells_of(metric):
    return metric.get("workloads", list(CELLS))


def ids(entries):
    return [e["name"] for e in entries]


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert 1 <= len(MANIFEST["workloads"]) <= 24
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p)), p
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1
    names = ids(ALL_METRICS) + ids(MANIFEST["configs"]) + list(CELLS)
    assert len(ids(ALL_METRICS)) == len(set(ids(ALL_METRICS)))
    assert all(NAME.match(n) for n in names)
    # the budget of a full check with the full 24 cells
    runs = 2 + 14 * 24
    assert (runs * (MANIFEST["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)


def test_at_most_one_cell_on_four_chips():
    four = [w["name"] for w in CELLS.values() if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4), four
    assert all(w["chips"] in (1, 4) for w in CELLS.values())


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=ids(MANIFEST["workloads"]))
def test_cell_resolves_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert NAME.match(cell["traffic"])
    config = {c["name"]: c for c in MANIFEST["configs"]}[cell["config"]]
    assert config["file"] == f"benchmark/configs/{cell['config']}.json"
    json.load(open(os.path.join(ROOT, config["file"])))
    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json")))
    assert traffic["kind"] in ("train_steps", "open_loop", "closed_loop")
    mine = [m for m in ALL_METRICS if cell["name"] in cells_of(m)]
    assert sum(m["name"] in E2E and m["name"] != "setup_s"
               for m in mine) >= 1, "no end-to-end metric beside setup_s"
    layer = [m for m in MANIFEST["per_layer"] if cell["name"] in cells_of(m)]
    assert layer, "no per-layer metric"
    for m in layer:  # the reader is found by the metric's base name
        base = m["name"].split(".")[0]
        path = os.path.join(ROOT, "benchmark", "metrics", base + ".py")
        assert os.path.isfile(path), path
        assert "def read(run)" in open(path).read()


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=ids(MANIFEST["configs"]))
def test_config_file_states_the_model(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(w["config"] == config["name"] for w in CELLS.values())
    body = json.load(open(os.path.join(ROOT, config["file"])))
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"]
    pub, model = body["published"], body["model"]
    # no width differs from the source; only what `reduced` names may
    widths = {"d_model": "hidden_size", "d_ff": "intermediate_size",
              "num_heads": "num_attention_heads",
              "num_kv_heads": "num_key_value_heads", "vocab": "vocab_size"}
    for ours, theirs in widths.items():
        assert model[ours] == pub[theirs], ours
        assert theirs not in config["reduced"]
    if "num_hidden_layers" in config["reduced"]:
        assert model["num_layers"] == body["num_hidden_layers"]
        assert model["num_layers"] < pub["num_hidden_layers"]
    else:
        assert model["num_layers"] == pub["num_hidden_layers"]
    assert body["assumed"] and body["departures"] and body["deployment"]


@pytest.mark.parametrize("metric", ALL_METRICS, ids=ids(ALL_METRICS))
def test_metric_entry(metric):
    e2e = metric["name"] in E2E
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert set(cells_of(metric)) <= set(CELLS)
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        # every cell that reports this metric reports the one it moves
        moved = E2E[metric["moves"]]
        assert set(cells_of(metric)) <= set(cells_of(moved))
        assert 1 <= len(metric["layer"]) <= 200
