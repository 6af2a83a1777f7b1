"""Every JSON under benchmark/ loads and cross-references, names keep to
the contract's characters, and a new cell, traffic mix, configuration and
per-layer metric can be added as files and entries alone."""

import json
import os
import re

import pytest

from conftest import BENCH, REPO
from harness import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def names(kind):
    return sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, kind))
                  if f.endswith(".json"))


def test_benchmark_json_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in bench["end_to_end"])


@pytest.mark.parametrize("kind", ["configs", "traffic", "workloads",
                                  "metrics"])
def test_every_json_loads(kind):
    for name in names(kind):
        assert NAME.match(name), name
        assert isinstance(common.load_json(kind, name), dict)


def test_names_units_and_lengths(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in bench[group]]
        assert len(seen) == len(set(seen)), group
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e
                assert e["better"] in ("lower", "higher")
                assert e["source"] in SOURCES
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_cells_reference_files(bench):
    cfgs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        cell = common.load_json("workloads", w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"],
                cell["why"]) == (w["config"], w["traffic"], w["chips"],
                                 w["why"])
        assert w["config"] in cfgs
        used.add(w["config"])
        cfg = common.load_json("configs", w["config"])
        common.load_json("traffic", w["traffic"])
        common.load_module("drivers", cfg["kind"])
        common.load_module("references", cfg["reference"])
        assert "setup_s" in cell["end_to_end"] and \
            len(cell["end_to_end"]) >= 2
    assert used == set(cfgs)
    for c in bench["configs"]:
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        cfg = common.load_json("configs", c["name"])
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden_size|n_embd|"
                                 r"intermediate_size|head)", key), key


def test_metrics_cross_reference(bench):
    cells = {w["name"]: common.load_json("workloads", w["name"])
             for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name, cell in cells.items():
        for m in cell["end_to_end"]:
            assert m in e2e
            assert name in e2e[m].get("workloads", cells)
    listed = {m["name"]: m for m in bench["per_layer"]}
    files = dict(common.metric_files())
    assert set(listed) == set(files)
    for name, spec in files.items():
        entry = listed[name]
        for key in ("unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert spec[key] == entry[key], (name, key)
        common.load_module("readers", spec["reader"])
        assert spec["moves"] in e2e and spec["moves"] != "setup_s"
        for cell in spec["workloads"]:
            assert spec["moves"] in cells[cell]["end_to_end"], (name, cell)
        if name.endswith("_roofline") or "_roofline." in name:
            assert spec["unit"] == "%"
    for name, cell in cells.items():
        mine = [s for s in files.values() if name in s["workloads"]]
        assert mine, name
        kernels = [s for s in mine if "roofline" in s["reader"]]
        whole = [s for s in mine if s["reader"] == "mfu"]
        assert whole and all(k["moves"] == whole[0]["moves"]
                             for k in kernels)


def test_added_files_load_without_an_edit(tiny_root):
    """conftest's copy adds a cell, a traffic mix, a configuration and a
    metric on an existing reader; the harness finds each by name."""
    for kind, name in (("workloads", "tiny_train"), ("traffic", "tiny_job"),
                       ("configs", "tiny_bert"),
                       ("metrics", "train_steps.tiny")):
        assert common.load_json(kind, name, tiny_root)
    found = dict(common.metric_files(tiny_root))
    assert found["train_steps.tiny"]["reader"] == "counter"
