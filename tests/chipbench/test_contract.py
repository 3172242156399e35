"""BENCHMARK.json against the contract it is read by, and against the
benchmark's own files (the driver checks the first before any run; the
second is what keeps the data-driven harness whole)."""

import json
import os
import re

import pytest

from tree import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _spec(name):
    path = os.path.join(REPO, "chipbench", "layer_metrics", name + ".json")
    with open(path) as f:
        return json.load(f)


def test_keys_names_and_lengths(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), group,
                          entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200, (entry["name"], key)
                    assert "\n" not in entry[key] and "\t" not in entry[key]
    metric_names = [n for is_metric, _, n in names if is_metric]
    assert len(metric_names) == len(set(metric_names))
    for entry in bench["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
    for entry in bench["workloads"]:
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
        assert entry["chips"] in (1, 4)
    for entry in bench["end_to_end"]:
        assert set(entry) - {"workloads"} == {"name", "unit", "better",
                                              "bound", "source"}
        assert 0.01 <= entry["bound"] <= 0.1
        assert entry["source"] in ("host_clock", "device_trace")
    for entry in bench["per_layer"]:
        assert set(entry) - {"workloads"} == {"name", "unit", "better",
                                              "source", "layer", "moves"}
        assert entry["source"] in SOURCES
    for entry in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(entry["unit"]) and entry["better"] in (
            "lower", "higher")


def test_cells_configs_and_shares(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = bench["workloads"]
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert {w["config"] for w in cells} == set(configs)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    widths = re.compile(r"_size$|_dim$|_rank$|head|experts_per_tok")
    for config in configs.values():
        assert config["file"].startswith("chipbench/configs/")
        with open(os.path.join(REPO, config["file"])) as f:
            held = json.load(f)
        assert held["reduced"] == config["reduced"]
        assert set(held["published"]) == set(config["reduced"])
        assert not [k for k in config["reduced"] if widths.search(k)]
        assert held["source"] == config["source"]
    for w in cells:
        path = os.path.join(REPO, "chipbench", "traffic",
                            w["traffic"] + ".json")
        assert os.path.exists(path), path


def test_every_cell_reports_what_its_metrics_move(bench):
    def cells_of(metric):
        return set(metric.get("workloads",
                              [w["name"] for w in bench["workloads"]]))

    end_to_end = {m["name"]: cells_of(m) for m in bench["end_to_end"]}
    assert end_to_end["setup_s"] == {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        assert sum(w["name"] in c for c in end_to_end.values()) >= 2
        assert any(w["name"] in cells_of(m) for m in bench["per_layer"])
    for metric in bench["per_layer"]:
        assert cells_of(metric) <= end_to_end[metric["moves"]], metric["name"]


def test_layer_metric_files_agree_with_the_entries(bench):
    folder = os.path.join(REPO, "chipbench", "layer_metrics")
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert {f[:-5] for f in os.listdir(folder)} == set(entries)
    for name, entry in entries.items():
        spec = _spec(name)
        assert {k: spec[k] for k in entry} == entry
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "reducers", spec["reducer"] + ".py"))
    layers = {m["layer"] for m in bench["per_layer"]}
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    assert not [layer for layer in layers if layer not in perf]
