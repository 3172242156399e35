"""Find a cell's files by the names in ``BENCHMARK.json``: the harness is
driven by data, so a later PR adds a cell, a configuration, a traffic mix,
a per-layer metric or a reducer as files of its own plus entries, and edits
nothing that is here."""

import importlib
import json
import os
from dataclasses import dataclass

from chipbench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # chipbench/configs/<config>.json
    mix: dict             # chipbench/traffic/<traffic>.json
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list       # chipbench/layer_metrics/<name>.json, same

    @property
    def family(self):
        return importlib.import_module(
            "chipbench.families." + self.config["family"])

    @property
    def reference(self):
        return importlib.import_module(
            "chipbench.reference." + self.family.REFERENCE)


def _reported_in(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name):
    bench = _json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in "
                         f"BENCHMARK.json (has: {sorted(cells)})")
    entry = cells[name]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    config = _json(ROOT, config_entry["file"])
    per_layer = [
        _json(HERE, "layer_metrics", m["name"] + ".json")
        for m in bench["per_layer"] if _reported_in(m, name)]
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                mix=traffic.load_mix(entry["traffic"]),
                end_to_end=[m for m in bench["end_to_end"]
                            if _reported_in(m, name)],
                per_layer=per_layer)


def read_layer_metrics(cell, run):
    """Each per-layer metric is a small reader of its own
    (``chipbench/reducers/<reducer>.py``: ``read(run, **args)``); one that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for spec in cell.per_layer:
        reader = importlib.import_module(
            "chipbench.reducers." + spec["reducer"])
        value = reader.read(run, **spec.get("args", {}))
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


@dataclass
class Run:
    """What one run measured, as the per-layer readers see it.  ``steps``
    are the whole steps inside the window, ``traced_steps`` those inside
    the traced part of it: dicts with at least ``kind``, ``t0``, ``t1``
    (host clock, seconds) and ``tokens``."""
    chips: int
    peaks: dict
    model: dict
    steps: list
    traced_steps: list
    samples: dict
    counters: dict
    memory_peak_bytes: int
    trace: object = None       # chipbench.reduce.Trace of a traced run
    config: dict = None        # the cell's configuration file, as loaded
