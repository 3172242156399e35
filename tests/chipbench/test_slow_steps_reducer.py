"""``chipbench/reducers/slow_steps.py`` and its six metrics (PR 54): the
reducer on a made-up ``Run`` against stand-ins for the program's record of
its slow steps, and the metric files against their entries."""

import json
import os

import pytest

from chipbench import cells
from chipbench.reducers import program_spans, slow_steps

TRAIN = "train step (runtime/engine.py train_batch)"
SERVE = "serve loop (inference/serving.py, scheduler.py)"
# metric -> (unit, field, layer, what it moves, how many cells).  ISSUE 54
# lists all ten cells; six of them are held to the exact set of their
# entries by tests of this directory that this PR may not edit (PERF.md
# section 7), so the lists hold the four cells that are not: the two dense
# train cells, chat, docbatch.
METRICS = {
    "slow_steps.train": ("count", "count", TRAIN, "train_tok_s_chip", 2),
    "slow_step_pct.train": ("%", "pct", TRAIN, "train_tok_s_chip", 2),
    "slow_steps.chat": ("count", "count", SERVE, "tpot_p90_ms", 1),
    "slow_step_pct.chat": ("%", "pct", SERVE, "tpot_p90_ms", 1),
    "slow_steps.serve": ("count", "count", SERVE, "serve_tok_s", 1),
    "slow_step_pct.serve": ("%", "pct", SERVE, "serve_tok_s", 1)}


def _run(traced_until=None, n=10):
    """``n`` steps of one second from t = 100; traced ones first."""
    steps = [{"kind": "train", "t0": 100.0 + i, "t1": 100.9 + i,
              "traced": traced_until is not None and i < traced_until}
             for i in range(n)]
    return cells.Run(chips=1, peaks={}, model={}, steps=steps,
                     traced_steps=[s for s in steps if s["traced"]],
                     samples={}, counters={}, memory_peak_bytes=0)


class Records:
    """Stands in for ``get_telemetry()``: records made by hand."""

    def __init__(self, *records):
        self.records, self.asked = records, []

    def slow_steps(self, since_ns=None, until_ns=None):
        self.asked.append((since_ns, until_ns))
        return [r for r in self.records
                if r["t0_ns"] >= since_ns and r["t1_ns"] <= until_ns]


def _record(t0, t1, median):
    return {"t0_ns": int(t0 * 1e9), "t1_ns": int(t1 * 1e9),
            "median_ns": int(median * 1e9), "where": "unknown"}


@pytest.mark.parametrize("name", list(METRICS))
def test_the_file_agrees_with_its_entry(name):
    unit, field, layer, moves, n_cells = METRICS[name]
    with open(os.path.join(cells.HERE, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert {k: spec[k] for k in entry} == entry
    assert (spec["reducer"], spec["args"]) == ("slow_steps",
                                               {"field": field})
    assert (entry["unit"], entry["layer"], entry["moves"],
            entry["better"], entry["source"]) == \
        (unit, layer, moves, "lower", "program_counter")
    reporting = next(m["workloads"] for m in bench["end_to_end"]
                     if m["name"] == moves)
    assert len(entry["workloads"]) == n_cells
    assert set(entry["workloads"]) <= set(reporting)
    # the six are the list's last, in the order they were added
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(METRICS)


def test_two_records_and_the_one_in_the_profilers_stop_is_left_out(
        monkeypatch):
    """The profiler stops in the tick ahead of step 3 (the first one whose
    ``traced`` is off): a record that touches the time from step 2's end
    to step 3's end is the stop's, not a step's."""
    run = _run(traced_until=3)
    stalled = _record(105.2, 107.6, 0.4)        # 2.0 s over its median
    at_the_stop = _record(102.5, 103.4, 0.1)
    before_the_window = _record(90.0, 99.0, 0.4)
    tel = Records(stalled, at_the_stop, before_the_window)
    monkeypatch.setattr(program_spans, "telemetry", lambda: tel)
    assert slow_steps.records(run) == [stalled]
    assert tel.asked == [(int(100.0 * 1e9), int(109.9 * 1e9))]
    assert slow_steps.read(run, "count") == 1.0
    assert slow_steps.read(run, "pct") == pytest.approx(100 * 2.0 / 9.9)
    # untraced, the same record at the same place is a step's
    assert slow_steps.read(_run(), "count") == 2.0


def test_the_profilers_start_step_is_left_out_too(monkeypatch):
    run = _run(traced_until=3)
    in_the_start = _record(100.1, 100.8, 0.1)
    monkeypatch.setattr(program_spans, "telemetry",
                        lambda: Records(in_the_start))
    assert slow_steps.read(run, "count") == 0.0
    assert slow_steps.read(run, "pct") == 0.0


@pytest.mark.parametrize("field", ["count", "pct"])
def test_a_sound_run_reads_zero_and_an_older_program_none(monkeypatch, field):
    monkeypatch.setattr(program_spans, "telemetry", lambda: Records())
    value = slow_steps.read(_run(), field)
    assert value == 0.0 and isinstance(value, float)
    monkeypatch.setattr(program_spans, "telemetry", object)
    assert slow_steps.read(_run(), field) is None
    monkeypatch.setattr(program_spans, "telemetry", lambda: Records())
    assert slow_steps.read(_run(n=0), field) is None


def test_the_real_telemetry_has_the_reader():
    from deepspeed_tpu.monitor.telemetry import get_telemetry
    assert get_telemetry().slow_steps(0, 1) == []
    assert slow_steps.read(_run(), "count") == 0.0
