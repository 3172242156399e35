"""``chipbench/reducers/startup_account.py`` and the eight ``.setup``
metrics (PR 39): the reducer on a made-up ``Run`` against the program's
real account and against stand-ins for it, the metric files against their
entries, and one traced toy run of each kind through ``tree.py``'s
made-up cells, which report the eight because no cell is listed."""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

import tree
from chipbench import cells
from chipbench.reducers import program_spans, startup_account
from deepspeed_tpu.monitor.telemetry import get_telemetry, setup_span

LAYER = ("set-up (monitor/telemetry.py account: import, engine "
         "construction, programs compiled or read)")
# metric -> (unit, source)
SETUP = {"import_s": ("s", "program_span"),
         "engine_init_s": ("s", "program_span"),
         "engine_state_s": ("s", "program_span"),
         "trace_lower_s": ("s", "program_counter"),
         "compile_s": ("s", "program_counter"),
         "cache_read_s": ("s", "program_counter"),
         "programs": ("programs", "program_counter"),
         "repeat_compiles": ("programs", "program_counter")}
METRICS = os.path.join(cells.HERE, "layer_metrics")


def _run(first_step_at):
    steps = [] if first_step_at is None else \
        [{"kind": "serve", "t0": first_step_at, "t1": first_step_at + 0.1}]
    return cells.Run(chips=1, peaks={}, model={}, steps=steps,
                     traced_steps=steps, samples={}, counters={},
                     memory_peak_bytes=0)


class Account:
    """Stands in for ``get_telemetry()``: a report made by hand."""

    def __init__(self, **seconds):
        self.asked = []
        self.report = {"programs": 7, "cache_hits": 5, "cache_misses": 2,
                       "repeat_compiles": 1,
                       "seconds": {"import": 0.0, "engine": 0.0, **seconds}}

    def startup_report(self, until_ns=None):
        self.asked.append(until_ns)
        return self.report


def test_the_fields_are_the_eight_metrics():
    assert set(startup_account.FIELDS) == set(SETUP)


@pytest.mark.parametrize("field", list(SETUP))
def test_each_field_of_a_made_up_account(monkeypatch, field):
    account = Account(**{"import": 20.5, "engine": 4.25, "engine/state": 1.5,
                         "engine/pools": 0.25, "trace": 3.0, "lower": 2.0,
                         "compile": 31.0, "cache_read": 6.5})
    monkeypatch.setattr(program_spans, "telemetry", lambda: account)
    want = {"import_s": 20.5, "engine_init_s": 4.25, "engine_state_s": 1.75,
            "trace_lower_s": 5.0, "compile_s": 31.0, "cache_read_s": 6.5,
            "programs": 7.0, "repeat_compiles": 1.0}
    value = startup_account.read(_run(12.5), field)
    assert value == want[field] and isinstance(value, float)
    # the account is read up to the window's first step, on its clock
    assert account.asked == [12_500_000_000]
    startup_account.read(_run(None), field)
    assert account.asked[-1] is None


@pytest.mark.parametrize("field", list(SETUP))
def test_nothing_happened_reads_zero_and_no_account_reads_none(monkeypatch,
                                                               field):
    # the program's own account, cut before anything was recorded
    value = startup_account.read(_run(0.0), field)
    assert value == 0.0 and isinstance(value, float)
    # a program from before the account: a telemetry object without it
    monkeypatch.setattr(program_spans, "telemetry", lambda: object())
    assert startup_account.read(_run(0.0), field) is None


def test_the_reducer_reads_the_programs_own_account():
    x = jnp.ones((3,))      # made first: a program or two of its own
    before = {f: startup_account.read(_run(time.perf_counter()), f)
              for f in SETUP}
    with setup_span("setup/engine", kind="serving"):
        with setup_span("setup/engine/pools"):
            time.sleep(0.02)
        jax.jit(lambda x: x * 3 + 39)(x)
    cut = time.perf_counter()
    jax.jit(lambda x: x * 3 + 40)(x)       # after the "window"
    grew = {f: startup_account.read(_run(cut), f) - before[f] for f in SETUP}
    made = get_telemetry().compile_log(until_ns=int(cut * 1e9))[-1]
    assert grew["programs"] == 1 and grew["repeat_compiles"] == 0
    assert grew["import_s"] == 0.0
    assert 0.02 <= grew["engine_state_s"] <= grew["engine_init_s"]
    assert grew["trace_lower_s"] == pytest.approx(
        made["trace_s"] + made["lower_s"])
    assert grew["compile_s"] + grew["cache_read_s"] == pytest.approx(
        made["backend_s"])
    assert before["import_s"] > 0       # the package's own import


@pytest.mark.parametrize("name", list(SETUP))
def test_a_setup_metrics_file_agrees_with_its_entry(name):
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    unit, source = SETUP[name]
    entry = {"name": name + ".setup", "unit": unit, "better": "lower",
             "source": source, "layer": LAYER, "moves": "setup_s"}
    assert [m for m in bench["per_layer"]
            if m["name"] == entry["name"]] == [entry]
    with open(os.path.join(METRICS, name + ".setup.json")) as f:
        spec = json.load(f)
    assert spec == dict(entry, reducer="startup_account",
                        args={"field": name})
    with open(os.path.join(cells.ROOT, "PERF.md")) as f:
        assert LAYER in f.read()


def test_the_eight_are_the_end_of_per_layer_and_every_cell_reports_them():
    """The eight, in their order, found by what they move (they were the
    end of ``per_layer`` when PR 39 added them; every PR appends)."""
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"]
            if m["moves"] == "setup_s"] == [n + ".setup" for n in SETUP]
    assert {f[:-5] for f in os.listdir(METRICS) if f.endswith(".setup.json")} \
        == {name + ".setup" for name in SETUP}
    for workload in bench["workloads"]:
        cell = cells.load_cell(workload["name"])
        assert {m["name"] for m in cell.per_layer} >= \
            {name + ".setup" for name in SETUP}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tree.make(tmp_path_factory.mktemp("setup_tree"))


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-chat"])
def test_a_traced_toy_run_reports_the_eight_as_numbers(checkout, cell):
    line, earlier = tree.run(checkout, cell, trace=1)
    metrics, log = line["metrics"], earlier[-1]
    for name, (unit, _) in SETUP.items():
        assert metrics[name + ".setup"]["unit"] == unit
        assert metrics[name + ".setup"]["value"] >= 0.0
    programs = metrics["programs.setup"]["value"]
    # the harness counts the same events from its own later start
    made = log.get("compiles_in_warm_up",
                   log["compiles_total"] - log["compiles_in_window"])
    assert 0 <= programs - made <= 4
    assert metrics["import_s.setup"]["value"] > 0
    assert metrics["engine_init_s.setup"]["value"] >= \
        metrics["engine_state_s.setup"]["value"] > 0
    assert metrics["trace_lower_s.setup"]["value"] > 0
    # compiled or read: the checkout's cache may be cold or warm
    assert metrics["compile_s.setup"]["value"] + \
        metrics["cache_read_s.setup"]["value"] > 0
