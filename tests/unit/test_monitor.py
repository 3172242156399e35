"""Monitor + unified telemetry tests (parity model: reference
``tests/unit/monitor/test_monitor.py`` plus the telemetry spine this repo
adds: JSONL sink rotation, metrics registry, spans, stall watchdog, and
the engine smoke run that exercises the whole stream)."""

import json
import os

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.monitor import (JsonlEventSink, MetricsRegistry,
                                   MonitorMaster, StepStallWatchdog,
                                   Telemetry, get_telemetry)
from deepspeed_tpu.monitor.monitor import csvMonitor
from deepspeed_tpu.runtime.config import CSVConfig, TelemetryConfig
from unit.simple_model import SimpleModel, base_config, random_batch


@pytest.fixture(autouse=True)
def _reset_telemetry():
    yield
    tel = get_telemetry()
    tel.close()
    tel.registry.reset()
    tel.config = None


def _events(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ----------------------------------------------------------------------
# existing writers
# ----------------------------------------------------------------------
def test_csv_monitor_file_layout(tmp_path):
    cfg = CSVConfig({"enabled": True, "output_path": str(tmp_path),
                     "job_name": "JobA"})
    mon = csvMonitor(cfg)
    mon.write_events([("Train/loss", 0.5, 1), ("Train/lr", 0.01, 1)])
    mon.write_events([("Train/loss", 0.4, 2)])
    loss_csv = tmp_path / "JobA" / "Train_loss.csv"
    lr_csv = tmp_path / "JobA" / "Train_lr.csv"
    assert loss_csv.exists() and lr_csv.exists()
    rows = loss_csv.read_text().strip().splitlines()
    assert rows[0] == "step,Train/loss"
    assert rows[1:] == ["1,0.5", "2,0.4"]


def test_monitor_master_rank_gating(tmp_path, monkeypatch):
    cfg = {
        "tensorboard": CSVConfig({}),  # .enabled=False is all that's read
        "wandb": CSVConfig({}),
        "csv_monitor": CSVConfig({"enabled": True,
                                  "output_path": str(tmp_path)}),
        "telemetry": TelemetryConfig({}),
    }
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    master = MonitorMaster(cfg)
    assert not master.enabled
    assert master.csv_monitor is None
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    master = MonitorMaster(cfg)
    assert master.enabled
    assert master.csv_monitor is not None


def test_monitor_master_jsonl_writer(tmp_path):
    tel_cfg = TelemetryConfig({"enabled": True,
                               "output_path": str(tmp_path),
                               "job_name": "JobB"})
    cfg = {"tensorboard": CSVConfig({}), "wandb": CSVConfig({}),
           "csv_monitor": CSVConfig({}), "telemetry": tel_cfg}
    master = MonitorMaster(cfg)
    assert master.enabled and master.jsonl_monitor is not None
    master.write_events([("Train/loss", 0.25, 3)])
    evs = _events(tmp_path / "JobB" / "events.jsonl")
    assert len(evs) == 1
    assert evs[0]["kind"] == "gauge" and evs[0]["name"] == "Train/loss"
    assert evs[0]["value"] == 0.25 and evs[0]["step"] == 3


# ----------------------------------------------------------------------
# JSONL sink
# ----------------------------------------------------------------------
def test_jsonl_sink_rotation(tmp_path):
    sink = JsonlEventSink(str(tmp_path), max_bytes=300, max_files=3)
    for i in range(40):
        sink.emit({"ts": 0.0, "kind": "meta", "name": f"event-{i:03d}"})
    sink.close()
    live = tmp_path / "events.jsonl"
    assert live.exists()
    gens = sorted(p.name for p in tmp_path.glob("events.jsonl.*"))
    assert gens and all(g.rsplit(".", 1)[1].isdigit() for g in gens)
    assert len(gens) <= 3  # max_files bounds the generations kept
    # newest rotated generation continues seamlessly from the live file
    rot1 = _events(tmp_path / "events.jsonl.1")
    assert all(ev["kind"] == "meta" for ev in rot1)
    total = sum(len(_events(p)) for p in
                [live] + list(tmp_path.glob("events.jsonl.*")))
    assert total < 40   # oldest generation beyond max_files was dropped
    assert total >= 10  # ...but the retained window survived


# ----------------------------------------------------------------------
# metrics registry
# ----------------------------------------------------------------------
def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("n").inc()
    reg.counter("n").inc(4)
    assert reg.counter("n").value == 5
    g = reg.gauge("hbm")
    g.set(10.0)
    g.set(3.0)
    assert g.value == 3.0 and g.peak == 10.0
    h = reg.histogram("lat")
    for v in range(1, 101):
        h.observe(float(v), now=100.0)
    assert h.percentile(50, now=100.0) == pytest.approx(50.0, abs=1.0)
    s = h.summary(now=100.0)
    assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
    assert s["p99"] >= s["p90"] >= s["p50"]
    snap = reg.snapshot()
    assert snap["counters"]["n"] == 5
    assert snap["gauges"]["hbm"] == {"value": 3.0, "peak": 10.0}


def test_histogram_time_window_pruning():
    reg = MetricsRegistry()
    h = reg.histogram("w", window_secs=10.0)
    h.observe(1.0, now=0.0)
    h.observe(2.0, now=9.0)
    assert sorted(h.values(now=9.5)) == [1.0, 2.0]
    assert h.values(now=15.0) == [2.0]  # first sample aged out


# ----------------------------------------------------------------------
# spans
# ----------------------------------------------------------------------
def test_span_disabled_is_noop():
    tel = Telemetry()  # enabled=False
    with tel.span("x"):
        pass  # must not raise, must not create state
    assert tel.registry.snapshot()["histograms"] == {}


def test_span_emits_event_and_histogram(tmp_path):
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "spans"}), rank=0)
    with tel.span("work", step=7, attrs={"k": "v"}):
        pass
    tel.close()
    (ev,) = _events(tmp_path / "spans" / "events.jsonl")
    assert ev["kind"] == "span" and ev["name"] == "work"
    assert ev["step"] == 7 and ev["dur_ms"] >= 0
    assert ev["attrs"] == {"k": "v"}
    assert tel.registry.histogram("span/work").summary()["count"] == 1


def test_nonzero_rank_writes_no_events(tmp_path):
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "r1"}), rank=1)
    assert tel.enabled and tel.sink is None
    tel.emit("meta", "x")  # swallowed
    with tel.span("y"):
        pass  # registry still records
    assert not (tmp_path / "r1" / "events.jsonl").exists()
    assert tel.registry.histogram("span/y").summary()["count"] == 1


# ----------------------------------------------------------------------
# stall watchdog
# ----------------------------------------------------------------------
def test_watchdog_stall_event(tmp_path):
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "wd"}), rank=0)
    wd = StepStallWatchdog(tel, stall_factor=10.0, min_stall_secs=0.0)
    wd.beat(0)
    wd.beat(1)
    wd.beat(2)  # two measured durations -> median defined
    median = wd.median_step_secs()
    assert median is not None
    # forced slow step: evaluate at an artificial future instant
    import time as _time
    future = _time.monotonic() + max(10.0 * median, 0.001) * 100
    assert wd.check(now=future)
    assert not wd.check(now=future)  # one event per stall, not a flood
    tel.close()
    evs = _events(tmp_path / "wd" / "events.jsonl")
    hb = [e for e in evs if e["kind"] == "heartbeat"]
    assert [e["step"] for e in hb] == [0, 1, 2]
    assert "step_ms" not in hb[0] and hb[1]["step_ms"] >= 0
    (stall,) = [e for e in evs if e["kind"] == "stall"]
    assert stall["step"] == 2
    assert stall["gap_s"] > stall["threshold_s"]
    assert stall["median_step_s"] == pytest.approx(median, abs=1e-6)
    # a new beat re-arms the watchdog
    wd.beat(3)
    assert wd.check(now=_time.monotonic() + max(10.0 * median, 0.001) * 100)


def test_watchdog_needs_history():
    wd = StepStallWatchdog(Telemetry(), min_stall_secs=0.0)
    assert not wd.check(now=1e9)   # no beats yet
    wd.beat(0)
    assert not wd.check(now=1e9)   # one beat, no duration yet


# ----------------------------------------------------------------------
# engine smoke run: the acceptance-criteria stream
# ----------------------------------------------------------------------
def test_engine_telemetry_smoke(tmp_path, mesh_1d):
    hidden = 16
    model = SimpleModel(hidden_dim=hidden)
    params = model.init(jax.random.key(0))
    cfg = base_config(0, telemetry={"enabled": True,
                                    "output_path": str(tmp_path),
                                    "job_name": "smoke"})
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=cfg)
    assert engine._tel_enabled and engine._watchdog is not None
    for s in range(3):
        engine.train_batch(batch=random_batch(32, hidden, seed=s))
    # the engine's jitted step has no dist.* verbs (XLA partitions the
    # collectives; the grad reduce lands via the trace-time census), so
    # drive one explicitly for the traced-verb path too
    import deepspeed_tpu.comm as dist
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    x = jax.numpy.ones((8, 4), jax.numpy.float32)
    sm = shard_map(lambda v: dist.all_reduce(v, group="fsdp"), mesh=mesh_1d,
                   in_specs=(P("fsdp", None),), out_specs=P("fsdp", None))
    jax.jit(sm)(x)
    engine._watchdog.stop()

    evs = _events(tmp_path / "smoke" / "events.jsonl")
    kinds = {e["kind"] for e in evs}
    assert {"span", "gauge", "comm", "heartbeat", "meta"} <= kinds
    spans = {e["name"] for e in evs if e["kind"] == "span"}
    assert "engine/train_batch" in spans
    gauges = {e["name"] for e in evs if e["kind"] == "gauge"}
    assert {"engine/loss", "engine/grad_norm",
            "engine/samples_per_sec"} <= gauges
    assert "Train/Samples/train_loss" in gauges  # MonitorMaster 4th writer
    comm = [e for e in evs if e["kind"] == "comm"]
    assert comm and all(e["name"] == "all_reduce" and e["bytes"] > 0
                        for e in comm)
    # the engine's trace-time grad-reduce census (XLA-inserted reduction,
    # no host duration) AND the explicitly traced verb (timed span)
    assert [e for e in comm if "dur_ms" not in e]
    assert [e for e in comm if "dur_ms" in e]
    beats = [e for e in evs if e["kind"] == "heartbeat"]
    assert [e["step"] for e in beats] == [1, 2, 3]
    # registry census rode along: >= 1 engine census + 1 explicit verb
    snap = get_telemetry().registry.snapshot()
    assert snap["counters"]["comm/all_reduce/calls"] >= 2


def test_engine_telemetry_disabled_by_default(tmp_path):
    model = SimpleModel(hidden_dim=16)
    params = model.init(jax.random.key(0))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=base_config(0))
    assert not engine._tel_enabled and engine._watchdog is None
    engine.train_batch(batch=random_batch(32, 16))
    assert not list(tmp_path.iterdir())  # nothing written anywhere


def test_report_cli_aggregates_smoke(tmp_path):
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "rep"}), rank=0)
    with tel.span("engine/step", step=1):
        pass
    tel.gauge("hbm/bytes_in_use", 1024.0, step=1)
    tel.comm("all_reduce", 4096, "dp")
    tel.emit("heartbeat", "engine/step", step=1, step_ms=12.5)
    tel.close()

    import importlib.util
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    spec = importlib.util.spec_from_file_location(
        "ds_telemetry_report",
        os.path.join(repo, "scripts", "ds_telemetry_report.py"))
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    files = rep.discover_files(str(tmp_path / "rep"))
    assert files
    summary = rep.summarize(rep.aggregate(rep.load_events(files)))
    assert summary["spans"]["engine/step"]["count"] == 1
    assert summary["comms"]["all_reduce"]["bytes"] == 4096
    assert summary["gauges"]["hbm/bytes_in_use"]["peak"] == 1024.0
    assert summary["heartbeat"] == {"steps": 1, "median_step_ms": 12.5}
    import io
    buf = io.StringIO()
    rep.print_tables(summary, out=buf)
    assert "engine/step" in buf.getvalue()
    assert "all_reduce" in buf.getvalue()


def test_report_tiered_memory_table(tmp_path):
    """tier/* gauges from a TieredStore land in the report's tiered
    summary (--json key ``tiered``) and its '== tiered memory ==' table,
    and every emitted event is schema-valid."""
    from deepspeed_tpu.monitor.telemetry import get_telemetry
    # the store publishes through the process-global telemetry
    tel = get_telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "tier"}), rank=0)
    from deepspeed_tpu.runtime.tiered_store import (PlacementPolicy,
                                                    TieredStore)
    store = TieredStore(name="t", nvme_dir=str(tmp_path / "nv"),
                        policy=PlacementPolicy(default_tier="nvme",
                                               quantize=True))
    store.put("w", np.random.default_rng(0).standard_normal(
        512).astype(np.float32))
    store.prefetch("w")
    store.fetch("w")
    store.publish_gauges()
    tel.close()

    import importlib.util
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    spec = importlib.util.spec_from_file_location(
        "ds_telemetry_report",
        os.path.join(repo, "scripts", "ds_telemetry_report.py"))
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    files = rep.discover_files(str(tmp_path / "tier"))
    summary = rep.summarize(rep.aggregate(rep.load_events(files)))
    tiered = summary["tiered"]
    assert tiered["gauges"]["nvme_bytes"]["last"] > 0
    assert tiered["prefetch_hit_rate"] == 1.0
    import io
    buf = io.StringIO()
    rep.print_tables(summary, out=buf)
    assert "== tiered memory ==" in buf.getvalue()
    assert "nvme_bytes" in buf.getvalue()

    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(repo, "scripts", "check_telemetry_schema.py"))
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    problems = []
    for f in files:
        with open(f) as fh:
            problems += list(checker.validate_stream(fh))
    assert not problems, problems
