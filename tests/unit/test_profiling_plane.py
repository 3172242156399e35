"""Performance observability plane (monitor/profiling.py): compile
tracing with the recompile-storm verdict and watchdog exemption, per-span
HBM attribution with the monotonic-growth leak detector, the live
roofline gauges, and the exporter surfaces."""

import importlib.util
import json
import os
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu.monitor.profiling import (COMPILE_CAUSES, PROFILE_SPANS,
                                             CompileWatcher, HbmTracker,
                                             ProfilingPlane, diff_cause,
                                             fingerprint_call)
from deepspeed_tpu.monitor.telemetry import StepStallWatchdog, Telemetry
from deepspeed_tpu.runtime.config import TelemetryConfig

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _load_script(name):
    path = os.path.join(REPO, "scripts", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def checker():
    return _load_script("check_telemetry_schema")


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _tel(tmp_path, job="prof", **extra):
    raw = {"enabled": True, "output_path": str(tmp_path), "job_name": job,
           "profiling": {"enabled": True, "storm_threshold": 3,
                         "storm_window_s": 60.0}}
    raw.update(extra)
    return Telemetry().configure(TelemetryConfig(raw), rank=0)


def _events(tmp_path, job="prof"):
    with open(os.path.join(str(tmp_path), job, "events.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


# ----------------------------------------------------------------------
# compile tracing
# ----------------------------------------------------------------------
def test_fingerprint_and_cause_diff():
    a = fingerprint_call((np.zeros((2, 4), np.float32),))
    same = fingerprint_call((np.ones((2, 4), np.float32),))
    assert a == same                      # values don't matter, avals do
    wider = fingerprint_call((np.zeros((2, 8), np.float32),))
    cast = fingerprint_call((np.zeros((2, 4), np.int32),))
    extra = fingerprint_call((np.zeros((2, 4), np.float32), 3))
    assert diff_cause(None, a) == "cold"
    assert diff_cause(a, wider) == "new_shape"
    assert diff_cause(a, cast) == "new_dtype"
    assert diff_cause(a, extra) == "new_callable"
    assert diff_cause(a, a) == "new_static"
    for fp in (a, wider, cast, extra):
        assert diff_cause(a, fp) in COMPILE_CAUSES


def test_compile_watcher_miss_events_and_hot_path(tmp_path, checker):
    tel = _tel(tmp_path)
    clock = FakeClock()
    cw = CompileWatcher(tel, storm_threshold=99, clock=clock)
    calls = []
    fn = cw.wrap(lambda x: calls.append(1) or x.sum(), "unit/site",
                 step_fn=lambda: 7)
    fn(np.zeros((2, 4), np.float32))      # cold miss
    fn(np.ones((2, 4), np.float32))       # hot: same fingerprint
    fn(np.zeros((2, 8), np.float32))      # new_shape miss
    fn(np.zeros((2, 8), np.int32))        # new_dtype miss
    tel.close()
    assert len(calls) == 4                # wrapper always calls through
    assert cw.total_misses == 3
    assert cw.snapshot()["sites"] == {"unit/site": 3}
    evs = [e for e in _events(tmp_path) if e["kind"] == "compile"]
    assert [e["cause"] for e in evs] == ["cold", "new_shape", "new_dtype"]
    assert all(e["name"] == "compile/miss" and e["site"] == "unit/site"
               and e["step"] == 7 for e in evs)
    assert [e["count"] for e in evs] == [1, 2, 3]
    assert checker.validate_file(
        os.path.join(str(tmp_path), "prof", "events.jsonl")) == []


def test_storm_rising_edge_and_decay(tmp_path):
    tel = _tel(tmp_path)
    clock = FakeClock()
    cw = CompileWatcher(tel, storm_threshold=3, storm_window_s=60.0,
                        clock=clock)
    for i in range(5):                    # 5 misses in-window: one storm
        clock.t += 1.0
        cw.note_miss("s", ("fp", (((i,), "f32"),)), 0.5)
    assert cw.storm_active
    tel.close()
    storms = [e for e in _events(tmp_path) if e["name"] == "compile/storm"]
    assert len(storms) == 1               # rising edge only, not a flood
    assert storms[0]["site"] == "*" and storms[0]["count"] >= 3
    clock.t += 120.0                      # window slides past the churn
    assert not cw.storm_active
    assert cw.snapshot()["recent_misses"] == 0


def test_compile_secs_since_and_watchdog_exemption(tmp_path):
    """A step that recompiled may exceed the stall threshold by exactly
    its compile cost — the watchdog must subtract observed compile time
    instead of crying stall (satellite: FakeClock regression test).  The
    watchdog reads the compile account, which is always on; the profiling
    plane's ``CompileWatcher`` keeps its own ``compile_secs_since``."""
    from deepspeed_tpu.monitor import telemetry as telemetry_module
    tel = _tel(tmp_path)
    clock = FakeClock(1000.0)
    cw = CompileWatcher(tel, storm_threshold=99, clock=clock)
    wd = StepStallWatchdog(tel, stall_factor=1.0, min_stall_secs=0.0)
    wd.beat(0, now=1000.0)
    wd.beat(1, now=1001.0)
    wd.beat(2, now=1002.0)                # median step 1s, threshold 1s
    clock.t = 1003.0                      # recompile AFTER the last beat
    cw.note_miss("engine/train_step:1", ("fp", ()), 8.0)
    assert cw.compile_secs_since(1002.0) == pytest.approx(8.0)
    assert cw.compile_secs_since(1004.0) == 0.0
    # the same recompile as the account records it: 8 s that end at 1010
    account = telemetry_module._account
    compiled = {"t0_ns": int(1002e9), "t1_ns": int(1010e9), "name": "step",
                "trace_s": 1.0, "lower_s": 1.0, "backend_s": 6.0,
                "cache": "off", "site": "engine/train_step:1",
                "shapes": None, "repeat": True}
    account.records.append(compiled)
    try:
        # 8.5s gap, 8s of it compile: exempted -> no stall
        assert not wd.check(now=1010.5)
    finally:
        account.records.remove(compiled)
    # same gap with nothing compiled in it IS a stall
    wd2 = StepStallWatchdog(tel, stall_factor=1.0, min_stall_secs=0.0)
    wd2.beat(0, now=1000.0)
    wd2.beat(1, now=1001.0)
    wd2.beat(2, now=1002.0)
    assert wd2.check(now=1010.5)
    tel.close()


# ----------------------------------------------------------------------
# HBM attribution + leak detection
# ----------------------------------------------------------------------
def test_hbm_tracker_emits_span_gauges(tmp_path, checker):
    tel = _tel(tmp_path)
    stats = {"bytes_in_use": 1000.0, "peak_bytes_in_use": 1500.0}
    hbm = HbmTracker(tel, stats_fn=lambda: dict(stats))
    with hbm.track("fwd"):
        stats["bytes_in_use"] = 4000.0    # the span raises the peak
        stats["peak_bytes_in_use"] = 6000.0
    with hbm.track("not_a_span"):         # outside PROFILE_SPANS: no-op
        pass
    tel.close()
    gauges = {e["name"]: e for e in _events(tmp_path)
              if e["kind"] == "gauge"}
    assert gauges["mem/fwd/live_bytes"]["value"] == 4000.0
    assert gauges["mem/fwd/peak_bytes"]["value"] == 6000.0
    assert gauges["mem/fwd/frag_bytes"]["value"] == 2000.0  # peak - live
    assert not any(n.startswith("mem/not_a_span") for n in gauges)
    assert checker.validate_file(
        os.path.join(str(tmp_path), "prof", "events.jsonl")) == []


def test_hbm_tracker_quiet_without_allocator_stats(tmp_path):
    """CPU backends return no memory_stats(): every surface is a quiet
    no-op, never an exception or a garbage gauge."""
    tel = _tel(tmp_path)
    hbm = HbmTracker(tel, stats_fn=lambda: None)
    with hbm.track("fwd"):
        pass
    hbm.sample(0)
    assert hbm.leak_report() == {}
    tel.close()
    assert not [e for e in _events(tmp_path) if e["kind"] == "gauge"]


def test_hbm_leak_detector():
    live = {"v": 0.0}
    hbm = HbmTracker(Telemetry(), leak_window=4, min_growth_bytes=1000,
                     snapshot_interval=1,
                     stats_fn=lambda: {"bytes_in_use": live["v"]})
    for step, v in enumerate([100.0, 600.0, 1300.0, 2100.0]):
        live["v"] = v
        hbm.sample(step)
    rep = hbm.leak_report()
    assert rep["hbm_monotonic_growth"]["growth_bytes"] == 2000
    assert rep["hbm_monotonic_growth"]["from_step"] == 0
    assert rep["hbm_monotonic_growth"]["to_step"] == 3
    # one flat sample breaks the monotonic window -> clean
    hbm.sample(4)
    assert hbm.leak_report() == {}
    # growth below min_growth_bytes never flags
    small = HbmTracker(Telemetry(), leak_window=3, min_growth_bytes=10**9,
                       snapshot_interval=1,
                       stats_fn=lambda: {"bytes_in_use": 1.0})
    for step in range(3):
        small.stats_fn = (lambda s=step: {"bytes_in_use": 100.0 + s})
        small.sample(step)
    assert small.leak_report() == {}


def test_hbm_sample_respects_snapshot_interval():
    seen = []
    hbm = HbmTracker(Telemetry(), snapshot_interval=4,
                     stats_fn=lambda: seen.append(1) or
                     {"bytes_in_use": 1.0})
    for step in range(9):
        hbm.sample(step)
    assert len(seen) == 3                 # steps 0, 4, 8


# ----------------------------------------------------------------------
# live roofline
# ----------------------------------------------------------------------
def test_roofline_gauges_with_explicit_peaks(tmp_path):
    tel = _tel(tmp_path)
    plane = ProfilingPlane(tel, peak_hbm_gbps=100.0)
    plane.roofline("train_batch", 0.5, flops=1e12, bytes_moved=1e10,
                   peak_flops=1e13, step=3)
    tel.close()
    gauges = {e["name"]: e for e in _events(tmp_path)
              if e["kind"] == "gauge"}
    cf = gauges["roofline/train_batch/compute_frac"]
    bf = gauges["roofline/train_batch/bandwidth_frac"]
    assert cf["value"] == pytest.approx(0.2)   # (1e12/0.5)/1e13
    assert bf["value"] == pytest.approx(0.2)   # (1e10/0.5)/1e11
    assert cf["step"] == 3


def test_roofline_silent_without_peaks(tmp_path):
    """CPU run, no override, no analytic flops: no garbage fractions."""
    tel = _tel(tmp_path)
    plane = ProfilingPlane(tel, peak_hbm_gbps=0.0)
    plane.roofline("train_batch", 0.5, flops=1e12, bytes_moved=None,
                   peak_flops=None)
    plane.roofline("warmup", 0.5, flops=1e12, peak_flops=1e13)  # bad span
    plane.roofline("train_batch", 0.0, flops=1e12, peak_flops=1e13)
    tel.close()
    assert not [e for e in _events(tmp_path)
                if e["kind"] == "gauge"
                and e["name"].startswith("roofline/")]


# ----------------------------------------------------------------------
# exporter surfaces: /metrics, /metrics.json, /healthz
# ----------------------------------------------------------------------
def test_exporter_surfaces_profiling_gauges_with_rank_labels(tmp_path):
    tel = _tel(tmp_path, distributed={"enabled": True},
               export={"enabled": True, "port": 0})
    assert tel.profiling is not None and tel.exporter is not None
    host, port = tel.exporter.address
    base = f"http://{host}:{port}"
    stats = {"bytes_in_use": 1024.0, "peak_bytes_in_use": 2048.0}
    tel.profiling.hbm.stats_fn = lambda: dict(stats)
    with tel.profiling.track("serve_step"):
        stats["bytes_in_use"] = 2048.0    # span raises the process peak
        stats["peak_bytes_in_use"] = 4096.0
    tel.profiling.peak_hbm_gbps = 100.0
    tel.profiling.roofline("serve_step", 0.1, flops=1e9, bytes_moved=1e8,
                           peak_flops=1e12, step=1)
    tel.profiling.compiles.note_miss("serve/step_fn", ("fp", ()), 0.25)
    prom = urllib.request.urlopen(base + "/metrics").read().decode()
    assert 'ds_mem_serve_step_live_bytes{rank="0"} 2048' in prom
    assert 'ds_roofline_serve_step_compute_frac{rank="0"} 0.01' in prom
    assert 'ds_roofline_serve_step_bandwidth_frac{rank="0"}' in prom
    assert 'ds_compile_misses{rank="0"} 1' in prom
    assert 'ds_compile_storm_active{rank="0"} 0' in prom
    snap = json.loads(
        urllib.request.urlopen(base + "/metrics.json").read())
    assert snap["gauges"]["mem/serve_step/peak_bytes"]["value"] == 4096.0
    assert "roofline/serve_step/bandwidth_frac" in snap["gauges"]
    hz = json.loads(urllib.request.urlopen(base + "/healthz").read())
    assert hz["ok"] is True and hz["recompile_storm"] is False
    tel.close()


def test_profile_spans_cover_engine_and_serving():
    """The frozen span vocabulary must keep covering both planes' track
    sites (engine fwd/bwd/step/train_batch, serving serve_step/prefill)."""
    for span in ("fwd", "bwd", "step", "train_batch", "serve_step",
                 "prefill"):
        assert span in PROFILE_SPANS
