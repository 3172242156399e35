"""Tiered-memory engine (``runtime/tiered_store.py``): a layer stack
more than four times over its HBM budget streams through the host and
NVMe tiers behind the schedule-driven prefetch engine, once per
placement (float32 and int8).  Every check is on bits, bytes or counts;
none reads a clock."""

import importlib.util
import json
import os

import numpy as np
import pytest

import deepspeed_tpu.monitor.telemetry as telemetry_mod
from deepspeed_tpu.monitor.telemetry import Telemetry
from deepspeed_tpu.runtime import resilience
from deepspeed_tpu.runtime.config import TelemetryConfig
from deepspeed_tpu.runtime.tiered_store import (PlacementPolicy,
                                                PrefetchEngine,
                                                TieredStore)

LAYERS, HIDDEN, PASSES = 16, 64, 3
LAYER_BYTES = HIDDEN * HIDDEN * 4
HBM_BUDGET = 3 * LAYER_BYTES


def _checker():
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(repo, "scripts", "check_telemetry_schema.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def streamed(tmp_path_factory):
    """Both placements after ``PASSES`` scheduled walks over the stack,
    with the ``tier/*`` gauges of both published to one event stream."""
    assert LAYERS * LAYER_BYTES > 4 * HBM_BUDGET
    tmp = str(tmp_path_factory.mktemp("tiered"))
    rng = np.random.default_rng(0)
    weights = [(rng.standard_normal((HIDDEN, HIDDEN)) / np.sqrt(HIDDEN))
               .astype(np.float32) for _ in range(LAYERS)]

    def run_store(name, quantize):
        store = TieredStore(
            name=name, nvme_dir=tmp,
            policy=PlacementPolicy(default_tier="nvme", quantize=quantize),
            hbm_budget_bytes=HBM_BUDGET)
        peak_hbm = 0
        for i, w in enumerate(weights):
            # alternate host/NVMe so both beyond-HBM tiers carry load
            store.put(f"L{i}", w, tier="host" if i % 2 else "nvme")
        store.commit()
        eng = PrefetchEngine(store, [[f"L{i}"] for i in range(LAYERS)],
                             depth=1)
        for _ in range(PASSES):
            for i in range(LAYERS):
                eng.access(i, device=True)
                peak_hbm = max(peak_hbm, store.tier_bytes()["hbm"])
        return store, peak_hbm

    # the store publishes through the process-global telemetry
    tel = Telemetry().configure(TelemetryConfig(
        {"enabled": True, "output_path": tmp, "job_name": "tiered"}))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(telemetry_mod, "_telemetry", tel)
        fp32, fp32_peak = run_store("fp32", quantize=False)
        int8, int8_peak = run_store("int8", quantize=True)
        # the walks only read; one writeback of the layer still staged
        # makes the device-to-host path carry bytes as well
        last = f"L{LAYERS - 1}"
        staged = np.asarray(fp32.fetch(last, device=True))
        stats = [fp32.stats(), int8.stats()]   # of the walks alone
        fp32.evict(last, writeback=staged)
        fp32.publish_gauges()
        int8.publish_gauges()
        tel.close()
    return {"weights": weights, "fp32": fp32, "int8": int8, "stats": stats,
            "peak_hbm": max(fp32_peak, int8_peak),
            "stream": os.path.join(tmp, "tiered", "events.jsonl")}


def test_float32_placement_round_trips_every_layer_bit_for_bit(streamed):
    for i, w in enumerate(streamed["weights"]):
        np.testing.assert_array_equal(streamed["fp32"].fetch(f"L{i}"), w)


def test_int8_placement_stays_in_the_codec_bound_and_halves_nvme(streamed):
    policy = streamed["int8"].policy
    for i, w in enumerate(streamed["weights"]):
        got = streamed["int8"].fetch(f"L{i}")
        blocks = np.abs(w).reshape(-1, policy.quant_block)
        bound = np.repeat(blocks.max(axis=1) / 127.0,
                          policy.quant_block).reshape(w.shape)
        assert np.all(np.abs(got - w) <= bound), f"L{i}"
    fp32_stats, int8_stats = streamed["stats"]
    assert 0 < int8_stats["nvme_bytes"] < 0.5 * fp32_stats["nvme_bytes"]


def test_hbm_working_set_never_passes_the_budget(streamed):
    assert 0 < streamed["peak_hbm"] <= HBM_BUDGET
    for stats in streamed["stats"]:
        assert stats["hbm_bytes"] <= HBM_BUDGET
        assert stats["evictions"] > 0


def test_scheduled_walk_hits_its_prefetches(streamed):
    for stats in streamed["stats"]:
        assert stats["prefetch_hits"] > stats["prefetch_misses"], stats


def test_sealed_tier_directories_fsck_committed(streamed):
    for store in (streamed["fp32"], streamed["int8"]):
        status, _ = store.validate()
        assert status == resilience.COMMITTED


def test_every_tier_gauge_is_emitted_and_the_stream_validates(streamed):
    checker = _checker()
    assert not checker.validate_file(streamed["stream"])
    with open(streamed["stream"]) as f:
        events = [json.loads(line) for line in f if line.strip()]
    emitted = {ev["name"] for ev in events if ev.get("kind") == "gauge"}
    assert set(checker.TIER_GAUGES) <= emitted, \
        set(checker.TIER_GAUGES) - emitted
