"""Distributed observability plane tests: per-rank telemetry shards,
collective-level comm tracing, cross-rank skew/straggler detection, and
the rank-labelled exporter surface.

Multi-rank behavior is exercised on CPU with the simulated-multiprocess
idiom: N threads, each owning its own :class:`Telemetry` instance
configured with a distinct rank, write distinct ``events.rank{N}.jsonl``
shards into one directory — exactly the layout N real processes produce —
and the aggregation/validation path runs over the result."""

import importlib.util
import json
import os
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.comm import COMM_OPS, _payload
from deepspeed_tpu.monitor import (ClusterAggregator, Telemetry,
                                   aggregate_cluster, aggregate_shards,
                                   discover_shards, get_telemetry)
from deepspeed_tpu.monitor.telemetry import StepStallWatchdog
from deepspeed_tpu.runtime.config import TelemetryConfig
from unit.simple_model import SimpleModel, base_config, random_batch


@pytest.fixture(autouse=True)
def _reset_telemetry():
    yield
    tel = get_telemetry()
    tel.close()
    tel.registry.reset()
    tel.config = None


def _load_checker():
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    path = os.path.join(repo, "scripts", "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("check_telemetry_schema",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def checker():
    return _load_checker()


def _dist_cfg(tmp_path, **overrides):
    dist = {"enabled": True, "skew_threshold": 2.0, "straggler_window": 16}
    dist.update(overrides.pop("distributed", {}))
    raw = {"enabled": True, "output_path": str(tmp_path),
           "job_name": "dist", "distributed": dist}
    raw.update(overrides)
    return TelemetryConfig(raw)


def _events(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ----------------------------------------------------------------------
# the simulated 4-rank fixture: shards -> aggregation -> verdicts
# ----------------------------------------------------------------------
N_RANKS = 4
STEPS = 6
STEP_MS = 50.0
STRAGGLER_MS = 200.0          # rank 3: 4x the cluster median (> 2.0x)
COMM_BYTES = 1 << 20
COMM_DUR_MS = 4.0
COMMS_PER_RANK = 5


def _run_rank(tmp_path, rank, straggle):
    """One simulated process: its own Telemetry, its own shard."""
    tel = Telemetry().configure(_dist_cfg(tmp_path), rank=rank)
    for step in range(1, STEPS + 1):
        ms = STRAGGLER_MS if straggle and rank == N_RANKS - 1 else STEP_MS
        tel.emit("heartbeat", "engine/heartbeat", step=step, step_ms=ms)
    for _ in range(COMMS_PER_RANK):
        tel.collective("all_gather", COMM_BYTES, "fsdp", dtype="float32",
                       dur_ms=COMM_DUR_MS, world=N_RANKS)
    tel.close()


def _run_cluster(tmp_path, straggle):
    threads = [threading.Thread(target=_run_rank,
                                args=(tmp_path, r, straggle))
               for r in range(N_RANKS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return os.path.join(str(tmp_path), "dist")


def test_four_rank_acceptance(tmp_path, checker):
    """The PR's acceptance fixture: 4 simulated ranks, per-collective
    achieved bandwidth within 1% of hand-computed bytes/duration, the
    injected straggler flagged, and every shard checker-valid."""
    shard_dir = _run_cluster(tmp_path, straggle=True)
    shards = discover_shards(shard_dir)
    assert sorted(shards) == list(range(N_RANKS))
    for rank, files in shards.items():
        for ev in _events(files[-1]):
            assert ev["rank"] == rank

    snap = aggregate_shards(shard_dir)
    assert snap["ranks"] == list(range(N_RANKS))
    assert snap["missing_ranks"] == [] and snap["torn_lines"] == 0
    assert snap["steps"]["aligned"] == STEPS

    # achieved bandwidth within 1% of hand-computed bytes/duration
    row = snap["collectives"]["all_gather"]
    timed = N_RANKS * COMMS_PER_RANK
    assert row["calls"] == timed and row["timed_calls"] == timed
    expect = (timed * COMM_BYTES) / (timed * COMM_DUR_MS / 1e3) / 1e9
    assert row["achieved_gbps"] == pytest.approx(expect, rel=0.01)
    # bus bandwidth applies the nccl-tests (n-1)/n all_gather factor
    assert row["busbw_gbps"] == pytest.approx(
        expect * (N_RANKS - 1) / N_RANKS, rel=0.01)
    assert row["world"] == N_RANKS

    # injected straggler flagged on the step-time metric
    assert snap["straggler"]["rank"] == N_RANKS - 1
    assert snap["straggler"]["metric"] == "step_time"

    # shards and payload pass the frozen-schema checker
    problems, n = checker.validate_shard_dir(shard_dir)
    assert problems == [] and n == N_RANKS
    assert checker.validate_cluster_payload(snap) == []


def test_zero_skew_no_false_positive(tmp_path):
    shard_dir = _run_cluster(tmp_path, straggle=False)
    snap = aggregate_shards(shard_dir)
    assert snap["straggler"]["rank"] is None
    assert snap["straggler"]["metric"] is None
    assert snap["step_skew"]["max_spread_ms"] == 0.0


def test_collective_entry_straggler(tmp_path):
    """A rank whose step times match but who arrives late at every
    collective is flagged on the collective_entry metric."""
    events = {}
    for rank in range(2):
        evs = [{"ts": 100.0 + s, "kind": "heartbeat", "name": "hb",
                "step": s, "step_ms": 10.0, "rank": rank}
               for s in range(8)]
        delay = 0.5 if rank == 1 else 0.0   # 500 ms late, median step 10 ms
        evs += [{"ts": 200.0 + k + delay, "kind": "comm",
                 "name": "all_reduce", "bytes": 1024, "axis": "dp",
                 "rank": rank} for k in range(4)]
        events[rank] = evs
    snap = aggregate_cluster(events, skew_threshold=2.0)
    assert snap["straggler"]["rank"] == 1
    assert snap["straggler"]["metric"] == "collective_entry"


# ----------------------------------------------------------------------
# shard-aggregation edge cases
# ----------------------------------------------------------------------
def _write_shard(shard_dir, rank, events):
    os.makedirs(shard_dir, exist_ok=True)
    path = os.path.join(shard_dir, f"events.rank{rank}.jsonl")
    with open(path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")
    return path


def _hb(rank, step, ms=10.0):
    return {"ts": 100.0 + step, "kind": "heartbeat", "name": "hb",
            "step": step, "step_ms": ms, "rank": rank}


def test_missing_rank_shard(tmp_path):
    d = str(tmp_path)
    for rank in (0, 1, 3):   # rank 2 never wrote (dead process)
        _write_shard(d, rank, [_hb(rank, s) for s in range(4)])
    snap = aggregate_shards(d)
    assert snap["ranks"] == [0, 1, 3]
    assert snap["missing_ranks"] == [2]
    assert snap["straggler"]["rank"] is None


def test_torn_last_line_tolerated(tmp_path):
    d = str(tmp_path)
    path = _write_shard(d, 0, [_hb(0, s) for s in range(4)])
    with open(path, "a") as f:
        f.write('{"ts": 104.0, "kind": "heartb')   # live writer mid-flush
    _write_shard(d, 1, [_hb(1, s) for s in range(4)])
    snap = aggregate_shards(d)
    assert snap["torn_lines"] == 1
    assert snap["steps"]["aligned"] == 4           # intact records survive


def test_out_of_order_steps(tmp_path):
    """Replayed/reordered streams collapse by step number: aggregation
    aligns on step ids, and the LAST record per step wins."""
    d = str(tmp_path)
    _write_shard(d, 0, [_hb(0, s) for s in (3, 1, 0, 2)])
    _write_shard(d, 1, [_hb(1, 2), _hb(1, 0), _hb(1, 1), _hb(1, 3),
                        _hb(1, 3, ms=99.0)])       # rewrite of step 3 wins
    snap = aggregate_shards(d)
    assert snap["steps"]["aligned"] == 4
    assert snap["straggler"]["per_rank"]["1"]["steps"] == 4
    spread = snap["step_skew"]["max_spread_ms"]
    assert spread == pytest.approx(89.0)           # 99 - 10 at step 3


def test_single_rank_degenerate_matches_legacy(tmp_path):
    """One legacy events.jsonl (no distributed block) aggregates to the
    PR 1 single-stream view: rank 0, zero spreads, no verdict."""
    d = str(tmp_path)
    with open(os.path.join(d, "events.jsonl"), "w") as f:
        for s in range(5):
            ev = _hb(0, s)
            del ev["rank"]                          # legacy: no stamps
            f.write(json.dumps(ev) + "\n")
    shards = discover_shards(d)
    assert list(shards) == [0]
    snap = aggregate_shards(d)
    assert snap["ranks"] == [0] and snap["missing_ranks"] == []
    assert snap["steps"]["count"] == 5 and snap["steps"]["aligned"] == 5
    assert snap["steps"]["median_step_ms"] == 10.0
    assert snap["step_skew"]["max_spread_ms"] is None
    assert snap["straggler"]["rank"] is None


def test_aggregator_pushes_frozen_gauges(tmp_path):
    from deepspeed_tpu.monitor import CLUSTER_GAUGES, MetricsRegistry
    d = str(tmp_path)
    _write_shard(d, 0, [_hb(0, s) for s in range(4)])
    _write_shard(d, 1, [_hb(1, s, ms=50.0) for s in range(4)])
    reg = MetricsRegistry()
    agg = ClusterAggregator(d, skew_threshold=2.0, registry=reg,
                            min_refresh_secs=0.0)
    snap = agg.snapshot()
    assert snap["straggler"]["rank"] == 1
    gauges = reg.snapshot()["gauges"]
    for name in CLUSTER_GAUGES:
        assert name in gauges
    assert gauges["cluster/straggler_rank"]["value"] == 1
    assert gauges["cluster/step_skew_ms"]["value"] == pytest.approx(40.0)


def test_aggregator_rate_limits_refresh(tmp_path):
    d = str(tmp_path)
    _write_shard(d, 0, [_hb(0, 0)])
    agg = ClusterAggregator(d, min_refresh_secs=3600.0)
    first = agg.snapshot()
    _write_shard(d, 0, [_hb(0, s) for s in range(4)])
    assert agg.snapshot() is first                 # cached within window
    assert agg.refresh(force=True)["steps"]["count"] == 4


# ----------------------------------------------------------------------
# distributed Telemetry wiring: shards, stamps, exporter, watchdog
# ----------------------------------------------------------------------
def test_distributed_mode_all_ranks_write(tmp_path):
    """With the distributed block on, the rank-0 gate is lifted: every
    rank writes its own shard and stamps each record."""
    for rank in range(2):
        tel = Telemetry().configure(_dist_cfg(tmp_path), rank=rank)
        assert tel._stamp_rank
        tel.gauge("engine/loss", 0.5, step=1)
        tel.close()
    for rank in range(2):
        path = tmp_path / "dist" / f"events.rank{rank}.jsonl"
        (ev,) = _events(path)
        assert ev["rank"] == rank and ev["name"] == "engine/loss"


def test_nondistributed_mode_unchanged(tmp_path):
    """Without the block, PR 1 behavior is byte-identical: rank 0 writes
    events.jsonl with no rank stamps; other ranks write nothing."""
    cfg = TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                           "job_name": "plain"})
    tel = Telemetry().configure(cfg, rank=0)
    assert not tel._stamp_rank and tel.cluster is None
    tel.gauge("engine/loss", 0.5, step=1)
    tel.close()
    (ev,) = _events(tmp_path / "plain" / "events.jsonl")
    assert "rank" not in ev
    tel1 = Telemetry().configure(cfg, rank=1)
    assert tel1.sink is None
    tel1.close()


def test_rank0_owns_cluster_aggregator(tmp_path):
    tel0 = Telemetry().configure(_dist_cfg(tmp_path), rank=0)
    tel1 = Telemetry().configure(_dist_cfg(tmp_path), rank=1)
    assert tel0.cluster is not None and tel1.cluster is None
    assert tel0.cluster.skew_threshold == 2.0
    assert tel0.cluster.straggler_window == 16
    tel0.close()
    tel1.close()
    assert tel0.cluster is None                    # close() drops it


def test_exporter_rank_labels_and_cluster_endpoint(tmp_path, checker):
    cfg = _dist_cfg(tmp_path, export={"enabled": True, "port": 0})
    tel0 = Telemetry().configure(cfg, rank=0)
    tel1 = Telemetry().configure(cfg, rank=1)
    for tel in (tel0, tel1):
        tel.emit("heartbeat", "engine/heartbeat", step=1, step_ms=10.0)
        tel.collective("all_reduce", 4096, "dp", dtype="float32",
                       dur_ms=1.0, world=2)
    host, port = tel0.exporter.address
    prom = urllib.request.urlopen(
        f"http://{host}:{port}/metrics", timeout=10).read().decode()
    assert checker.validate_prom_exposition(prom) == []
    assert 'rank="0"' in prom
    assert 'ds_comm_all_reduce_ms{quantile="0.5",rank="0"}' in prom
    cluster = json.loads(urllib.request.urlopen(
        f"http://{host}:{port}/cluster", timeout=10).read())
    assert checker.validate_cluster_payload(cluster) == []
    assert cluster["ranks"] == [0, 1]
    tel0.close()
    tel1.close()


def test_cluster_endpoint_404_without_aggregator(tmp_path):
    cfg = TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                           "job_name": "plain",
                           "export": {"enabled": True, "port": 0}})
    tel = Telemetry().configure(cfg, rank=0)
    host, port = tel.exporter.address
    with pytest.raises(urllib.request.HTTPError) as e:
        urllib.request.urlopen(f"http://{host}:{port}/cluster", timeout=10)
    assert e.value.code == 404
    tel.close()


def test_watchdog_cluster_sweep(tmp_path):
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "wd"}), rank=0)

    class FakeCluster:
        calls = 0

        def snapshot(self):
            self.calls += 1
            return {"straggler": {"rank": 2, "metric": "step_time",
                                  "threshold": 2.0}}

    fake = FakeCluster()
    wd = StepStallWatchdog(tel, cluster=fake, cluster_poll_secs=3600.0)
    assert wd.check_cluster(now=0.0) == 2
    # rate-limited: a poll inside the window reuses the last verdict
    assert wd.check_cluster(now=1.0) == 2
    assert fake.calls == 1
    tel.close()
    evs = _events(tmp_path / "wd" / "events.jsonl")
    flagged = [e for e in evs if e["kind"] == "meta"
               and e["name"] == "cluster/straggler"]
    assert len(flagged) == 1                        # one event per verdict
    assert flagged[0]["attrs"]["rank"] == 2

    wd_off = StepStallWatchdog(Telemetry())
    assert wd_off.check_cluster() is None           # no cluster: no-op


# ----------------------------------------------------------------------
# comm tracing: dtype-true payloads, timed spans, config validation
# ----------------------------------------------------------------------
def test_payload_is_dtype_true():
    """The byte accounting regression: payload size must be
    size * itemsize at the ACTUAL dtype, never an element count."""
    x8 = np.zeros((16, 4), dtype=np.int8)
    x32 = np.zeros((16, 4), dtype=np.float32)
    assert _payload(x8) == (64, "int8")
    assert _payload(x32) == (256, "float32")
    assert _payload(np.float32(1.0))[0] == 4        # np scalars coerce
    assert _payload(3.0)[0] == 8                    # python floats too


def test_collective_registry_and_event(tmp_path):
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "coll"}), rank=0)
    tel.collective("reduce_scatter", 1 << 20, "fsdp", dtype="bfloat16",
                   dur_ms=2.0, world=4)
    snap = tel.registry.snapshot()
    assert snap["counters"]["comm/reduce_scatter/calls"] == 1
    assert snap["counters"]["comm/reduce_scatter/bytes"] == 1 << 20
    assert snap["histograms"]["comm/reduce_scatter_ms"]["count"] == 1
    # algbw = 1 MiB / 2 ms; busbw applies the (n-1)/n reduce_scatter factor
    algbw = (1 << 20) / (2.0 / 1e3) / 1e9
    assert snap["gauges"]["comm/reduce_scatter/busbw_gbps"]["value"] == \
        pytest.approx(algbw * 3 / 4, rel=1e-3)
    tel.close()
    (ev,) = _events(tmp_path / "coll" / "events.jsonl")
    assert ev["kind"] == "comm" and ev["name"] == "reduce_scatter"
    assert ev["bytes"] == 1 << 20 and ev["dtype"] == "bfloat16"
    assert ev["dur_ms"] == 2.0 and ev["world"] == 4
    assert ev["busbw_gbps"] == pytest.approx(algbw * 3 / 4, rel=1e-3)


def test_traced_verb_records_duration(tmp_path, mesh_1d):
    # the verbs log through the process-global telemetry
    tel = get_telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "verb"}), rank=0)
    import deepspeed_tpu.comm as dist
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    x = jax.numpy.ones((8, 4), jax.numpy.float32)
    sm = shard_map(lambda v: dist.all_reduce(v, group="fsdp"),
                   mesh=mesh_1d, in_specs=(P("fsdp", None),),
                   out_specs=P("fsdp", None))
    jax.jit(sm)(x)
    dist.barrier()
    tel.close()
    evs = _events(tmp_path / "verb" / "events.jsonl")
    ar = [e for e in evs if e["name"] == "all_reduce"]
    assert ar and ar[0]["dur_ms"] > 0 and ar[0]["dtype"] == "float32"
    assert ar[0]["world"] == mesh_1d.devices.size
    bar = [e for e in evs if e["name"] == "barrier"]
    assert bar and bar[0]["dur_ms"] >= 0 and bar[0]["bytes"] == 0
    assert all(e["name"] in COMM_OPS for e in evs if e["kind"] == "comm")


def test_distributed_config_validation():
    with pytest.raises(ValueError):
        TelemetryConfig({"enabled": True,
                         "distributed": {"enabled": True,
                                         "skew_threshold": 1.0}})
    with pytest.raises(ValueError):
        TelemetryConfig({"enabled": True,
                         "distributed": {"enabled": True,
                                         "straggler_window": 0}})
    cfg = TelemetryConfig({"enabled": True,
                           "distributed": {"enabled": True,
                                           "shard_dir": "/tmp/x",
                                           "skew_threshold": 3.0}})
    assert cfg.distributed.enabled and cfg.distributed.shard_dir == "/tmp/x"


# ----------------------------------------------------------------------
# engine integration: grad-reduce census + MFU gauge
# ----------------------------------------------------------------------
def test_engine_grad_census_dtype_true_bytes(tmp_path):
    """The ZeRO grad reduce is an XLA-inserted collective (no dist.* call);
    the engine's trace-time census must still account its bytes — at the
    grad tree's TRUE dtypes."""
    from deepspeed_tpu.parallel import groups
    hidden = 16
    model = SimpleModel(hidden_dim=hidden)
    params = model.init(jax.random.key(0))
    cfg = base_config(0, telemetry={"enabled": True,
                                    "output_path": str(tmp_path),
                                    "job_name": "census",
                                    "stall_watchdog": False})
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=cfg)
    engine.train_batch(batch=random_batch(32, hidden, seed=0))
    dp_world = groups.get_data_parallel_world_size()
    expect_bytes = sum(
        int(np.prod(p.shape)) * np.dtype(p.dtype).itemsize
        for p in jax.tree_util.tree_leaves(engine.state.params))
    get_telemetry().close()
    evs = _events(tmp_path / "census" / "events.jsonl")
    census = [e for e in evs if e["kind"] == "comm" and "dur_ms" not in e]
    if dp_world <= 1:
        assert census == []                         # gated: no DP, no comm
        return
    assert census and census[0]["name"] == "all_reduce"   # stage 0
    assert census[0]["bytes"] == expect_bytes
    assert census[0]["world"] == dp_world
    assert census[0]["axis"] == "fsdp"


def test_engine_mfu_gauge(tmp_path):
    """train/mfu rides each profiled step: analytic flops from the flops
    profiler over measured step time, against the configured peak (the
    peak_tflops knob makes this computable on CPU)."""
    hidden = 16
    model = SimpleModel(hidden_dim=hidden)
    params = model.init(jax.random.key(0))
    cfg = base_config(0, telemetry={"enabled": True,
                                    "output_path": str(tmp_path),
                                    "job_name": "mfu",
                                    "stall_watchdog": False},
                      flops_profiler={"enabled": True, "profile_step": 1,
                                      "detailed": False,
                                      "peak_tflops": 0.001})
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=cfg)
    for s in range(3):
        engine.train_batch(batch=random_batch(32, hidden, seed=s))
    assert engine._analytic_step_flops and engine._analytic_step_flops > 0
    assert engine._mfu_peak_flops == pytest.approx(
        0.001 * 1e12 * jax.device_count())
    get_telemetry().close()
    evs = _events(tmp_path / "mfu" / "events.jsonl")
    mfu = [e for e in evs if e["kind"] == "gauge"
           and e["name"] == "train/mfu"]
    flops = [e for e in evs if e["kind"] == "gauge"
             and e["name"] == "train/model_flops_per_sec"]
    assert mfu and flops
    assert all(e["value"] > 0 for e in mfu)
    # MFU is flops-rate over peak, so the two gauges must agree
    assert mfu[-1]["value"] == pytest.approx(
        flops[-1]["value"] / engine._mfu_peak_flops, rel=1e-6)


# ----------------------------------------------------------------------
# report script over shards
# ----------------------------------------------------------------------
def test_report_aggregates_rank_shards(tmp_path):
    shard_dir = _run_cluster(tmp_path, straggle=True)
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    spec = importlib.util.spec_from_file_location(
        "ds_telemetry_report",
        os.path.join(repo, "scripts", "ds_telemetry_report.py"))
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    files = rep.discover_files(shard_dir)
    assert len(files) == N_RANKS
    summary = rep.summarize(rep.aggregate(rep.load_events(files)))
    row = summary["comms"]["all_gather"]
    assert row["calls"] == N_RANKS * COMMS_PER_RANK
    expect = COMM_BYTES / (COMM_DUR_MS / 1e3) / 1e9
    assert row["achieved_gbps"] == pytest.approx(expect, rel=0.01)
    cl = summary["cluster"]
    assert cl["ranks"] == N_RANKS
    assert cl["per_rank"][str(N_RANKS - 1)]["median_step_ms"] == \
        pytest.approx(STRAGGLER_MS)
    assert cl["step_skew_ms"]["max"] == pytest.approx(
        STRAGGLER_MS - STEP_MS)
    assert cl["worst_rel"] == pytest.approx(STRAGGLER_MS / STEP_MS)
    import io
    buf = io.StringIO()
    rep.print_tables(summary, out=buf)
    out = buf.getvalue()
    assert "cluster (4 ranks" in out and "slowest rank vs median" in out
