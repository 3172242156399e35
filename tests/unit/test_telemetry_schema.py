"""Frozen-schema enforcement for the telemetry JSONL event stream.

Every event the telemetry spine can emit must validate against
``scripts/check_telemetry_schema.py``, and the script's kind set must stay
in lockstep with ``deepspeed_tpu.monitor.telemetry.EVENT_KINDS`` — the
stream is a contract, so drift fails tier-1."""

import importlib.util
import os

import pytest

from deepspeed_tpu.monitor.telemetry import (EVENT_KINDS, StepStallWatchdog,
                                             Telemetry)
from deepspeed_tpu.runtime.config import TelemetryConfig


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


def test_kind_sets_in_lockstep(checker):
    assert set(checker.EVENT_KINDS) == set(EVENT_KINDS)


def test_serve_event_names_in_lockstep(checker):
    """The frozen serve-name vocabulary must stay byte-identical between
    the engine side (inference/robustness.py) and the checker script."""
    from deepspeed_tpu.inference.robustness import SERVE_EVENTS
    assert checker.SERVE_EVENTS == SERVE_EVENTS


def test_fleet_event_names_in_lockstep(checker):
    """The frozen fleet-name vocabulary must stay byte-identical between
    the router side (inference/fleet.py) and the checker script."""
    from deepspeed_tpu.inference.fleet import FLEET_EVENTS
    assert checker.FLEET_EVENTS == FLEET_EVENTS


def test_rejects_unknown_fleet_name(checker):
    assert checker.validate_event(
        {"ts": 1.0, "kind": "fleet", "name": "fleet/not_a_thing"})
    assert not checker.validate_event(
        {"ts": 1.0, "kind": "fleet", "name": "fleet/kill",
         "attrs": {"replica": "r1", "epoch": "r1g0"}, "step": 3})


def test_fleet_gauges_in_lockstep(checker):
    """The frozen fleet-gauge vocabulary must stay byte-identical between
    the router side (inference/fleet.py) and the checker script."""
    from deepspeed_tpu.inference.fleet import FLEET_GAUGES
    assert checker.FLEET_GAUGES == FLEET_GAUGES


def test_rejects_unknown_fleet_gauge(checker):
    assert checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "fleet/not_a_gauge",
         "value": 1.0, "peak": 1.0, "step": 3})
    assert not checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "fleet/breaker_open_replicas",
         "value": 1.0, "peak": 1.0, "step": 3})


def test_comm_ops_in_lockstep(checker):
    """The frozen collective-name vocabulary must stay byte-identical
    between the engine side (comm/comm.py) and the checker script."""
    from deepspeed_tpu.comm.comm import COMM_OPS
    assert checker.COMM_OPS == COMM_OPS


def test_quant_gauges_in_lockstep(checker):
    """The frozen comm/*/quant_bytes_saved gauge vocabulary must stay
    byte-identical between the codec (comm/quantize.py) and the checker."""
    from deepspeed_tpu.comm.quantize import QUANT_GAUGES
    assert checker.QUANT_GAUGES == QUANT_GAUGES


def test_overlap_gauges_in_lockstep(checker):
    """The frozen comm/overlap/* gauge vocabulary must stay byte-identical
    between the overlap plan (runtime/zero/stage_plan.py) and the
    checker."""
    from deepspeed_tpu.runtime.zero.stage_plan import OVERLAP_GAUGES
    assert checker.OVERLAP_GAUGES == OVERLAP_GAUGES


def test_overlap_gauge_validation(checker):
    # comm/overlap/ gauges ride their own frozen vocabulary; other comm/
    # gauges stay on QUANT_GAUGES
    assert not checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "comm/overlap/exposed_ms",
         "value": 0.4, "peak": 0.4})
    assert not checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "comm/overlap/rs_buckets",
         "value": 3.0, "peak": 3.0})
    assert checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "comm/overlap/vibes",
         "value": 1.0, "peak": 1.0})


def test_tier_gauges_in_lockstep(checker):
    """The frozen tier/* gauge vocabulary must stay byte-identical
    between the tiered-memory engine (runtime/tiered_store.py) and the
    checker."""
    from deepspeed_tpu.runtime.tiered_store import TIER_GAUGES
    assert checker.TIER_GAUGES == TIER_GAUGES


def test_tier_gauge_validation(checker):
    assert not checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "tier/nvme_bytes",
         "value": 4096.0, "peak": 4096.0})
    assert not checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "tier/prefetch_hits",
         "value": 7.0, "peak": 7.0})
    assert checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "tier/vibes",
         "value": 1.0, "peak": 1.0})


def test_cluster_gauges_in_lockstep(checker):
    """The frozen cluster/* gauge vocabulary must stay byte-identical
    between the aggregator (monitor/aggregate.py) and the checker."""
    from deepspeed_tpu.monitor.aggregate import CLUSTER_GAUGES
    assert checker.CLUSTER_GAUGES == CLUSTER_GAUGES


def test_rejects_unknown_comm_and_cluster_names(checker):
    assert checker.validate_event(
        {"ts": 1.0, "kind": "comm", "name": "gossip", "bytes": 4,
         "axis": "dp"})
    assert not checker.validate_event(
        {"ts": 1.0, "kind": "comm", "name": "all_gather", "bytes": 4,
         "axis": "dp", "dtype": "float32", "dur_ms": 1.5, "world": 4,
         "busbw_gbps": 0.75, "peak_gbps": 100.0, "rank": 2})
    # quantized-collective annotations: wire_dtype + bytes_saved are
    # optional on every comm record; wrong types are rejected
    assert not checker.validate_event(
        {"ts": 1.0, "kind": "comm", "name": "reduce_scatter",
         "bytes": 1056, "axis": "fsdp", "dtype": "float32", "world": 4,
         "wire_dtype": "int8", "bytes_saved": 3040})
    assert checker.validate_event(
        {"ts": 1.0, "kind": "comm", "name": "reduce_scatter",
         "bytes": 1056, "axis": "fsdp", "bytes_saved": "3040"})
    # comm/ gauges are validated against the frozen QUANT_GAUGES tuple
    assert not checker.validate_event(
        {"ts": 1.0, "kind": "gauge",
         "name": "comm/all_reduce/quant_bytes_saved", "value": 3040.0,
         "peak": 3040.0})
    assert checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "comm/all_reduce/vibes",
         "value": 1.0, "peak": 1.0})
    assert checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "cluster/bogus", "value": 1.0,
         "peak": 1.0})
    assert not checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "cluster/step_skew_ms",
         "value": 1.0, "peak": 1.0, "rank": 0})


def test_rejects_unknown_serve_name(checker):
    assert checker.validate_event(
        {"ts": 1.0, "kind": "serve", "name": "serve/not_a_thing"})
    assert not checker.validate_event(
        {"ts": 1.0, "kind": "serve", "name": "serve/prefix_hit"})


def test_rejects_unknown_kind_and_fields(checker):
    assert checker.validate_event({"ts": 1.0, "kind": "bogus", "name": "x"})
    assert checker.validate_event(
        {"ts": 1.0, "kind": "span", "name": "x", "dur_ms": 1.0,
         "surprise": 1})
    assert checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "x"})  # missing value/peak
    assert checker.validate_event(
        {"ts": 1.0, "kind": "comm", "name": "x", "bytes": "4",
         "axis": "dp"})  # wrong type
    assert checker.validate_event([1, 2])  # not an object


def test_accepts_every_emitter(checker, tmp_path):
    """Drive every emit path in the telemetry module and validate the
    resulting stream line-by-line — the live emitters ARE the schema."""
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "schema"}), rank=0)
    with tel.span("engine/step", step=1, attrs={"zero_stage": 2}):
        pass
    with tel.span("checkpoint/save"):
        pass
    tel.gauge("hbm/bytes_in_use", 123456.0, step=1)
    tel.gauge("engine/loss", 0.5)
    tel.comm("all_reduce", 1 << 20, "dp")
    # the fully-annotated collective-tracing record (comm tracing)
    tel.collective("reduce_scatter", 1 << 20, "fsdp", dtype="bfloat16",
                   dur_ms=2.5, world=4)
    # ...and its quantized twin (comm/quantize.py): wire payload bytes,
    # on-wire dtype, and the saving vs the dtype-true baseline
    tel.collective("all_reduce", 1082368, "dp", dtype="float32",
                   dur_ms=1.5, world=4, wire_dtype="int8",
                   bytes_saved=3111936)
    tel.gauge("comm/all_reduce/quant_bytes_saved", 3111936.0, step=1)
    tel.emit("meta", "engine/init", attrs={"mesh": {"dp": 8}})
    tel.fault("fault/retry", attrs={"op": "ckpt_save[t1]", "attempt": 1,
                                    "max_retries": 3, "error": "OSError()",
                                    "delay_s": 0.5})
    tel.fault("fault/ckpt_fallback", step=4, attrs={"to": "global_step2"})
    tel.fault("fault/preempt_requested")
    tel.serve("serve/admit", attrs={"req_id": "r1", "queue_depth": 2,
                                    "free_pages": 14})
    tel.serve("serve/reject", attrs={"req_id": "r2",
                                     "reason": "queue_full"})
    tel.serve("serve/shed", attrs={"req_id": "r0", "reason": "shed_oldest"})
    tel.serve("serve/deadline", attrs={"req_id": "r3", "reason": "deadline",
                                       "where": "active"})
    tel.serve("serve/evict", attrs={"req_id": "r4", "reason": "fault",
                                    "error": "boom"})
    tel.serve("serve/fault", attrs={"site": "serve_step", "error": "inj"})
    tel.serve("serve/finish", attrs={"req_id": "r1", "n_generated": 8})
    tel.serve("serve/drain", attrs={"finished": 3, "shed": 1, "steps": 12})
    tel.serve("serve/prefix_hit", attrs={"req_id": "r5", "pages_reused": 3,
                                         "tokens_reused": 384, "cow": 1})
    tel.serve("serve/prefix_cow", attrs={"req_id": "r5", "src": 7,
                                         "dst": 12, "tokens": 90})
    tel.serve("serve/prefix_insert", attrs={"req_id": "r5", "pages": 4,
                                            "at": "finish"})
    tel.serve("serve/prefix_evict", attrs={"page": 7})
    tel.serve("serve/backend", attrs={"attention_backend": "pallas",
                                      "impl": "pallas", "interpret": 0})
    # scheduler plane (inference/scheduler.py): policy meta, one prefill
    # chunk, one speculative draft proposal and its verification
    tel.serve("serve/sched", attrs={"policy": "chunked",
                                    "prefill_chunk_tokens": 256,
                                    "speculative": 1,
                                    "num_draft_tokens": 4})
    tel.serve("serve/prefill_chunk",
              attrs={"req_id": "r10", "slot": 1, "start": 256,
                     "tokens": 256, "remaining": 128,
                     "slo_class": "latency"})
    tel.serve("serve/spec_draft", attrs={"slots": 3, "window": 4})
    tel.serve("serve/spec_verify", attrs={"slots": 3, "window": 4,
                                          "accepted": 9, "rejected": 3})
    # the per-request lifecycle trace (RequestTracer): admitted ->
    # prefill_start -> first_token -> exactly one terminal
    tel.serve("serve/request/admitted",
              attrs={"req_id": "r6", "queue_depth": 1, "prompt_tokens": 5,
                     "max_new_tokens": 8, "deadline": 1})
    tel.serve("serve/request/prefill_start",
              attrs={"req_id": "r6", "slot": 0, "pages": 2,
                     "cached_tokens": 0, "queue_wait_ms": 1.25})
    tel.serve("serve/request/first_token",
              attrs={"req_id": "r6", "slot": 0, "ttft_ms": 4.5})
    tel.serve("serve/request/finish",
              attrs={"req_id": "r6", "slot": 0, "n_generated": 8,
                     "queue_wait_ms": 1.25, "ttft_ms": 4.5,
                     "tpot_ms": 2.0, "e2e_ms": 18.5, "slo": "ok"})
    tel.serve("serve/request/shed",
              attrs={"req_id": "r7", "reason": "shed_oldest",
                     "n_generated": 0, "e2e_ms": 3.0, "slo": "miss"})
    tel.serve("serve/request/deadline",
              attrs={"req_id": "r8", "slot": 1, "reason": "deadline",
                     "n_generated": 2, "e2e_ms": 55.0, "slo": "miss"})
    tel.serve("serve/request/evict",
              attrs={"req_id": "r9", "slot": 2, "reason": "fault",
                     "n_generated": 1, "e2e_ms": 9.0})
    # the terminal-adjacent critical-path attribution event
    # (monitor/attribution.py): one <stage>_ms per frozen stage, summing
    # to e2e_ms by construction
    tel.serve("serve/request/attr",
              attrs={"req_id": "r6", "terminal": "finish", "migrated": 1,
                     "chunks": 2, "path": "queue>prefill>migrate>decode",
                     "queue_ms": 1.25, "prefill_ms": 3.0,
                     "migrate_ms": 0.5, "gap_ms": 0.25, "decode_ms": 13.5,
                     "e2e_ms": 18.5})
    # the attribution plane's frozen per-step decomposition gauges
    for attr_name in ("compute_ms", "exposed_comm_ms", "input_wait_ms",
                      "host_sync_ms", "compile_ms"):
        tel.gauge(f"step/attr/{attr_name}", 1.0, step=1)
    tel.gauge("step/attr/exposed_comm_frac", 0.05, step=1)
    # the fleet router's full vocabulary — every name the checker
    # freezes must pass through the live emitter
    tel.fleet("fleet/spawn", attrs={"replica": "r0", "epoch": "r0g0"})
    tel.fleet("fleet/respawn", step=9,
              attrs={"replica": "r1", "epoch": "r1g1"})
    tel.fleet("fleet/route", attrs={"req_id": "f1", "replica": "r0",
                                    "dispatches": 1})
    tel.fleet("fleet/spill", attrs={"req_id": "f2", "replica": "r1",
                                    "affinity": "r0"})
    tel.fleet("fleet/dispatch_fault", attrs={"req_id": "f3",
                                             "error": "inj"})
    tel.fleet("fleet/redispatch", attrs={"req_id": "f1", "dispatches": 2})
    tel.fleet("fleet/kill", attrs={"replica": "r1", "epoch": "r1g1",
                                   "redispatched": 2, "detail": "chaos"})
    tel.fleet("fleet/fence", attrs={"replica": "r0", "epoch": "r0g0",
                                    "reason": "recompile_storm"})
    tel.fleet("fleet/drain", attrs={"replica": "r0", "finished": 3,
                                    "shed": 1, "steps": 12})
    tel.fleet("fleet/shed", attrs={"req_id": "f3",
                                   "reason": "redispatch_budget"})
    tel.fleet("fleet/scale_up", attrs={"replicas": 3, "queue_depth": 40})
    tel.fleet("fleet/scale_down", attrs={"replicas": 2, "queue_depth": 1})
    # the autotuner control plane's full vocabulary (tune/*)
    tel.tune("tune/trial_start",
             attrs={"trial": "tune-0000",
                    "knobs": '{"prefill_chunk_tokens": 64}'})
    tel.tune("tune/trial_result",
             attrs={"trial": "tune-0000", "objective": 12.5,
                    "snapshot_hash": "sha256:abc",
                    "metrics": '{"tokens_per_sec": 100.0}'})
    tel.tune("tune/trial_pruned",
             attrs={"trial": "tune-0001",
                    "reason": "draft_exceeds_page (draft=20, page=16)",
                    "knobs": '{"num_draft_tokens": 20}'})
    tel.tune("tune/overlay_written",
             attrs={"trial": "tune-0000", "path": "/tmp/overlay.json",
                    "snapshot_hash": "sha256:abc"})
    # the per-step attention spans the serving engine wraps its dispatches
    # in (phase: prefill / decode / decode_chunk)
    with tel.span("serve/step", attrs={"backend": "pallas",
                                       "phase": "decode", "batch": 4,
                                       "tokens": 1}):
        pass
    with tel.span("serve/attn", attrs={"backend": "jnp"}):
        pass
    wd = StepStallWatchdog(tel, stall_factor=1.0, min_stall_secs=0.0)
    wd.beat(0)
    wd.beat(1)
    wd.beat(2)
    import time
    assert wd.check(now=time.monotonic() + 1e6)  # forced stall event
    tel.close()
    problems = checker.validate_file(
        os.path.join(str(tmp_path), "schema", "events.jsonl"))
    assert problems == []


def test_trace_terminals_are_tail_of_serve_vocabulary(checker):
    """The four TRACE_TERMINALS map onto serve/request/<terminal> names in
    the frozen vocabulary — a rename on either side fails here."""
    from deepspeed_tpu.inference.robustness import TRACE_TERMINALS
    for t in TRACE_TERMINALS:
        assert f"serve/request/{t}" in checker.SERVE_EVENTS


def test_attribution_vocabularies_in_lockstep(checker):
    """STEP_ATTR_GAUGES and ATTR_STAGES are frozen in lockstep between
    monitor/attribution.py and the checker."""
    from deepspeed_tpu.monitor import attribution
    assert checker.STEP_ATTR_GAUGES == attribution.STEP_ATTR_GAUGES
    assert checker.ATTR_STAGES == attribution.ATTR_STAGES


def test_rejects_unknown_step_attr_gauge(checker):
    import time
    base = {"ts": time.time(), "kind": "gauge", "value": 1.0,
            "peak": 1.0}
    assert checker.validate_event(
        dict(base, name="step/attr/compute_ms")) == []
    assert checker.validate_event(
        dict(base, name="step/attr/bogus_ms"))


def test_train_attn_gauges_in_lockstep(checker):
    """The trainer's flash-attention plan: ``ATTN_PLAN`` of the model file
    and ``ATTN_SAVED`` after it, under ``train/attn/``, are the checker's
    vocabulary, name for name."""
    import time
    from deepspeed_tpu.models.transformer import ATTN_PLAN, ATTN_SAVED
    assert checker.TRAIN_ATTN_GAUGES == tuple(
        "train/attn/" + name for name in ATTN_PLAN + (ATTN_SAVED,))
    base = {"ts": time.time(), "kind": "gauge", "value": 1.0,
            "peak": 1.0}
    for name in checker.TRAIN_ATTN_GAUGES:
        assert checker.validate_event(dict(base, name=name)) == []
    assert checker.validate_event(dict(base, name="train/attn/tiles"))
    # its neighbours stay free-form
    assert checker.validate_event(
        dict(base, name="train/moe/expert_pairs")) == []


def test_attr_event_requires_every_stage(checker):
    """serve/request/attr must carry one numeric <stage>_ms per frozen
    stage plus e2e_ms — a dropped or non-numeric stage fails."""
    import time
    attrs = {"req_id": "r1", "terminal": "finish", "migrated": 0,
             "chunks": 1, "path": "queue>decode",
             "queue_ms": 1.0, "prefill_ms": 2.0, "migrate_ms": 0.0,
             "gap_ms": 0.0, "decode_ms": 3.0, "e2e_ms": 6.0}
    base = {"ts": time.time(), "kind": "serve",
            "name": "serve/request/attr"}
    assert checker.validate_event(dict(base, attrs=dict(attrs))) == []
    for stage in checker.ATTR_STAGES + ("e2e",):
        broken = dict(attrs)
        del broken[f"{stage}_ms"]
        assert checker.validate_event(dict(base, attrs=broken)), stage
        broken = dict(attrs)
        broken[f"{stage}_ms"] = "fast"
        assert checker.validate_event(dict(base, attrs=broken)), stage


def test_prom_exposition_validation(checker):
    good = ("# TYPE ds_serve_ttft_ms summary\n"
            'ds_serve_ttft_ms{quantile="0.5"} 2.0\n'
            "ds_serve_ttft_ms_sum 6.0\n"
            "ds_serve_ttft_ms_count 3\n"
            "# TYPE ds_engine_loss gauge\n"
            "ds_engine_loss 0.5\n")
    assert checker.validate_prom_exposition(good) == []
    assert checker.validate_prom_exposition("ds_orphan 1\n")  # no TYPE
    assert checker.validate_prom_exposition(
        "# TYPE 9bad gauge\n9bad 1\n")          # illegal name
    assert checker.validate_prom_exposition(
        "# TYPE ds_x frobnicator\nds_x 1\n")    # unknown type
    assert checker.validate_prom_exposition(
        "# TYPE ds_x gauge\nds_x banana\n")     # non-numeric value


def test_prom_lockstep_with_exporter(checker):
    """The exporter's live output must satisfy the checker's --prom
    grammar — the two halves of the scrape contract."""
    from deepspeed_tpu.monitor.export import prom_name, prom_text
    assert checker.PROM_NAME_RE.match(prom_name("serve/ttft_ms"))
    snap = {"counters": {"serve/slo_attained": 2},
            "gauges": {"engine/loss": {"value": 0.5, "peak": 0.9},
                       "fresh": {"value": 0.0, "peak": float("-inf")}},
            "histograms": {"serve/ttft_ms":
                           {"count": 3, "min": 1.0, "max": 3.0,
                            "mean": 2.0, "p50": 2.0, "p90": 3.0,
                            "p99": 3.0},
                           "empty": {"count": 0, "min": None, "max": None,
                                     "mean": None, "p50": None,
                                     "p90": None, "p99": None}}}
    text = prom_text(snap)
    assert checker.validate_prom_exposition(text) == []
    assert 'ds_serve_ttft_ms{quantile="0.5"} 2.0' in text
    assert "ds_fresh_peak" not in text      # -inf sentinel skipped
    assert "ds_empty_count 0" in text       # typed empty summary exports


def test_prom_cli_exit_codes(checker, tmp_path, capsys):
    good = tmp_path / "good.prom"
    good.write_text("# TYPE ds_x gauge\nds_x 1.0\n")
    bad = tmp_path / "bad.prom"
    bad.write_text("ds_untyped 1.0\n")
    assert checker.main(["--prom", str(good)]) == 0
    assert checker.main(["--prom", str(good), str(bad)]) == 1
    assert "no TYPE declaration" in capsys.readouterr().out


def test_cli_exit_codes(checker, tmp_path, capsys):
    good = tmp_path / "good.jsonl"
    good.write_text('{"ts": 1.0, "kind": "meta", "name": "ok"}\n\n')
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"ts": 1.0, "kind": "nope", "name": "x"}\nnot json\n')
    assert checker.main([str(good)]) == 0
    assert checker.main([str(good), str(bad)]) == 1
    out = capsys.readouterr().out
    assert "unknown kind" in out and "not valid JSON" in out


def _shard_line(rank, **extra):
    import json
    ev = {"ts": 1.0, "kind": "meta", "name": "engine/init", "rank": rank}
    ev.update(extra)
    return json.dumps(ev) + "\n"


def test_shards_cli(checker, tmp_path, capsys):
    good = tmp_path / "good"
    good.mkdir()
    (good / "events.rank0.jsonl").write_text(_shard_line(0))
    (good / "events.rank1.jsonl").write_text(_shard_line(1))
    assert checker.main(["--shards", str(good)]) == 0
    assert "2 shard(s)" in capsys.readouterr().out
    # a torn FINAL line is tolerated (live writer), anywhere else fatal
    (good / "events.rank1.jsonl").write_text(_shard_line(1) + '{"torn')
    assert checker.main(["--shards", str(good)]) == 0
    (good / "events.rank1.jsonl").write_text('{"torn\n' + _shard_line(1))
    assert checker.main(["--shards", str(good)]) == 1
    capsys.readouterr()
    # a rank stamp disagreeing with the shard filename is corruption
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "events.rank0.jsonl").write_text(_shard_line(3))
    assert checker.main(["--shards", str(bad)]) == 1
    assert "rank stamp" in capsys.readouterr().out
    empty = tmp_path / "empty"
    empty.mkdir()
    assert checker.main(["--shards", str(empty)]) == 1


def test_cluster_cli_and_payload(checker, tmp_path, capsys):
    import json
    from deepspeed_tpu.monitor.aggregate import aggregate_cluster
    events = {r: [{"ts": 1.0 + s, "kind": "heartbeat", "name": "hb",
                   "step": s, "step_ms": 10.0, "rank": r}
                  for s in range(4)] for r in range(2)}
    snap = aggregate_cluster(events)
    assert checker.validate_cluster_payload(snap) == []
    p = tmp_path / "cluster.json"
    p.write_text(json.dumps(snap))
    assert checker.main(["--cluster", str(p)]) == 0
    # mutations the validator must catch
    assert checker.validate_cluster_payload({"ts": 1.0})
    broken = dict(snap)
    broken["straggler"] = dict(snap["straggler"], metric="vibes")
    assert checker.validate_cluster_payload(broken)
    broken = dict(snap)
    broken["collectives"] = {"gossip": {}}
    assert checker.validate_cluster_payload(broken)
    p.write_text("not json")
    assert checker.main(["--cluster", str(p)]) == 1
    capsys.readouterr()


# ----------------------------------------------------------------------
# profiling plane: compile kind + mem/roofline gauge vocabularies
# ----------------------------------------------------------------------
def test_profiling_vocabularies_in_lockstep(checker):
    """The frozen compile/mem/roofline vocabularies must stay
    byte-identical between monitor/profiling.py and the checker."""
    from deepspeed_tpu.monitor import profiling
    assert checker.COMPILE_EVENTS == profiling.COMPILE_EVENTS
    assert checker.COMPILE_CAUSES == profiling.COMPILE_CAUSES
    assert checker.PROFILE_SPANS == profiling.PROFILE_SPANS
    assert checker.MEM_METRICS == profiling.MEM_METRICS
    assert checker.ROOFLINE_METRICS == profiling.ROOFLINE_METRICS


def test_compile_event_validation(checker):
    miss = {"ts": 1.0, "kind": "compile", "name": "compile/miss",
            "site": "engine/train_step:1", "dur_ms": 812.5, "count": 1,
            "cause": "cold", "step": 0, "rank": 0}
    assert not checker.validate_event(miss)
    storm = {"ts": 2.0, "kind": "compile", "name": "compile/storm",
             "site": "*", "count": 4, "window_s": 60.0}
    assert not checker.validate_event(storm)
    # unknown event name / cause outside the frozen vocabulary
    assert checker.validate_event(dict(miss, name="compile/hiccup"))
    assert checker.validate_event(dict(miss, cause="gremlins"))
    # missing required site/count
    assert checker.validate_event(
        {"ts": 1.0, "kind": "compile", "name": "compile/miss", "count": 1})
    assert checker.validate_event(
        {"ts": 1.0, "kind": "compile", "name": "compile/miss",
         "site": "engine/apply"})


def test_mem_and_roofline_gauge_validation(checker):
    for span in checker.PROFILE_SPANS:
        for metric in checker.MEM_METRICS:
            assert not checker.validate_event(
                {"ts": 1.0, "kind": "gauge",
                 "name": f"mem/{span}/{metric}", "value": 1024.0,
                 "peak": 2048.0})
        for metric in checker.ROOFLINE_METRICS:
            assert not checker.validate_event(
                {"ts": 1.0, "kind": "gauge",
                 "name": f"roofline/{span}/{metric}", "value": 0.41,
                 "peak": 0.5, "step": 7, "rank": 1})
    # unknown span / metric / malformed structure are all rejected
    assert checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "mem/warmup/live_bytes",
         "value": 1.0, "peak": 1.0})
    assert checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "mem/fwd/rss_bytes",
         "value": 1.0, "peak": 1.0})
    assert checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "roofline/fwd/mfu",
         "value": 1.0, "peak": 1.0})
    assert checker.validate_event(
        {"ts": 1.0, "kind": "gauge", "name": "roofline/compute_frac",
         "value": 1.0, "peak": 1.0})


def test_ledger_row_validation(checker):
    good = {"ts": 1.0, "run": "run-1", "bench": "cpu_dispatch",
            "metric": "steps_per_sec", "value": 12.5, "unit": "steps/s"}
    assert checker.validate_ledger_row(good) == []
    assert checker.validate_ledger_row({"ts": 1.0, "run": "r",
                                        "bench": "b", "metric": "m",
                                        "value": 1})== []
    # missing field / wrong types / unknown field / bool value
    assert checker.validate_ledger_row({k: v for k, v in good.items()
                                        if k != "metric"})
    assert checker.validate_ledger_row(dict(good, value="fast"))
    assert checker.validate_ledger_row(dict(good, value=True))
    assert checker.validate_ledger_row(dict(good, vibe="good"))
    assert checker.validate_ledger_row([1, 2])


def test_ledger_cli_exit_codes(checker, tmp_path, capsys):
    import json
    good = tmp_path / "good.jsonl"
    good.write_text(json.dumps(
        {"ts": 1.0, "run": "r1", "bench": "b", "metric": "m",
         "value": 1.0}) + "\n\n")
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"ts": 1.0, "run": "r1"}\nnot json\n')
    assert checker.main(["--ledger", str(good)]) == 0
    assert checker.main(["--ledger", str(good), str(bad)]) == 1
    out = capsys.readouterr().out
    assert "not valid JSON" in out


# ----------------------------------------------------------------------
# incident plane: frozen trigger/event vocabularies + bundle layout
# ----------------------------------------------------------------------
def test_incident_vocabularies_in_lockstep(checker):
    """The frozen incident vocabularies must stay byte-identical between
    the incident plane (monitor/incidents.py) and the checker script."""
    from deepspeed_tpu.monitor import incidents
    assert checker.INCIDENT_EVENTS == incidents.INCIDENT_EVENTS
    assert checker.INCIDENT_TRIGGERS == incidents.INCIDENT_TRIGGERS


def test_incident_event_validation(checker):
    good = {"ts": 1.0, "kind": "incident", "name": "incident/written",
            "id": "inc-0001-stall", "trigger": "stall"}
    assert checker.validate_event(good) == []
    assert checker.validate_event(dict(good, name="incident/vibes"))
    assert checker.validate_event(dict(good, trigger="gossip"))
    assert checker.validate_event({k: v for k, v in good.items()
                                   if k != "id"})


def test_tune_event_names_in_lockstep(checker):
    """The frozen tune-name vocabulary must stay byte-identical between
    the control plane (autotuning/controlplane.py) and the checker."""
    from deepspeed_tpu.autotuning.controlplane import TUNE_EVENTS
    assert checker.TUNE_EVENTS == TUNE_EVENTS


def test_rejects_unknown_tune_name(checker):
    assert checker.validate_event(
        {"ts": 1.0, "kind": "tune", "name": "tune/not_a_thing"})
    assert not checker.validate_event(
        {"ts": 1.0, "kind": "tune", "name": "tune/trial_start",
         "attrs": {"trial": "tune-0000"}, "step": 1})


def test_overlay_payload_validation(checker, tmp_path):
    import json
    good = {"overlay": {"serving": {"page_size": 32}},
            "provenance": {"trial": "tune-0000", "snapshot_hash":
                           "sha256:abc", "objective": 1.5, "ts": 1.0,
                           "knobs": {"page_size": 32}}}
    assert checker.validate_overlay_payload(good) == []
    # missing fragment / missing provenance field / wrong types
    assert checker.validate_overlay_payload({"provenance":
                                             good["provenance"]})
    bad_prov = {k: v for k, v in good["provenance"].items()
                if k != "snapshot_hash"}
    assert checker.validate_overlay_payload(
        dict(good, provenance=bad_prov))
    assert checker.validate_overlay_payload(
        dict(good, provenance=dict(good["provenance"], objective="high")))
    assert checker.validate_overlay_payload([1, 2])
    p = tmp_path / "overlay.json"
    p.write_text(json.dumps(good))
    assert checker.validate_overlay_file(str(p)) == []
    p.write_text("not json")
    assert checker.validate_overlay_file(str(p))


def test_tune_cli_exit_codes(checker, tmp_path, capsys):
    import json
    d = tmp_path / "results"
    d.mkdir()
    (d / "overlay.json").write_text(json.dumps(
        {"overlay": {"serving": {"page_size": 32}},
         "provenance": {"trial": "tune-0000", "snapshot_hash":
                        "sha256:abc", "objective": 1.5, "ts": 1.0,
                        "knobs": {}}}))
    (d / "tune-0000.json").write_text(json.dumps(
        {"objective": 1.5, "ds_config": {"serving": {"page_size": 32}}}))
    (d / "events.jsonl").write_text(json.dumps(
        {"ts": 1.0, "kind": "tune", "name": "tune/trial_start",
         "attrs": {"trial": "tune-0000"}}) + "\n")
    assert checker.main(["--tune", str(d)]) == 0
    assert "3 tune artifact(s)" in capsys.readouterr().out
    # a journal without a ds_config stamp is corrupt
    (d / "tune-0001.json").write_text(json.dumps({"objective": 2.0}))
    assert checker.main(["--tune", str(d)]) == 1
    capsys.readouterr()
    empty = tmp_path / "empty"
    empty.mkdir()
    assert checker.main(["--tune", str(empty)]) == 1
    capsys.readouterr()


def test_incidents_cli_and_bundle_validation(checker, tmp_path, capsys):
    import json
    from deepspeed_tpu.monitor.incidents import IncidentManager
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig
    tel = Telemetry().configure(TelemetryConfig(
        {"enabled": True, "output_path": str(tmp_path), "job_name": "j",
         "incidents": {"enabled": True, "cooldown_s": 0.0}}), rank=0)
    tel.incidents.trigger("leak", source="test", detail="stray")
    bdir = tel.incidents.bundle_dir
    tel.close()
    assert checker.main(["--incidents", bdir]) == 0
    # single-bundle form: point straight at the bundle directory
    (bundle,) = sorted(os.listdir(bdir))
    assert checker.main(["--incidents", os.path.join(bdir, bundle)]) == 0
    # mutations the validator must catch
    inc_path = os.path.join(bdir, bundle, "incident.json")
    with open(inc_path) as f:
        payload = json.load(f)
    with open(inc_path, "w") as f:
        json.dump(dict(payload, trigger=dict(payload["trigger"],
                                             kind="gossip")), f)
    assert checker.main(["--incidents", bdir]) == 1
    os.remove(os.path.join(bdir, bundle, "ring.jsonl"))
    problems, n = checker.validate_incidents_path(bdir)
    assert problems and n == 1
    empty = tmp_path / "empty"
    empty.mkdir()
    assert checker.main(["--incidents", str(empty)]) == 1
    capsys.readouterr()
