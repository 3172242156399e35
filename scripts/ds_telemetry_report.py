#!/usr/bin/env python
"""Aggregate a unified-telemetry JSONL stream into human-readable tables.

Reads the ``events.jsonl`` (plus rotated ``events.jsonl.N`` generations,
oldest first) written by ``deepspeed_tpu/monitor/telemetry.py`` — or, for
a distributed run, every per-rank shard ``events.rank{N}.jsonl`` in the
directory — and prints:

* per-span latency percentiles (count / mean / p50 / p90 / p99 / max),
* comm census per op: traced calls, total bytes, summed duration, and
  achieved bandwidth (timed bytes / timed duration) for timed records,
* gauge last/peak table (HBM bytes-in-use, tokens/s, MFU, loss, ...),
* heartbeat summary (steps seen, median step time) and any stall events,
* with >= 2 rank shards: a per-rank cluster table (steps, median step
  time) and the cross-rank step-time skew.

Usage:
    python scripts/ds_telemetry_report.py <telemetry_dir_or_events.jsonl>
    python scripts/ds_telemetry_report.py --json run/telemetry/MyJob
"""

import argparse
import glob
import json
import os
import sys


def _with_rotations(live):
    """[oldest rotated .N .. live] for one stream file."""
    rotated = sorted(
        glob.glob(live + ".*"),
        key=lambda p: int(p.rsplit(".", 1)[1])
        if p.rsplit(".", 1)[1].isdigit() else 0,
        reverse=True)
    files = [p for p in rotated if p.rsplit(".", 1)[1].isdigit()]
    if os.path.exists(live):
        files.append(live)
    return files


def discover_files(target):
    """Stream files for a path that may be a dir, the live file, or a
    glob; ordered oldest -> newest per stream so replay is in time order.
    A directory holding per-rank shards (``events.rank{N}.jsonl``,
    distributed telemetry) yields every shard; records carry their rank
    stamp so the merged replay keeps attribution."""
    if os.path.isdir(target):
        shards = sorted(
            p for p in glob.glob(os.path.join(target, "events.rank*.jsonl"))
            if p.rsplit("rank", 1)[1].split(".")[0].isdigit())
        if shards:
            files = []
            for live in shards:
                files.extend(_with_rotations(live))
            return files
        live = os.path.join(target, "events.jsonl")
    else:
        live = target
    return _with_rotations(live)


def load_events(files):
    for path in files:
        try:
            f = open(path)
        except OSError as e:
            print(f"WARN: skipping unreadable {path}: {e}",
                  file=sys.stderr)
            continue
        with f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    yield json.loads(line)
                except ValueError:
                    continue  # torn tail line from a live writer


def _pct(sorted_vals, q):
    n = len(sorted_vals)
    return sorted_vals[min(n - 1, max(0, int(round(q / 100.0 * (n - 1)))))]


def aggregate(events):
    spans = {}       # name -> [dur_ms]
    comms = {}       # op -> {calls, bytes, axes}
    gauges = {}      # name -> {last, peak, n}
    heartbeats = []  # step_ms values
    rank_steps = {}  # rank -> {step: step_ms} (distributed shards)
    steps = set()
    stalls = []
    metas = []
    serves = {}      # event name -> {count, reasons: {reason: n}}
    fleets = {}      # fleet event name -> {count, reasons, replicas}
    fleet_roles = {} # replica id -> role (disaggregated fleets)
    requests = []    # reconstructed serve/request/* lifecycle traces
    open_reqs = {}   # req_id -> index into requests (trace not yet closed)
    closed_reqs = {} # req_id -> last closed trace index (attr attaches here)
    compiles = {"sites": {}, "storms": 0, "total_misses": 0}
    tunes = {"trials": {}, "pruned": {}, "overlay": None}
    for ev in events:
        kind = ev.get("kind")
        if kind == "span":
            spans.setdefault(ev["name"], []).append(float(ev["dur_ms"]))
        elif kind == "comm":
            rec = comms.setdefault(ev["name"],
                                   {"calls": 0, "bytes": 0, "axes": set(),
                                    "dur_ms": 0.0, "timed_calls": 0,
                                    "timed_bytes": 0})
            rec["calls"] += 1
            rec["bytes"] += int(ev["bytes"])
            rec["axes"].add(ev.get("axis", "?"))
            # timed records (comm tracing): achieved bandwidth is the
            # summed timed payload over the summed duration
            if ev.get("dur_ms"):
                rec["dur_ms"] += float(ev["dur_ms"])
                rec["timed_calls"] += 1
                rec["timed_bytes"] += int(ev["bytes"])
        elif kind == "gauge":
            g = gauges.setdefault(ev["name"],
                                  {"last": None, "peak": None, "n": 0})
            g["last"] = ev["value"]
            g["peak"] = ev.get("peak", ev["value"])
            g["n"] += 1
        elif kind == "heartbeat":
            steps.add(ev.get("step"))
            if ev.get("step_ms") is not None:
                heartbeats.append(float(ev["step_ms"]))
            # distributed shards stamp each record; single-rank -> rank 0
            rs = rank_steps.setdefault(int(ev.get("rank", 0)), {})
            if ev.get("step") is not None:
                rs[int(ev["step"])] = (ev.get("step_ms")
                                       if ev.get("step_ms") is not None
                                       else rs.get(int(ev["step"])))
        elif kind == "compile":
            # profiling plane (monitor/profiling.py): per-site recompile
            # census + storm count for the compile-tracing table
            if ev.get("name") == "compile/storm":
                compiles["storms"] += 1
            else:
                rec = compiles["sites"].setdefault(
                    ev.get("site", "?"),
                    {"misses": 0, "dur_ms": 0.0, "causes": {}})
                rec["misses"] += 1
                rec["dur_ms"] += float(ev.get("dur_ms") or 0.0)
                cause = ev.get("cause")
                if cause:
                    rec["causes"][cause] = rec["causes"].get(cause, 0) + 1
                compiles["total_misses"] += 1
        elif kind == "stall":
            stalls.append(ev)
        elif kind == "meta":
            metas.append(ev)
        elif kind == "fleet":
            rec = fleets.setdefault(ev["name"], {"count": 0, "reasons": {},
                                                 "replicas": set()})
            rec["count"] += 1
            attrs = ev.get("attrs") or {}
            reason = attrs.get("reason")
            if reason:
                rec["reasons"][reason] = rec["reasons"].get(reason, 0) + 1
            replica = attrs.get("replica")
            if replica:
                rec["replicas"].add(str(replica))
            # disaggregated fleets: spawn/respawn stamp each replica's
            # role; migrate_commit carries the page-transfer ledger and
            # migrate_fault its injector site
            role = attrs.get("role")
            if role and replica:
                fleet_roles[str(replica)] = str(role)
            if ev["name"] == "fleet/migrate_commit":
                for k in ("pages", "skipped", "bytes", "bytes_saved",
                          "quant_bytes_saved"):
                    rec[k] = rec.get(k, 0) + int(attrs.get(k) or 0)
            elif ev["name"] == "fleet/migrate_fault":
                site = attrs.get("site")
                if site:
                    sites = rec.setdefault("sites", {})
                    sites[site] = sites.get(site, 0) + 1
            # transport plane (fleet/retry, breaker transitions,
            # dup_call_dropped): per-op retry counts + elapsed-at-retry
            # samples for the timeout percentiles, breaker open/close
            # per replica, and the dedup drop census by op+kind
            elif ev["name"] == "fleet/retry":
                op = str(attrs.get("op") or "?")
                ops = rec.setdefault("ops", {})
                ops[op] = ops.get(op, 0) + 1
                if attrs.get("elapsed_s") is not None:
                    rec.setdefault("elapsed_s", []).append(
                        float(attrs["elapsed_s"]))
            elif ev["name"] in ("fleet/breaker_open",
                                "fleet/breaker_close"):
                if replica:
                    per = rec.setdefault("per_replica", {})
                    per[str(replica)] = per.get(str(replica), 0) + 1
            elif ev["name"] == "fleet/dup_call_dropped":
                op = str(attrs.get("op") or "?")
                kind_ = str(attrs.get("kind") or "?")
                drops = rec.setdefault("drops", {})
                drops[(op, kind_)] = drops.get((op, kind_), 0) + 1
        elif kind == "tune":
            # closed-loop autotuner stream (frozen tune/* vocabulary):
            # trial_start stamps the knob point, trial_result the
            # snapshot-scored objective, trial_pruned the memory-model
            # verdict, overlay_written the persisted winner
            attrs = ev.get("attrs") or {}
            trial = attrs.get("trial")
            if ev["name"] == "tune/trial_start":
                rec = tunes["trials"].setdefault(trial, {})
                rec["knobs"] = attrs.get("knobs")
            elif ev["name"] == "tune/trial_result":
                rec = tunes["trials"].setdefault(trial, {})
                rec["objective"] = attrs.get("objective")
                rec["snapshot_hash"] = attrs.get("snapshot_hash")
                try:
                    rec["metrics"] = json.loads(attrs.get("metrics")
                                                or "{}")
                except ValueError:
                    rec["metrics"] = {}
            elif ev["name"] == "tune/trial_pruned":
                tunes["pruned"][trial] = {"reason": attrs.get("reason"),
                                          "knobs": attrs.get("knobs")}
            elif ev["name"] == "tune/overlay_written":
                tunes["overlay"] = {"trial": trial,
                                    "path": attrs.get("path"),
                                    "snapshot_hash":
                                        attrs.get("snapshot_hash")}
        elif kind == "serve":
            rec = serves.setdefault(ev["name"], {"count": 0, "reasons": {}})
            rec["count"] += 1
            attrs = ev.get("attrs") or {}
            reason = attrs.get("reason")
            if reason:
                rec["reasons"][reason] = rec["reasons"].get(reason, 0) + 1
            # prefix-cache events carry their numbers in attrs — sum them
            # so the report can print the reuse digest without the engine
            if ev["name"] == "serve/prefix_hit":
                rec["pages_reused"] = rec.get("pages_reused", 0) + \
                    int(attrs.get("pages_reused", 0))
                rec["tokens_reused"] = rec.get("tokens_reused", 0) + \
                    int(attrs.get("tokens_reused", 0))
            elif ev["name"] == "serve/prefix_insert":
                rec["pages"] = rec.get("pages", 0) + \
                    int(attrs.get("pages", 0))
            elif ev["name"] == "serve/backend":
                rec["backend"] = attrs.get("attention_backend", "?")
            # scheduler-plane events: the chunked/speculative policies
            # stamp their work on attrs — sum them here so the report can
            # print chunks-per-prefill / acceptance without the engine
            elif ev["name"] == "serve/sched":
                rec["policy"] = attrs.get("policy", "?")
                rec["attrs"] = dict(attrs)
            elif ev["name"] == "serve/prefill_chunk":
                rec["tokens"] = rec.get("tokens", 0) + \
                    int(attrs.get("tokens", 0))
                by_req = rec.setdefault("by_req", {})
                rid = attrs.get("req_id")
                by_req[rid] = by_req.get(rid, 0) + 1
            elif ev["name"] == "serve/spec_draft":
                rec["slots"] = rec.get("slots", 0) + \
                    int(attrs.get("slots", 0))
            elif ev["name"] == "serve/spec_verify":
                rec["accepted"] = rec.get("accepted", 0) + \
                    int(attrs.get("accepted", 0))
                rec["rejected"] = rec.get("rejected", 0) + \
                    int(attrs.get("rejected", 0))
            elif ev["name"].startswith("serve/request/"):
                # rebuild per-request lifecycle traces from the stream;
                # req_ids may recur across runs in one file, so a fresh
                # "admitted" after a terminal opens a NEW trace
                stage = ev["name"].rsplit("/", 1)[1]
                rid = attrs.get("req_id")
                if stage == "attr":
                    # critical-path attribution (emitted adjacent to the
                    # terminal): total per-stage milliseconds for the
                    # attribution digest and pin the breakdown onto the
                    # just-closed trace
                    for k in ("queue_ms", "prefill_ms", "migrate_ms",
                              "gap_ms", "decode_ms", "e2e_ms"):
                        if attrs.get(k) is not None:
                            rec[k] = rec.get(k, 0.0) + float(attrs[k])
                    rec["migrated"] = rec.get("migrated", 0) + \
                        int(attrs.get("migrated") or 0)
                    idx = closed_reqs.get(rid)
                    if idx is not None:
                        requests[idx]["attr"] = {
                            k: attrs[k] for k in
                            ("queue_ms", "prefill_ms", "migrate_ms",
                             "gap_ms", "decode_ms", "e2e_ms", "path")
                            if attrs.get(k) is not None}
                    continue
                if stage == "admitted":
                    open_reqs[rid] = len(requests)
                    requests.append({"req_id": rid, "t_admit": ev["ts"],
                                     "prompt_tokens":
                                         attrs.get("prompt_tokens"),
                                     "deadline": attrs.get("deadline", 0),
                                     "slo_class": attrs.get("slo_class"),
                                     "terminal": None})
                    continue
                idx = open_reqs.get(rid)
                if idx is None:
                    continue    # trace head rotated away
                trace = requests[idx]
                if stage == "prefill_start":
                    trace["slot"] = attrs.get("slot")
                    trace["queue_wait_ms"] = attrs.get("queue_wait_ms")
                elif stage == "first_token":
                    trace["ttft_ms"] = attrs.get("ttft_ms")
                else:           # finish | shed | deadline | evict
                    trace["terminal"] = stage
                    for k in ("reason", "n_generated", "slot", "slo",
                              "queue_wait_ms", "ttft_ms", "tpot_ms",
                              "e2e_ms"):
                        if attrs.get(k) is not None:
                            trace[k] = attrs[k]
                    closed_reqs[rid] = idx
                    del open_reqs[rid]
    return {"spans": spans, "comms": comms, "gauges": gauges,
            "heartbeats": heartbeats, "rank_steps": rank_steps,
            "steps": steps, "stalls": stalls,
            "metas": metas, "serves": serves, "fleets": fleets,
            "fleet_roles": fleet_roles, "tunes": tunes,
            "requests": requests, "compiles": compiles}


def summarize(agg):
    """JSON-friendly summary of an aggregate()."""
    span_rows = {}
    for name, durs in sorted(agg["spans"].items()):
        vals = sorted(durs)
        span_rows[name] = {
            "count": len(vals),
            "mean_ms": round(sum(vals) / len(vals), 3),
            "p50_ms": round(_pct(vals, 50), 3),
            "p90_ms": round(_pct(vals, 90), 3),
            "p99_ms": round(_pct(vals, 99), 3),
            "max_ms": round(vals[-1], 3),
        }
    comm_rows = {}
    for op, rec in sorted(agg["comms"].items()):
        row = {"calls": rec["calls"], "bytes": rec["bytes"],
               "axes": sorted(rec["axes"]),
               "dur_ms": round(rec.get("dur_ms", 0.0), 3),
               "timed_calls": rec.get("timed_calls", 0)}
        dur, tb = rec.get("dur_ms", 0.0), rec.get("timed_bytes", 0)
        row["achieved_gbps"] = (round(tb / (dur / 1e3) / 1e9, 4)
                                if dur > 0 and tb else None)
        comm_rows[op] = row
    gauge_rows = {
        name: {"last": g["last"], "peak": g["peak"], "samples": g["n"]}
        for name, g in sorted(agg["gauges"].items())}
    hb = sorted(agg["heartbeats"])
    heartbeat = {"steps": len(agg["steps"]),
                 "median_step_ms": round(_pct(hb, 50), 3) if hb else None}
    serve_rows = {
        name: {"count": rec["count"],
               "reasons": dict(sorted(rec["reasons"].items()))}
        for name, rec in sorted(agg.get("serves", {}).items())}
    fleet_rows = {
        name: {"count": rec["count"],
               "reasons": dict(sorted(rec["reasons"].items())),
               "replicas": sorted(rec["replicas"])}
        for name, rec in sorted(agg.get("fleets", {}).items())}
    for name, rec in agg.get("fleets", {}).items():
        # migration ledger columns ride the per-event rows too
        for k in ("pages", "skipped", "bytes", "bytes_saved",
                  "quant_bytes_saved", "sites"):
            if k in rec:
                fleet_rows[name][k] = rec[k]
    return {"spans": span_rows, "comms": comm_rows, "gauges": gauge_rows,
            "heartbeat": heartbeat,
            "profiling": _profiling_summary(agg),
            "attribution": _attribution_summary(agg),
            "overlap": _overlap_summary(agg),
            "tiered": _tiered_summary(agg),
            "cluster": _cluster_summary(agg),
            "input_feed": _input_feed_summary(agg),
            "serving": serve_rows,
            "fleet": fleet_rows,
            "fleet_transport": _transport_summary(agg),
            "fleet_disagg": _disagg_summary(agg),
            "autotuning": _autotuning_summary(agg),
            "serving_attention": _serving_attention_summary(agg),
            "scheduler": _scheduler_summary(agg),
            "prefix_cache": _prefix_cache_summary(agg),
            "request_latency": _request_latency_summary(agg),
            "stalls": [{k: v for k, v in s.items() if k != "kind"}
                       for s in agg["stalls"]]}


def _transport_summary(agg):
    """Fleet wire-layer digest from the frozen transport events
    (``fleet/retry``, ``fleet/breaker_open|close``,
    ``fleet/dup_call_dropped``): retry counts by op with the
    elapsed-at-retry percentiles (a proxy for the call-timeout tail),
    breaker transitions per replica, and the duplicate-call drop census
    by op and kind (``stale_resp`` = late reply discarded by call id,
    ``ikey_replay`` = worker-side idempotency dedup).  None when the
    stream carries no transport events."""
    fleets = agg.get("fleets") or {}
    retry = fleets.get("fleet/retry") or {}
    opens = fleets.get("fleet/breaker_open") or {}
    closes = fleets.get("fleet/breaker_close") or {}
    drops = fleets.get("fleet/dup_call_dropped") or {}
    if not (retry or opens or closes or drops):
        return None
    elapsed = sorted(retry.get("elapsed_s") or [])
    breakers = {}
    for name, rec in (("opens", opens), ("closes", closes)):
        for rid, n in (rec.get("per_replica") or {}).items():
            breakers.setdefault(rid, {"opens": 0, "closes": 0})[name] = n
    return {
        "retries": retry.get("count", 0),
        "retries_by_op": dict(sorted((retry.get("ops") or {}).items())),
        "retry_elapsed_p50_s": (round(_pct(elapsed, 50), 4)
                                if elapsed else None),
        "retry_elapsed_p99_s": (round(_pct(elapsed, 99), 4)
                                if elapsed else None),
        "breaker_opens": opens.get("count", 0),
        "breaker_closes": closes.get("count", 0),
        "breakers": dict(sorted(breakers.items())),
        "dup_calls_dropped": drops.get("count", 0),
        "drops_by_op": {f"{op}:{kind}": n for (op, kind), n in
                        sorted((drops.get("drops") or {}).items())},
    }


def _autotuning_summary(agg):
    """Closed-loop autotuner digest from the frozen ``tune/*`` stream:
    trials run/pruned with their knob points, the snapshot-scored
    objective per trial, the winning overlay's knobs and provenance,
    and the ledger rows the trial runner appended (one per scored
    metric plus the objective row).  None when the stream carries no
    tune events."""
    tunes = agg.get("tunes") or {}
    trials, pruned = tunes.get("trials") or {}, tunes.get("pruned") or {}
    if not trials and not pruned and not tunes.get("overlay"):
        return None

    def _knobs(raw):
        if isinstance(raw, str):
            try:
                return json.loads(raw)
            except ValueError:
                return raw
        return raw

    rows = []
    for tid, rec in sorted(trials.items(), key=lambda kv: str(kv[0])):
        metrics = rec.get("metrics") or {}
        rows.append({"trial": tid, "knobs": _knobs(rec.get("knobs")),
                     "objective": rec.get("objective"),
                     "snapshot_hash": rec.get("snapshot_hash"),
                     "ledger_rows": len(metrics) + 1 if metrics else 0})
    pruned_rows = [
        {"trial": tid, "reason": rec.get("reason"),
         "knobs": _knobs(rec.get("knobs"))}
        for tid, rec in sorted(pruned.items(), key=lambda kv: str(kv[0]))]
    overlay = tunes.get("overlay")
    winner = None
    if overlay:
        winner = {"trial": overlay.get("trial")}
        for r in rows:
            if r["trial"] == overlay.get("trial"):
                winner.update(knobs=r["knobs"], objective=r["objective"])
    return {"trials_run": len(rows), "trials_pruned": len(pruned_rows),
            "trials": rows, "pruned": pruned_rows, "overlay": overlay,
            "winner": winner,
            "ledger_rows_written": sum(r["ledger_rows"] for r in rows)}


def _disagg_summary(agg):
    """Disaggregated-fleet digest: the per-role replica census (from
    role-stamped spawn/respawn events), per-pool queue-depth gauges, and
    the migration ledger summed from the frozen ``fleet/migrate_*``
    stream.  None when the run never stamped a non-unified role."""
    roles = agg.get("fleet_roles") or {}
    if not (set(roles.values()) - {"unified"}):
        return None
    fleets = agg.get("fleets", {})
    gauges = agg.get("gauges", {})

    def _gauge(name):
        g = gauges.get(name)
        return g["last"] if g else None

    by_role = {}
    for rid, role in sorted(roles.items()):
        by_role.setdefault(role, []).append(rid)
    commit = fleets.get("fleet/migrate_commit", {})
    return {
        "roles": {role: sorted(rids)
                  for role, rids in sorted(by_role.items())},
        "queue_depth": {role: _gauge(f"fleet/{role}_queue_depth")
                        for role in sorted(by_role)},
        "migrations": commit.get("count", 0),
        "migrated_pages": commit.get("pages", 0),
        "dedup_skipped_pages": commit.get("skipped", 0),
        "migrate_bytes": commit.get("bytes", 0),
        "bytes_saved": commit.get("bytes_saved", 0),
        "quant_bytes_saved": commit.get("quant_bytes_saved", 0),
        "faults": dict(sorted(fleets.get("fleet/migrate_fault", {})
                              .get("sites", {}).items())),
        "aborts": dict(sorted(fleets.get("fleet/migrate_abort", {})
                              .get("reasons", {}).items())),
        "local_prefills": fleets.get("fleet/local_prefill",
                                     {}).get("count", 0),
    }


def _profiling_summary(agg):
    """Profiling-plane digest (monitor/profiling.py): the per-site
    recompile census, per-span memory attribution from the
    ``mem/<span>/<metric>`` gauges, and the live roofline fractions from
    ``roofline/<span>/<metric>``.  None when the stream carries no
    profiling records at all (plane off)."""
    comp = agg.get("compiles") or {"sites": {}, "storms": 0,
                                   "total_misses": 0}
    mem, roofline = {}, {}
    for name, g in agg["gauges"].items():
        parts = name.split("/")
        if len(parts) != 3:
            continue
        family = {"mem": mem, "roofline": roofline}.get(parts[0])
        if family is not None:
            family.setdefault(parts[1], {})[parts[2]] = {
                "last": g["last"], "peak": g["peak"]}
    if not (comp["total_misses"] or comp["storms"] or mem or roofline):
        return None
    sites = {site: {"misses": rec["misses"],
                    "dur_ms": round(rec["dur_ms"], 3),
                    "causes": dict(sorted(rec["causes"].items()))}
             for site, rec in sorted(comp["sites"].items())}
    return {"compile": {"total_misses": comp["total_misses"],
                        "storms": comp["storms"], "sites": sites},
            "mem": mem, "roofline": roofline}


def _attribution_summary(agg):
    """Attribution-plane digest (monitor/attribution.py): the training
    step decomposition from the frozen ``step/attr/*`` gauges — the same
    numbers the roofline tables sit next to — and the serving
    critical-path stage totals summed over every ``serve/request/attr``
    event.  None when the stream carries neither."""
    step = {name.rsplit("/", 1)[1]: {"last": g["last"], "peak": g["peak"]}
            for name, g in sorted(agg["gauges"].items())
            if name.startswith("step/attr/")}
    attr = agg.get("serves", {}).get("serve/request/attr", {})
    serving = None
    if attr.get("count"):
        e2e = attr.get("e2e_ms", 0.0)
        stages = {}
        for k in ("queue_ms", "prefill_ms", "migrate_ms", "gap_ms",
                  "decode_ms"):
            ms = attr.get(k, 0.0)
            stages[k] = {"total_ms": round(ms, 3),
                         "frac": round(ms / e2e, 4) if e2e else None}
        serving = {"requests": attr["count"],
                   "migrated": attr.get("migrated", 0),
                   "e2e_ms": round(e2e, 3), "stages": stages}
    if not step and not serving:
        return None
    return {"step": step or None, "serving": serving}


def _overlap_summary(agg):
    """Comm/compute-overlap digest (runtime/zero/stage_plan.py): the
    frozen ``comm/overlap/*`` gauges the engine emits when
    ``zero_optimization.overlap.enabled`` — exposed vs overlapped comm
    time per step, the gather/reduce-scatter bucket census, and the
    configured prefetch depth — plus the exposed-comm fraction the
    overlap is meant to drive down.  None when the run never overlapped."""
    rows = {name.rsplit("/", 1)[1]: {"last": g["last"], "peak": g["peak"]}
            for name, g in sorted(agg["gauges"].items())
            if name.startswith("comm/overlap/")}
    if not rows:
        return None
    frac = agg["gauges"].get("step/attr/exposed_comm_frac")
    return {"gauges": rows,
            "exposed_comm_frac": frac["last"] if frac else None}


def _tiered_summary(agg):
    """Tiered-memory-engine digest (runtime/tiered_store.py): the frozen
    ``tier/*`` gauges — occupancy per tier, prefetch hit rate, transfer
    bandwidths, eviction/writeback counts, int8-tier savings.  None when
    the run never touched a tiered store."""
    rows = {name.split("/", 1)[1]: {"last": g["last"], "peak": g["peak"]}
            for name, g in sorted(agg["gauges"].items())
            if name.startswith("tier/")}
    if not rows:
        return None
    hits = (rows.get("prefetch_hits") or {}).get("last") or 0
    misses = (rows.get("prefetch_misses") or {}).get("last") or 0
    return {"gauges": rows,
            "prefetch_hit_rate": (round(hits / (hits + misses), 4)
                                  if hits + misses else None)}


def _cluster_summary(agg):
    """Cross-rank digest from the rank stamps on heartbeat records: one
    row per rank (steps seen, median step time) plus step-time skew over
    the aligned steps (step numbers every rank reported).  None for
    single-rank streams — the table only means something when >= 2 shards
    were merged."""
    rank_steps = agg.get("rank_steps") or {}
    if len(rank_steps) < 2:
        return None
    ranks = sorted(rank_steps)
    per_rank = {}
    for r in ranks:
        ms = sorted(float(v) for v in rank_steps[r].values()
                    if v is not None)
        per_rank[str(r)] = {
            "steps": len(rank_steps[r]),
            "median_step_ms": round(_pct(ms, 50), 3) if ms else None,
        }
    aligned = sorted(set.intersection(
        *(set(s) for s in rank_steps.values())))
    spreads = []
    for step in aligned:
        ms = [float(rank_steps[r][step]) for r in ranks
              if rank_steps[r].get(step) is not None]
        if len(ms) >= 2:
            spreads.append(max(ms) - min(ms))
    spreads.sort()
    medians = sorted(v["median_step_ms"] for v in per_rank.values()
                     if v["median_step_ms"] is not None)
    return {
        "ranks": len(ranks),
        "aligned_steps": len(aligned),
        "per_rank": per_rank,
        "step_skew_ms": {
            "p50": round(_pct(spreads, 50), 3) if spreads else None,
            "max": round(spreads[-1], 3) if spreads else None,
        },
        # the slowest rank relative to the median-of-medians: the same
        # ratio the live aggregator's straggler verdict thresholds on
        "worst_rel": (round(medians[-1] / _pct(medians, 50), 4)
                      if medians and _pct(medians, 50) else None),
    }


# how many individual request rows the latency table prints (slowest by
# e2e first); the percentile block always covers EVERY reconstructed trace
MAX_REQUEST_ROWS = 20


def _request_latency_summary(agg):
    """Per-request latency digest from the reconstructed
    ``serve/request/*`` traces: terminal counts + trace-completeness
    (orphans = admitted with no terminal — a live engine mid-run, or a
    trace leak), SLO attainment, p50/p90/p99 for every derived latency,
    and the slowest individual requests."""
    traces = agg.get("requests") or []
    if not traces:
        return None
    terminals = {}
    slo = {"ok": 0, "miss": 0}
    dists = {"queue_wait_ms": [], "ttft_ms": [], "tpot_ms": [],
             "e2e_ms": []}
    for t in traces:
        term = t.get("terminal")
        terminals[term or "open"] = terminals.get(term or "open", 0) + 1
        if t.get("slo") in slo:
            slo[t["slo"]] += 1
        for k, vals in dists.items():
            if t.get(k) is not None:
                vals.append(float(t[k]))
    pct_rows = {}
    for k, vals in dists.items():
        if not vals:
            continue
        vals = sorted(vals)
        pct_rows[k] = {"count": len(vals),
                       "p50": round(_pct(vals, 50), 3),
                       "p90": round(_pct(vals, 90), 3),
                       "p99": round(_pct(vals, 99), 3),
                       "max": round(vals[-1], 3)}
    closed = [t for t in traces if t.get("terminal")]
    slowest = sorted(closed, key=lambda t: t.get("e2e_ms") or -1.0,
                     reverse=True)[:MAX_REQUEST_ROWS]
    return {
        "traces": len(traces),
        "terminals": dict(sorted(terminals.items())),
        "orphans": terminals.get("open", 0),
        "slo": slo,
        "latency": pct_rows,
        "slowest": [{k: t.get(k) for k in
                     ("req_id", "terminal", "reason", "slot",
                      "n_generated", "queue_wait_ms", "ttft_ms",
                      "tpot_ms", "e2e_ms", "slo") if t.get(k) is not None}
                    for t in slowest],
    }


def _serving_attention_summary(agg):
    """Attention-backend digest: which kernel path served the stream
    (``serve/backend`` event) and attention's share of serve-step time —
    the ``serve/attn`` spans a bench or instrumented engine wraps the
    attention calls in, sized against the engine's ``serve/step``
    dispatch spans."""
    steps = agg["spans"].get("serve/step")
    attn = agg["spans"].get("serve/attn")
    backend = agg.get("serves", {}).get("serve/backend", {}).get("backend")
    if not steps and not attn and backend is None:
        return None
    total_step = sum(steps) if steps else None
    total_attn = sum(attn) if attn else None
    return {
        "backend": backend,
        "steps": len(steps) if steps else 0,
        "total_step_ms": round(total_step, 3) if total_step else None,
        "attn_spans": len(attn) if attn else 0,
        "total_attn_ms": round(total_attn, 3) if total_attn else None,
        "attn_fraction_of_step": (round(total_attn / total_step, 4)
                                  if total_attn and total_step else None),
    }


def _prefix_cache_summary(agg):
    """Prefix-cache reuse digest from the ``serve/prefix_*`` events, plus
    the frozen ``serve/prefix_hit_rate`` gauge when ``health()`` pushed
    one (the gauge is exact — page-level hit rate over every lookup; the
    event-derived fields count only admitted requests)."""
    serves = agg.get("serves", {})
    hits = serves.get("serve/prefix_hit", {})
    admits = serves.get("serve/admit", {}).get("count", 0)
    if not hits and "serve/prefix_hit_rate" not in agg["gauges"]:
        return None
    rate = agg["gauges"].get("serve/prefix_hit_rate", {}).get("last")
    return {
        "requests_with_hits": hits.get("count", 0),
        "admitted": admits,
        "request_hit_fraction": (round(hits.get("count", 0) / admits, 4)
                                 if admits else None),
        "pages_reused": hits.get("pages_reused", 0),
        "tokens_reused": hits.get("tokens_reused", 0),
        "cow_copies": serves.get("serve/prefix_cow", {}).get("count", 0),
        "pages_inserted": serves.get("serve/prefix_insert",
                                     {}).get("pages", 0),
        "evictions": serves.get("serve/prefix_evict", {}).get("count", 0),
        "page_hit_rate_gauge": rate,
    }


def _scheduler_summary(agg):
    """Scheduler-plane digest from the ``serve/sched`` announcement and
    the chunked policy's ``serve/prefill_chunk`` / ``serve/spec_*``
    events: chunks-per-prefill, the prefill/decode interleave ratio,
    speculative acceptance, and per-SLO-class TTFT/TPOT percentiles from
    the reconstructed request traces.  None when the stream predates the
    scheduler plane (no ``serve/sched`` event and no chunk events)."""
    serves = agg.get("serves", {})
    sched = serves.get("serve/sched", {})
    chunks = serves.get("serve/prefill_chunk", {})
    verify = serves.get("serve/spec_verify", {})
    if not sched and not chunks:
        return None
    by_req = chunks.get("by_req", {})
    n_chunks = chunks.get("count", 0)
    # decode work from the closed traces: every generated token was one
    # decode-step's worth of output for that slot
    traces = agg.get("requests") or []
    decode_tokens = sum(int(t.get("n_generated") or 0) for t in traces
                        if t.get("terminal"))
    accepted = verify.get("accepted", 0)
    rejected = verify.get("rejected", 0)
    by_class = {}
    for t in traces:
        cls = t.get("slo_class")
        if cls is None:
            continue
        rec = by_class.setdefault(cls, {"requests": 0, "ttft_ms": [],
                                        "tpot_ms": []})
        rec["requests"] += 1
        for k in ("ttft_ms", "tpot_ms"):
            if t.get(k) is not None:
                rec[k].append(float(t[k]))
    class_rows = {}
    for cls, rec in sorted(by_class.items()):
        row = {"requests": rec["requests"]}
        for k in ("ttft_ms", "tpot_ms"):
            vals = sorted(rec[k])
            row[k] = ({"p50": round(_pct(vals, 50), 3),
                       "p90": round(_pct(vals, 90), 3),
                       "p99": round(_pct(vals, 99), 3)}
                      if vals else None)
        class_rows[cls] = row
    return {
        "policy": sched.get("policy"),
        "config": sched.get("attrs"),
        "prefill_chunks": n_chunks,
        "prefill_chunk_tokens": chunks.get("tokens", 0),
        "prefills_chunked": len(by_req),
        "chunks_per_prefill": (round(n_chunks / len(by_req), 3)
                               if by_req else None),
        # share of cache-writing dispatches that were prefill chunks —
        # how much decode had to share the step loop with prefill
        "interleave_ratio": (round(n_chunks / (n_chunks + decode_tokens),
                                   4)
                             if n_chunks + decode_tokens else None),
        "spec_windows": serves.get("serve/spec_draft", {}).get("count", 0),
        "spec_accepted": accepted,
        "spec_rejected": rejected,
        "spec_acceptance_rate": (round(accepted / (accepted + rejected), 4)
                                 if accepted + rejected else None),
        "slo_classes": class_rows,
    }


# a warm prefetch queue pops in microseconds — any input wait past this is
# a dispatch stall (the feed couldn't keep ahead of compute)
STALL_WAIT_MS = 1.0


def _input_feed_summary(agg):
    """Input-wait / dispatch-stall digest from the ``engine/input_wait``
    spans (emitted around the prefetched-batch pop when the async pipeline
    is on), sized against total ``engine/train_batch`` time."""
    waits = agg["spans"].get("engine/input_wait")
    if not waits:
        return None
    vals = sorted(waits)
    total_wait = sum(vals)
    total_step = sum(agg["spans"].get("engine/train_batch", [])) or None
    return {
        "waits": len(vals),
        "total_wait_ms": round(total_wait, 3),
        "mean_ms": round(total_wait / len(vals), 3),
        "p50_ms": round(_pct(vals, 50), 3),
        "p99_ms": round(_pct(vals, 99), 3),
        "max_ms": round(vals[-1], 3),
        "stalled_steps": sum(1 for v in vals if v > STALL_WAIT_MS),
        "stall_threshold_ms": STALL_WAIT_MS,
        "wait_fraction_of_step": (round(total_wait / total_step, 4)
                                  if total_step else None),
    }


def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024.0
    return f"{n}"


def print_tables(summary, out=sys.stdout):
    w = out.write
    if summary["spans"]:
        w("== span latency (ms) ==\n")
        w(f"{'span':<36}{'count':>7}{'mean':>10}{'p50':>10}"
          f"{'p90':>10}{'p99':>10}{'max':>10}\n")
        for name, r in summary["spans"].items():
            w(f"{name:<36}{r['count']:>7}{r['mean_ms']:>10}{r['p50_ms']:>10}"
              f"{r['p90_ms']:>10}{r['p99_ms']:>10}{r['max_ms']:>10}\n")
        w("\n")
    if summary["comms"]:
        w("== comm census (traced calls) ==\n")
        w(f"{'op':<24}{'calls':>7}{'bytes':>14}{'dur_ms':>12}"
          f"{'GB/s':>9}  axes\n")
        for op, r in summary["comms"].items():
            bw = r.get("achieved_gbps")
            w(f"{op:<24}{r['calls']:>7}{_fmt_bytes(r['bytes']):>14}"
              f"{r.get('dur_ms', 0.0):>12}"
              f"{bw if bw is not None else '-':>9}  "
              f"{','.join(r['axes'])}\n")
        w("\n")
    if summary["gauges"]:
        w("== gauges (last / peak) ==\n")
        w(f"{'gauge':<36}{'last':>16}{'peak':>16}{'samples':>9}\n")
        for name, r in summary["gauges"].items():
            last, peak = r["last"], r["peak"]
            if name.startswith("hbm/"):
                last, peak = _fmt_bytes(last), _fmt_bytes(peak)
            else:
                last = round(last, 4) if isinstance(last, float) else last
                peak = round(peak, 4) if isinstance(peak, float) else peak
            w(f"{name:<36}{last:>16}{peak:>16}{r['samples']:>9}\n")
        w("\n")
    prof = summary.get("profiling")
    if prof:
        comp = prof["compile"]
        w("== profiling: compile tracing ==\n")
        w(f"jit cache misses: {comp['total_misses']}  "
          f"storms: {comp['storms']}\n")
        if comp["sites"]:
            w(f"{'site':<32}{'misses':>7}{'dur_ms':>12}  causes\n")
            for site, r in comp["sites"].items():
                causes = ", ".join(f"{k}={v}"
                                   for k, v in r["causes"].items())
                w(f"{site:<32}{r['misses']:>7}{r['dur_ms']:>12}  "
                  f"{causes}\n")
        w("\n")
        if prof["mem"]:
            w("== profiling: HBM attribution (peak per span) ==\n")
            w(f"{'span':<16}{'live':>12}{'peak':>12}{'frag':>12}\n")
            for span, metrics in sorted(prof["mem"].items()):
                cells = []
                for m in ("live_bytes", "peak_bytes", "frag_bytes"):
                    rec = metrics.get(m)
                    cells.append(_fmt_bytes(rec["peak"]) if rec else "-")
                w(f"{span:<16}{cells[0]:>12}{cells[1]:>12}"
                  f"{cells[2]:>12}\n")
            w("\n")
        if prof["roofline"]:
            w("== profiling: live roofline (fraction of peak) ==\n")
            w(f"{'span':<16}{'compute':>10}{'bandwidth':>11}\n")
            for span, metrics in sorted(prof["roofline"].items()):
                cells = []
                for m in ("compute_frac", "bandwidth_frac"):
                    rec = metrics.get(m)
                    cells.append(f"{rec['last'] * 100:.1f}%"
                                 if rec and isinstance(
                                     rec["last"], (int, float)) else "-")
                w(f"{span:<16}{cells[0]:>10}{cells[1]:>11}\n")
            w("\n")
    at = summary.get("attribution")
    if at:
        w("== attribution ==\n")
        if at.get("step"):
            w("step decomposition (last / peak):\n")
            for name, r in at["step"].items():
                w(f"  {name:<20}{r['last']:>12}{r['peak']:>12}\n")
        sv = at.get("serving")
        if sv:
            w(f"requests attributed: {sv['requests']} "
              f"({sv['migrated']} migrated)  "
              f"e2e total: {sv['e2e_ms']} ms\n")
            w(f"{'stage':<12}{'total_ms':>12}{'share':>8}\n")
            for k, r in sv["stages"].items():
                share = (f"{r['frac'] * 100:.1f}%"
                         if r["frac"] is not None else "-")
                w(f"{k[:-3]:<12}{r['total_ms']:>12}{share:>8}\n")
        w("\n")
    ov = summary.get("overlap")
    if ov:
        w("== comm/compute overlap ==\n")
        w(f"{'gauge':<18}{'last':>12}{'peak':>12}\n")
        for name, r in ov["gauges"].items():
            w(f"{name:<18}{r['last']:>12}{r['peak']:>12}\n")
        if ov["exposed_comm_frac"] is not None:
            w(f"exposed comm fraction (step/attr): "
              f"{ov['exposed_comm_frac']}\n")
        w("\n")
    tiered = summary.get("tiered")
    if tiered:
        w("== tiered memory ==\n")
        w(f"{'gauge':<20}{'last':>14}{'peak':>14}\n")
        for name, r in tiered["gauges"].items():
            last, peak = r["last"], r["peak"]
            if name.endswith("_bytes") or name == "quant_bytes_saved":
                last, peak = _fmt_bytes(last), _fmt_bytes(peak)
            w(f"{name:<20}{last:>14}{peak:>14}\n")
        if tiered["prefetch_hit_rate"] is not None:
            w(f"prefetch hit rate: "
              f"{tiered['prefetch_hit_rate'] * 100:.1f}%\n")
        w("\n")
    feed = summary.get("input_feed")
    if feed:
        w("== input feed (engine/input_wait) ==\n")
        w(f"waits: {feed['waits']}  total: {feed['total_wait_ms']} ms  "
          f"mean: {feed['mean_ms']}  p50: {feed['p50_ms']}  "
          f"p99: {feed['p99_ms']}  max: {feed['max_ms']}\n")
        w(f"dispatch stalls (> {feed['stall_threshold_ms']} ms): "
          f"{feed['stalled_steps']}")
        if feed["wait_fraction_of_step"] is not None:
            w(f"  |  wait fraction of train_batch: "
              f"{feed['wait_fraction_of_step'] * 100:.2f}%")
        w("\n\n")
    serving = summary.get("serving")
    if serving:
        w("== serving events ==\n")
        w(f"{'event':<24}{'count':>7}  reasons\n")
        for name, r in serving.items():
            reasons = ", ".join(f"{k}={v}" for k, v in r["reasons"].items())
            w(f"{name:<24}{r['count']:>7}  {reasons}\n")
        w("\n")
    fleet = summary.get("fleet")
    if fleet:
        w("== fleet events ==\n")
        w(f"{'event':<24}{'count':>7}  replicas | reasons\n")
        for name, r in fleet.items():
            parts = []
            if r["replicas"]:
                parts.append(",".join(r["replicas"]))
            if r["reasons"]:
                parts.append(", ".join(f"{k}={v}"
                                       for k, v in r["reasons"].items()))
            w(f"{name:<24}{r['count']:>7}  {' | '.join(parts)}\n")
        w("\n")
    tp = summary.get("fleet_transport")
    if tp:
        w("== fleet transport ==\n")
        retries = ", ".join(f"{k}={v}" for k, v in
                            tp["retries_by_op"].items()) or "-"
        w(f"retries: {tp['retries']}  by op: {retries}\n")
        if tp["retry_elapsed_p50_s"] is not None:
            w(f"elapsed at retry: p50 {tp['retry_elapsed_p50_s']}s  "
              f"p99 {tp['retry_elapsed_p99_s']}s\n")
        w(f"breaker: {tp['breaker_opens']} open, "
          f"{tp['breaker_closes']} close\n")
        if tp["breakers"]:
            w(f"{'replica':<12}{'opens':>7}{'closes':>8}\n")
            for rid, b in tp["breakers"].items():
                w(f"{rid:<12}{b['opens']:>7}{b['closes']:>8}\n")
        drops = ", ".join(f"{k}={v}" for k, v in
                          tp["drops_by_op"].items()) or "-"
        w(f"duplicate calls dropped: {tp['dup_calls_dropped']}  "
          f"by op: {drops}\n")
        w("\n")
    tune = summary.get("autotuning")
    if tune:
        w("== autotuning ==\n")
        w(f"trials: {tune['trials_run']} run, {tune['trials_pruned']} "
          f"pruned  |  ledger rows written: "
          f"{tune['ledger_rows_written']}\n")
        w(f"{'trial':<12}{'objective':>14}  knobs\n")

        def _kn(raw):
            if isinstance(raw, dict):
                return ", ".join(f"{k}={v}" for k, v in raw.items())
            return str(raw or "")

        for r in tune["trials"]:
            obj = (f"{r['objective']:.3f}"
                   if isinstance(r["objective"], (int, float)) else "-")
            w(f"{str(r['trial']):<12}{obj:>14}  {_kn(r['knobs'])}\n")
        for r in tune["pruned"]:
            w(f"{str(r['trial']):<12}{'pruned':>14}  {_kn(r['knobs'])}"
              f"  [{r['reason']}]\n")
        win = tune.get("winner")
        if win:
            w(f"winner: {win['trial']}  knobs: {_kn(win.get('knobs'))}\n")
        if (tune.get("overlay") or {}).get("path"):
            w(f"overlay: {tune['overlay']['path']}\n")
        w("\n")
    dis = summary.get("fleet_disagg")
    if dis:
        w("== disaggregated fleet ==\n")
        w(f"{'role':<10}{'replicas':<20}{'queue':>6}\n")
        for role, rids in dis["roles"].items():
            q = dis["queue_depth"].get(role)
            w(f"{role:<10}{','.join(rids):<20}"
              f"{q if q is not None else '?':>6}\n")
        quant = (f"  quant bytes saved: {dis['quant_bytes_saved']}"
                 if dis.get("quant_bytes_saved") else "")
        w(f"migrations: {dis['migrations']}  "
          f"pages migrated: {dis['migrated_pages']}  "
          f"dedup skipped: {dis['dedup_skipped_pages']}  "
          f"bytes saved: {dis['bytes_saved']}{quant}\n")
        extras = []
        if dis["faults"]:
            extras.append("faults: " + ", ".join(
                f"{k}={v}" for k, v in dis["faults"].items()))
        if dis["aborts"]:
            extras.append("aborts: " + ", ".join(
                f"{k}={v}" for k, v in dis["aborts"].items()))
        if dis["local_prefills"]:
            extras.append(
                f"local prefills (degraded): {dis['local_prefills']}")
        if extras:
            w("  |  ".join(extras) + "\n")
        w("\n")
    sa = summary.get("serving_attention")
    if sa:
        w("== serving attention ==\n")
        w(f"backend: {sa['backend'] or '?'}  "
          f"steps: {sa['steps']}  "
          f"total step: {sa['total_step_ms']} ms\n")
        w(f"attn spans: {sa['attn_spans']}  "
          f"total attn: {sa['total_attn_ms']} ms")
        if sa["attn_fraction_of_step"] is not None:
            w(f"  |  attention share of serve-step: "
              f"{sa['attn_fraction_of_step'] * 100:.1f}%")
        w("\n\n")
    pc = summary.get("prefix_cache")
    if pc:
        w("== prefix cache ==\n")
        frac = pc["request_hit_fraction"]
        w(f"requests with hits: {pc['requests_with_hits']}"
          f"/{pc['admitted']} admitted"
          + (f" ({frac * 100:.1f}%)" if frac is not None else "") + "\n")
        w(f"pages reused: {pc['pages_reused']}  "
          f"tokens reused: {pc['tokens_reused']}  "
          f"cow copies: {pc['cow_copies']}\n")
        w(f"pages inserted: {pc['pages_inserted']}  "
          f"evictions: {pc['evictions']}")
        if pc["page_hit_rate_gauge"] is not None:
            w(f"  |  page hit rate (gauge): "
              f"{pc['page_hit_rate_gauge'] * 100:.1f}%")
        w("\n\n")
    sc = summary.get("scheduler")
    if sc:
        w("== scheduler ==\n")
        w(f"policy: {sc['policy'] or '?'}")
        cfg = sc.get("config") or {}
        if cfg.get("prefill_chunk_tokens"):
            w(f"  chunk: {cfg['prefill_chunk_tokens']} tok")
        if cfg.get("speculative"):
            w(f"  speculative: gamma={cfg.get('num_draft_tokens', '?')}")
        w("\n")
        if sc["prefill_chunks"]:
            w(f"prefill chunks: {sc['prefill_chunks']} "
              f"({sc['prefill_chunk_tokens']} tok) over "
              f"{sc['prefills_chunked']} prefills")
            if sc["chunks_per_prefill"] is not None:
                w(f"  |  chunks/prefill: {sc['chunks_per_prefill']}")
            if sc["interleave_ratio"] is not None:
                w(f"  |  interleave: "
                  f"{sc['interleave_ratio'] * 100:.1f}%")
            w("\n")
        if sc["spec_accepted"] or sc["spec_rejected"]:
            w(f"speculative: {sc['spec_windows']} windows  "
              f"accepted {sc['spec_accepted']}  "
              f"rejected {sc['spec_rejected']}")
            if sc["spec_acceptance_rate"] is not None:
                w(f"  |  acceptance: "
                  f"{sc['spec_acceptance_rate'] * 100:.1f}%")
            w("\n")
        if sc["slo_classes"]:
            w(f"{'slo class':<14}{'reqs':>6}{'ttft p50':>10}"
              f"{'ttft p90':>10}{'ttft p99':>10}{'tpot p50':>10}"
              f"{'tpot p99':>10}\n")
            for cls, row in sc["slo_classes"].items():
                ttft = row.get("ttft_ms") or {}
                tpot = row.get("tpot_ms") or {}
                w(f"{cls:<14}{row['requests']:>6}"
                  f"{ttft.get('p50', '-'):>10}{ttft.get('p90', '-'):>10}"
                  f"{ttft.get('p99', '-'):>10}{tpot.get('p50', '-'):>10}"
                  f"{tpot.get('p99', '-'):>10}\n")
        w("\n")
    rl = summary.get("request_latency")
    if rl:
        w("== request latency (serve/request/* traces) ==\n")
        terms = ", ".join(f"{k}={v}" for k, v in rl["terminals"].items())
        w(f"traces: {rl['traces']}  terminals: {terms}\n")
        if rl["orphans"]:
            w(f"OPEN TRACES (no terminal yet): {rl['orphans']}\n")
        if rl["slo"]["ok"] or rl["slo"]["miss"]:
            total = rl["slo"]["ok"] + rl["slo"]["miss"]
            w(f"slo: {rl['slo']['ok']}/{total} attained "
              f"({rl['slo']['ok'] / total * 100:.1f}%)\n")
        if rl["latency"]:
            w(f"{'latency (ms)':<20}{'count':>7}{'p50':>10}{'p90':>10}"
              f"{'p99':>10}{'max':>10}\n")
            for name, r in rl["latency"].items():
                w(f"{name:<20}{r['count']:>7}{r['p50']:>10}{r['p90']:>10}"
                  f"{r['p99']:>10}{r['max']:>10}\n")
        if rl["slowest"]:
            w(f"slowest requests (by e2e, top {len(rl['slowest'])}):\n")
            w(f"{'req_id':<12}{'terminal':<10}{'slot':>5}{'gen':>5}"
              f"{'queue':>9}{'ttft':>9}{'tpot':>9}{'e2e':>10}  slo\n")
            for t in rl["slowest"]:
                w(f"{str(t.get('req_id', '?')):<12}"
                  f"{t.get('terminal', '?'):<10}"
                  f"{t.get('slot', '-'):>5}{t.get('n_generated', 0):>5}"
                  f"{t.get('queue_wait_ms', '-'):>9}"
                  f"{t.get('ttft_ms', '-'):>9}{t.get('tpot_ms', '-'):>9}"
                  f"{t.get('e2e_ms', '-'):>10}  {t.get('slo', '-')}\n")
        w("\n")
    cl = summary.get("cluster")
    if cl:
        w(f"== cluster ({cl['ranks']} ranks, "
          f"{cl['aligned_steps']} aligned steps) ==\n")
        w(f"{'rank':<6}{'steps':>7}{'median step ms':>16}\n")
        for r, row in sorted(cl["per_rank"].items(), key=lambda kv:
                             int(kv[0])):
            med = row["median_step_ms"]
            w(f"{r:<6}{row['steps']:>7}"
              f"{med if med is not None else '-':>16}\n")
        skew = cl["step_skew_ms"]
        w(f"step skew: p50 {skew['p50']} ms  max {skew['max']} ms")
        if cl["worst_rel"] is not None:
            w(f"  |  slowest rank vs median: {cl['worst_rel']:.2f}x")
        w("\n\n")
    hb = summary["heartbeat"]
    w(f"== heartbeat ==\nsteps: {hb['steps']}  "
      f"median step: {hb['median_step_ms']} ms\n\n")
    if summary["stalls"]:
        w(f"== stalls ({len(summary['stalls'])}) ==\n")
        for s in summary["stalls"]:
            w(f"  step {s.get('step')}: gap {s.get('gap_s')}s "
              f"(median {s.get('median_step_s')}s, "
              f"threshold {s.get('threshold_s')}s)\n")
    else:
        w("== stalls ==\nnone\n")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Aggregate a telemetry JSONL stream into tables.")
    ap.add_argument("target",
                    help="telemetry dir (containing events.jsonl) or the "
                         "events.jsonl path itself")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as JSON instead of tables")
    args = ap.parse_args(argv)
    files = discover_files(args.target)
    if not files:
        print(f"no events.jsonl under {args.target!r}", file=sys.stderr)
        return 1
    events = list(load_events(files))
    if not events and os.path.isdir(args.target):
        # a shard dir holding only torn/empty events.rank*.jsonl files
        # must not take the report down with it: degrade to the
        # single-stream events.jsonl path with a warning
        single = [
            p for p in
            _with_rotations(os.path.join(args.target, "events.jsonl"))
            if p not in files]
        if single:
            print("WARN: shard files held no parseable events; falling "
                  "back to the single-stream events.jsonl",
                  file=sys.stderr)
            events = list(load_events(single))
    summary = summarize(aggregate(events))
    if args.json:
        json.dump(summary, sys.stdout, indent=2)
        print()
    else:
        print_tables(summary)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
