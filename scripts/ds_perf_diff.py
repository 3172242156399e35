#!/usr/bin/env python
"""Perf-regression gate over the bench ledger.

``bench.py`` appends one row per micro-bench metric to a JSONL ledger
(``BENCH_LEDGER.jsonl`` by default; schema frozen in
``scripts/check_telemetry_schema.py --ledger``).  This script compares
the LATEST run against the baseline built from every earlier run — the
per-(bench, metric) median, so one noisy historical run cannot shift the
gate — and exits nonzero when any metric regressed beyond tolerance.

Direction is inferred from the metric name: duration/size metrics
(``*_ms``, ``*_s``, ``*_secs``, ``*_bytes``, ``*_time*``) regress by
going UP; throughput metrics (``*per_sec*``, ``*gbps*``, ``*rate*``,
``*frac*``, ``*tokens*``, ``*flops*``) regress by going DOWN.  Unknown
directions are reported but never gate.

Usage:
    python scripts/ds_perf_diff.py [LEDGER] [--tolerance 0.25] [--json]
    python scripts/ds_perf_diff.py --check [LEDGER]

``--check`` is the CI entry point: it behaves identically when a usable
ledger exists (>= 2 runs) but exits 0 — with a note — when the ledger is
missing or still single-run, so the gate can ride in the tier-1 flow
before any baseline has been seeded.

``--check`` additionally audits baseline FRESHNESS: when the newest
on-chip train evidence (the latest ``bench == "train"`` ledger row, or
``captured_unix`` in the file ``--onchip`` names) is older than the last
``--stale-runs`` cpu-only bench runs, it prints an explicit
``STALE-BASELINE`` warning — the cpu gate keeps ratcheting while the
on-chip numbers it is meant to stand in for go quietly out of date.
The warning never changes the exit code; it is a prompt to re-run the
on-chip bench, not a gate.

Exit codes: 0 ok / skipped, 1 regression(s), 2 usage or malformed ledger.
"""

import argparse
import importlib.util
import json
import os
import sys

DEFAULT_LEDGER = os.environ.get(
    "BENCH_LEDGER",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "BENCH_LEDGER.jsonl"))

# exact-name direction overrides, checked BEFORE the substring
# heuristics: the attribution plane's exposed-comm fraction is a "frac"
# the heuristics would read as higher-is-better, but exposed collective
# time is pure loss — and every critical-path stage scalar is a
# millisecond cost even where the suffix heuristic can't see it.
_DIRECTION_OVERRIDES = {
    "exposed_comm_frac": "down",
    "exposed_comm_ms": "down",
    "host_sync_ms": "down",
    "input_wait_ms": "down",
    "queue_ms": "down",
    "migrate_ms": "down",
    "gap_ms": "down",
}

# metric-name direction heuristics: substring/suffix -> True when lower
# is better.  Checked in order; first hit wins.
_LOWER_BETTER = ("_ms", "_s", "_secs", "_seconds", "_bytes")
_HIGHER_BETTER = ("per_sec", "gbps", "rate", "frac", "tokens", "flops",
                  "mfu", "hits")


def _load_checker():
    """Sibling-module import of check_telemetry_schema (scripts/ is not a
    package) for the frozen ledger row schema."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("_ds_schema", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_direction(metric):
    """'down' when lower is better, 'up' when higher is better, None when
    the name matches neither heuristic (such metrics never gate)."""
    m = metric.lower()
    for name, direction in _DIRECTION_OVERRIDES.items():
        if m == name or m.endswith("_" + name):
            return direction
    for pat in _HIGHER_BETTER:
        if pat in m:
            return "up"
    if "time" in m:
        return "down"
    for pat in _LOWER_BETTER:
        if m.endswith(pat):
            return "down"
    return None


def load_ledger(path):
    """Parse + schema-check the ledger.  Returns (rows, problems)."""
    checker = _load_checker()
    rows, problems = [], []
    with open(path) as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError as e:
                problems.append(f"{path}:{i}: not valid JSON: {e}")
                continue
            bad = checker.validate_ledger_row(row)
            if bad:
                problems.extend(f"{path}:{i}: {p}" for p in bad)
                continue
            rows.append(row)
    return rows, problems


def _median(vals):
    vals = sorted(vals)
    n = len(vals)
    mid = n // 2
    return vals[mid] if n % 2 else (vals[mid - 1] + vals[mid]) / 2.0


def split_runs(rows):
    """(baseline_rows, current_rows, current_run) — runs ordered by first
    appearance (appends are chronological); the last run is the
    candidate, everything earlier is baseline.  ``tune-*`` runs (the
    autotuner's per-trial rows) are never the candidate: each one
    measures a DIFFERENT knob point, so trial-vs-trial deltas are search
    results, not regressions — they ride as baseline history only and
    the tuned-vs-default verdict gates via the ``cpu_autotune`` summary
    rows of the surrounding bench run instead."""
    order = []
    for row in rows:
        if row["run"] not in order:
            order.append(row["run"])
    candidates = [r for r in order if not r.startswith("tune-")]
    if len(order) < 2 or not candidates:
        return [], [], order[-1] if order else None
    current = candidates[-1]
    return ([r for r in rows if r["run"] != current],
            [r for r in rows if r["run"] == current], current)


def diff(baseline_rows, current_rows, tolerance):
    """Compare the current run against per-(bench, metric) baseline
    medians.  Returns a list of row dicts with verdicts."""
    base = {}
    for row in baseline_rows:
        base.setdefault((row["bench"], row["metric"]), []).append(
            float(row["value"]))
    results = []
    for row in current_rows:
        key = (row["bench"], row["metric"])
        cur = float(row["value"])
        rec = {"bench": row["bench"], "metric": row["metric"],
               "current": cur, "baseline": None, "change": None,
               "direction": metric_direction(row["metric"]),
               "verdict": "no_baseline"}
        if key in base:
            med = _median(base[key])
            rec["baseline"] = med
            if med != 0:
                change = (cur - med) / abs(med)
                rec["change"] = change
                if rec["direction"] == "down" and change > tolerance:
                    rec["verdict"] = "regression"
                elif rec["direction"] == "up" and change < -tolerance:
                    rec["verdict"] = "regression"
                elif rec["direction"] is None:
                    rec["verdict"] = "ungated"
                else:
                    rec["verdict"] = "ok"
            else:
                rec["verdict"] = "ok" if cur == 0 else "ungated"
        results.append(rec)
    return results


def check_stale_baseline(rows, onchip_path, stale_runs):
    """Return a STALE-BASELINE warning string, or None when the on-chip
    evidence is still fresh (or there are not yet ``stale_runs`` cpu-only
    runs to judge against).

    Evidence of an on-chip run is the newest of (a) any ``bench ==
    "train"`` ledger row's ts and (b) ``captured_unix`` inside
    ``onchip_path``.  A run counts as cpu-only when none of its rows is a
    train metric."""
    train_ts = max((float(r["ts"]) for r in rows if r["bench"] == "train"),
                   default=None)
    onchip_ts = None
    if onchip_path and os.path.exists(onchip_path):
        try:
            with open(onchip_path) as f:
                cap = json.load(f).get("captured_unix")
            if isinstance(cap, (int, float)) and not isinstance(cap, bool):
                onchip_ts = float(cap)
        except (ValueError, OSError):
            pass
    evidence = [t for t in (train_ts, onchip_ts) if t is not None]
    evidence_ts = max(evidence) if evidence else None

    order, first_ts, has_train = [], {}, set()
    for row in rows:
        run = row["run"]
        if run not in first_ts:
            order.append(run)
            first_ts[run] = float(row["ts"])
        if row["bench"] == "train":
            has_train.add(run)
    cpu_runs = [r for r in order if r not in has_train]
    recent = cpu_runs[-stale_runs:]
    if len(recent) < stale_runs:
        return None
    if evidence_ts is None:
        no_file = f", no {onchip_path}" if onchip_path else ""
        return (f"STALE-BASELINE: no on-chip train evidence at all (no "
                f"train ledger rows{no_file}) behind the last "
                f"{stale_runs} cpu bench run(s) — the cpu gate has "
                f"nothing on-chip to stand in for; re-run the on-chip "
                f"train bench (ROADMAP.md open follow-up: 'Re-measure "
                f"on-chip training' — a fresh on-chip row is still owed)")
    if all(first_ts[r] > evidence_ts for r in recent):
        return (f"STALE-BASELINE: newest on-chip train evidence "
                f"(ts {evidence_ts:.0f}) predates the last {stale_runs} "
                f"cpu bench run(s) (oldest at ts "
                f"{min(first_ts[r] for r in recent):.0f}) — cpu gating "
                f"may have drifted from hardware reality; re-run the "
                f"on-chip train bench (ROADMAP.md open follow-up: "
                f"'Re-measure on-chip training' — a fresh on-chip row "
                f"is still owed)")
    return None


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Gate the latest bench run against the ledger "
                    "baseline.")
    ap.add_argument("ledger", nargs="?", default=DEFAULT_LEDGER,
                    help=f"ledger path (default {DEFAULT_LEDGER})")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed fractional change in the bad direction "
                         "(default 0.25)")
    ap.add_argument("--check", action="store_true",
                    help="CI mode: exit 0 when the ledger is missing or "
                         "has no baseline yet")
    ap.add_argument("--stale-runs", type=int, default=3,
                    help="warn STALE-BASELINE when the newest on-chip "
                         "train evidence is older than this many cpu "
                         "runs (default 3; --check only)")
    ap.add_argument("--onchip", default=None,
                    help="on-chip evidence file: JSON with captured_unix "
                         "(default: the ledger's train rows only)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full diff as JSON")
    args = ap.parse_args(argv)

    if not os.path.exists(args.ledger):
        if args.check:
            print(f"perf-diff: no ledger at {args.ledger} — skipping "
                  f"(seed one with bench.py)")
            return 0
        print(f"perf-diff: ledger not found: {args.ledger}",
              file=sys.stderr)
        return 2
    rows, problems = load_ledger(args.ledger)
    if problems:
        for p in problems:
            print(p, file=sys.stderr)
        return 2
    if args.check:
        warn = check_stale_baseline(rows, args.onchip, args.stale_runs)
        if warn:
            print(warn)
    baseline_rows, current_rows, current = split_runs(rows)
    if not current_rows:
        msg = (f"perf-diff: ledger has "
               f"{'one run' if current else 'no runs'} — no baseline to "
               f"compare against")
        if args.check:
            print(msg + " — skipping")
            return 0
        print(msg, file=sys.stderr)
        return 2

    results = diff(baseline_rows, current_rows, args.tolerance)
    regressions = [r for r in results if r["verdict"] == "regression"]
    if args.json:
        json.dump({"run": current, "tolerance": args.tolerance,
                   "results": results,
                   "regressions": len(regressions)},
                  sys.stdout, indent=2)
        print()
    else:
        print(f"perf-diff: run {current!r} vs median of "
              f"{len({r['run'] for r in baseline_rows})} baseline run(s), "
              f"tolerance {args.tolerance:.0%}")
        print(f"{'bench':<26}{'metric':<26}{'baseline':>12}"
              f"{'current':>12}{'change':>9}  verdict")
        for r in sorted(results, key=lambda r: (r["bench"], r["metric"])):
            base = ("-" if r["baseline"] is None
                    else f"{r['baseline']:.4g}")
            change = ("-" if r["change"] is None
                      else f"{r['change']:+.1%}")
            print(f"{r['bench']:<26}{r['metric']:<26}{base:>12}"
                  f"{r['current']:>12.4g}{change:>9}  {r['verdict']}")
    if regressions:
        print(f"FAIL: {len(regressions)} metric(s) regressed beyond "
              f"{args.tolerance:.0%}")
        return 1
    print("OK: no regressions")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
