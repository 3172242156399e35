#!/usr/bin/env python
"""Chaos campaign runner for the cross-process fleet (run_gates.sh's "chaos smoke").

Sweeps gray-failure scenarios over a REAL 2-worker subprocess fleet on
the deterministic ``tiny_engine_factory`` spec, with every fault driven
by the seeded :class:`WireFaultInjector` (``serving.fleet.transport.
chaos``) — the whole campaign replays from ``(scenario, seed)`` alone,
no wall-clock races.  Each scenario must end with:

* ZERO lost requests — every submitted id reaches exactly one typed
  tracer terminal (``finished`` xor ``pop_terminated``);
* an empty fleet ``leak_report()``;
* survivors BIT-IDENTICAL to the no-fault in-process reference (a
  request's output depends only on prompt/params/seed, never on which
  replica, retry, or dispatch attempt served it);
* the scenario's own expectations (retries absorbed, breaker opened
  and closed without a respawn, duplicate calls dropped, exactly one
  committed migration, ...);
* a schema-clean telemetry stream (``check_telemetry_schema.py`` over
  the run's events.jsonl).

Scenarios::

    ack_loss      worker admits, the ack frame is dropped — the channel
                  retry replays under the same idempotency key and the
                  worker dedups (one admission, one terminal)
    dup_dispatch  the add_request frame is duplicated on the wire — the
                  worker's call-id cache resends the cached response
                  instead of double-admitting
    slow_worker   consecutive step timeouts trip the per-replica
                  circuit breaker: fenced WITHOUT a kill, half-open
                  probe rejoins, zero respawns
    torn_commit   the commit_import ack is dropped mid-migration — the
                  retried commit converges exactly-once (one committed
                  migration, source unpinned once)
    reorder       a step reply is held back past its call's timeout —
                  the late frame is discarded by call id and the
                  cumulative ack redelivers the work
    flap          a link that fails every Nth call — breaker hysteresis
                  (doubling cooldown inside the flap window) keeps the
                  fleet from respawn-storming

Usage::

    python scripts/ds_chaos.py --scenarios ack_loss,slow_worker
    python scripts/ds_chaos.py --scenarios all --seed 7 -v
"""

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

SPEC = {"factory":
        "deepspeed_tpu.inference.fleet_worker:tiny_engine_factory",
        "kwargs": {}}

# Short per-RPC wall budget so an injected drop times out in CI time;
# the heartbeat deadline stays LARGE so the breaker — not heartbeat
# death — owns every gray verdict in these scenarios.
BASE_TRANSPORT = {"mode": "subprocess",
                  "heartbeat_interval_s": 0.2,
                  "heartbeat_deadline_s": 60.0,
                  "call_timeout_s": 30.0}


def _load_checker():
    path = os.path.join(REPO, "scripts", "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _prompts(seed, n=4):
    """Deterministic prompt set sharing a family prefix (exercises the
    prefix cache + migration dedup paths)."""
    import numpy as np

    from deepspeed_tpu.models.transformer import TransformerConfig
    vocab = TransformerConfig.tiny(hidden_size=64, n_heads=4,
                                   n_kv_heads=2).vocab_size
    rng = np.random.default_rng(seed)
    fam = rng.integers(0, vocab, (24,)).tolist()
    return {f"c{i}": fam + rng.integers(0, vocab, (4,)).tolist()
            for i in range(n)}


def _submit_all(router, prompts):
    for rid, p in sorted(prompts.items()):
        router.submit(rid, p, max_new_tokens=6, temperature=0.7, seed=11)


def _drive(router, max_steps=2000, wall_s=180.0, settle=None):
    """Step the fleet until every request resolves (typed terminal or
    finish) AND the optional ``settle`` predicate holds (breaker
    scenarios keep stepping until the half-open probe has decided) —
    bounded by steps AND wall clock so a broken scenario fails loudly
    instead of hanging the gate."""
    deadline = time.monotonic() + wall_s
    for _ in range(max_steps):
        router.step()
        if not router._unresolved() and \
                (settle is None or settle(router)):
            return
        if time.monotonic() > deadline:
            break
    raise AssertionError(
        f"fleet did not converge: {router._unresolved()} unresolved, "
        f"settle={settle is None or settle(router)} "
        f"after {router.steps} steps")


def reference_outputs(prompts, roles=None):
    """No-fault IN-PROCESS reference over the identical factory — the
    bit-identity oracle for every chaos scenario."""
    from deepspeed_tpu.inference.fleet import FleetRouter
    from deepspeed_tpu.inference.fleet_worker import tiny_engine_factory
    fleet = {"replicas": 2, "health_interval": 1000}
    if roles:
        fleet = dict(roles, health_interval=1000)
    router = FleetRouter(tiny_engine_factory, fleet=fleet)
    try:
        _submit_all(router, prompts)
        _drive(router)
        term = router.pop_terminated()
        leaks = router.leak_report()
        assert not term and leaks == {}, \
            f"reference run not clean: term={term} leaks={leaks}"
        return dict(router.finished)
    finally:
        router.close()


def run_scenario(name, seed=0, out_dir=None, verbose=False):
    """Run ONE chaos scenario end to end; returns the result dict
    (stats, events, retry/breaker counters) after asserting the
    zero-loss / exactly-once / bit-identity bar.  Raises
    ``AssertionError`` on any violation."""
    from deepspeed_tpu.inference.fleet import FleetRouter
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig

    scen = SCENARIOS[name]
    prompts = _prompts(seed + 5)
    ref = reference_outputs(prompts, roles=scen.get("roles"))

    transport = dict(BASE_TRANSPORT)
    transport.update(scen.get("transport") or {})
    chaos = {k: dict(v) for k, v in (scen.get("chaos") or {}).items()}
    if chaos:
        chaos["seed"] = seed
    transport["chaos"] = chaos
    fleet = {"replicas": 2, "health_interval": 1000,
             "transport": transport}
    if scen.get("roles"):
        fleet = dict(scen["roles"], health_interval=1000,
                     transport=transport)

    tmp = out_dir or tempfile.mkdtemp(prefix=f"ds_chaos_{name}_")
    tel = Telemetry().configure(TelemetryConfig(
        {"enabled": True, "output_path": tmp, "job_name": name,
         "incidents": {"enabled": True, "cooldown_s": 0.0}}), rank=0)
    t0 = time.monotonic()
    router = FleetRouter(SPEC, fleet=fleet, telemetry=tel)
    try:
        _submit_all(router, prompts)
        _drive(router, settle=scen.get("settle"))
        finished = dict(router.finished)
        term = router.pop_terminated()
        leaks = router.leak_report()
        stats = dict(router.stats)
    finally:
        router.close()
        tel.close()
    elapsed = time.monotonic() - t0

    # -- the campaign bar (every scenario) ----------------------------
    assert leaks == {}, f"{name}: leak_report not empty: {leaks}"
    assert set(finished) | set(term) == set(prompts), \
        f"{name}: lost requests: " \
        f"{set(prompts) - set(finished) - set(term)}"
    assert not (set(finished) & set(term)), \
        f"{name}: double terminal: {set(finished) & set(term)}"
    for rid, toks in finished.items():
        assert toks == ref[rid], \
            f"{name}: {rid} diverged from the no-fault reference"

    # -- schema-clean, expected-event-bearing telemetry ---------------
    events_path = os.path.join(tmp, name, "events.jsonl")
    checker = _load_checker()
    problems = checker.validate_file(events_path)
    assert problems == [], f"{name}: schema problems: {problems[:5]}"
    with open(events_path) as f:
        events = [json.loads(ln) for ln in f if ln.strip()]

    result = {"scenario": name, "seed": seed, "elapsed_s": elapsed,
              "finished": len(finished), "terminated": len(term),
              "stats": stats, "events": events}
    scen["check"](result)
    if out_dir is None:
        shutil.rmtree(tmp, ignore_errors=True)
    if verbose:
        print(f"  stats: retries={stats['retries']} "
              f"rpc_timeouts={stats['rpc_timeouts']} "
              f"breaker={stats['breaker_opens']}/"
              f"{stats['breaker_closes']} "
              f"dup_dropped={stats['dup_calls_dropped']} "
              f"workers_lost={stats['workers_lost']} "
              f"respawns={stats['respawns']}")
    return result


def _count(events, kind, name=None, trigger=None):
    return sum(1 for e in events
               if e.get("kind") == kind
               and (name is None or e.get("name") == name)
               and (trigger is None or e.get("trigger") == trigger))


# -- per-scenario expectations ----------------------------------------
def _check_ack_loss(res):
    st, ev = res["stats"], res["events"]
    assert st["retries"] >= 1, "ack loss never retried"
    assert st["dup_calls_dropped"] >= 1, \
        "worker never deduped the replayed admission"
    assert st["workers_lost"] == 0 and st["respawns"] == 0
    assert _count(ev, "fleet", "fleet/retry") >= 1
    assert _count(ev, "fleet", "fleet/dup_call_dropped") >= 1


def _check_dup_dispatch(res):
    st, ev = res["stats"], res["events"]
    assert st["dup_calls_dropped"] >= 1, \
        "duplicated dispatch was not dropped anywhere"
    assert st["workers_lost"] == 0 and st["respawns"] == 0
    assert _count(ev, "fleet", "fleet/dup_call_dropped") >= 1


def _check_slow_worker(res):
    st, ev = res["stats"], res["events"]
    assert st["breaker_opens"] == 1, \
        f"expected exactly one breaker open, got {st['breaker_opens']}"
    assert st["breaker_closes"] == 1, "breaker never rejoined"
    assert st["workers_lost"] == 0 and st["respawns"] == 0, \
        "a slow worker must NOT be killed or respawned"
    assert _count(ev, "fleet", "fleet/breaker_open") == 1
    assert _count(ev, "fleet", "fleet/breaker_close") == 1
    # breaker/liveness composition: one gray failure, ONE incident
    # bundle — the open fires a breaker_open bundle and heartbeat
    # death stays out of it entirely
    assert _count(ev, "incident", "incident/open",
                  trigger="breaker_open") == 1
    assert _count(ev, "incident", trigger="worker_lost") == 0


def _check_torn_commit(res):
    st, ev = res["stats"], res["events"]
    assert st["migrations"] >= 1, "no migration ever committed"
    assert st["dup_calls_dropped"] >= 1, \
        "torn commit ack was not converged by idempotency-key replay"
    assert st["migrate_commit_faults"] == 0, \
        "channel-level retry should absorb the torn ack before the " \
        "router books a commit fault"
    assert st["workers_lost"] == 0 and st["respawns"] == 0
    # exactly one committed migration per migrated request: commits
    # counted once, and the dup drop proves the retry was a replay
    assert _count(ev, "fleet", "fleet/migrate_commit") == \
        st["migrations"]


def _check_reorder(res):
    st, ev = res["stats"], res["events"]
    assert st["rpc_timeouts"] >= 1, "held frame never timed a call out"
    assert st["dup_calls_dropped"] >= 1, \
        "the late reply should be discarded by call id"
    assert st["workers_lost"] == 0 and st["respawns"] == 0
    assert _count(ev, "fleet", "fleet/dup_call_dropped") >= 1


def _check_flap(res):
    st, ev = res["stats"], res["events"]
    assert st["breaker_opens"] >= 2, \
        f"flapping link should re-trip, got {st['breaker_opens']}"
    assert st["breaker_closes"] >= 1
    assert st["workers_lost"] == 0 and st["respawns"] == 0, \
        "hysteresis must keep a flapping link from respawn-storming"
    opens = [e for e in ev if e.get("kind") == "fleet"
             and e.get("name") == "fleet/breaker_open"]
    cools = [e["attrs"]["cooldown_s"] for e in opens]
    assert cools == sorted(cools) and cools[-1] > cools[0], \
        f"flap cooldowns must escalate, got {cools}"


def _no_open_breakers(router):
    return all(r.state != "breaker_open"
               for r in router.replicas.values())


# Drop scenarios pay one call_timeout_s wall wait per injected drop —
# 8s keeps the campaign fast while staying safely above the worker's
# first-step jit compile (init has its own init_timeout_s budget).
_DROP_TIMEOUT = 8.0

SCENARIOS = {
    # worker admits, ack dropped → channel retry → ikey dedup.  No
    # replica filter: routing affinity may place the first admission on
    # either worker, and the op filter alone is deterministic (the
    # router is single-threaded).
    "ack_loss": {
        "chaos": {"wire_recv": {"drop_at": [0], "ops": ["add_request"]}},
        "transport": {"call_timeout_s": _DROP_TIMEOUT,
                      "retry": {"max_retries": 2, "backoff_s": 0.02,
                                "backoff_max_s": 0.1}},
        "check": _check_ack_loss,
    },
    # request frame duplicated → worker cid-cache resends, router
    # drops the extra reply as stale
    "dup_dispatch": {
        "chaos": {"wire_send": {"dup_at": [0], "ops": ["add_request"]}},
        "transport": {"call_timeout_s": _DROP_TIMEOUT},
        "check": _check_dup_dispatch,
    },
    # two consecutive step timeouts trip the breaker; the half-open
    # ping (not a step — the chaos op filter skips it) rejoins.  The
    # rpc_timeout site fires BEFORE anything is sent, so no wall-clock
    # wait and no counter noise from the other replica's traffic.
    "slow_worker": {
        "chaos": {"rpc_timeout": {"action": "timeout", "times": 2,
                                  "ops": ["step"], "replicas": ["r0"]}},
        "transport": {"retry": {"max_retries": 0},
                      "breaker_failures": 2, "breaker_open_s": 0.2,
                      "breaker_probe_timeout_s": 5.0},
        "settle": lambda r: (r.stats["breaker_closes"] >= 1 and
                             _no_open_breakers(r)),
        "check": _check_slow_worker,
    },
    # disaggregated fleet; the commit_import ACK is dropped — the
    # idempotent retry must converge to exactly one committed
    # migration with the source unpinned exactly once
    "torn_commit": {
        "roles": {"roles": {"enabled": True, "prefill_replicas": 1,
                            "decode_replicas": 1,
                            "page_transfer_budget": 1}},
        "chaos": {"wire_recv": {"drop_at": [0],
                                "ops": ["commit_import"]}},
        "transport": {"call_timeout_s": _DROP_TIMEOUT,
                      "retry": {"max_retries": 2, "backoff_s": 0.02,
                                "backoff_max_s": 0.1}},
        "check": _check_torn_commit,
    },
    # a step reply held past its timeout: the NEXT call's reply
    # releases it and the stale frame is discarded by cid; cumulative
    # acks redeliver the first step's tokens
    "reorder": {
        "chaos": {"wire_recv": {"reorder_at": [0], "ops": ["step"],
                                "replicas": ["r0"]}},
        "transport": {"call_timeout_s": _DROP_TIMEOUT},
        "check": _check_reorder,
    },
    # every 3rd step call to r0 times out: breaker_failures=1 trips
    # instantly, the flap window doubles each cooldown, and the fleet
    # never respawns
    "flap": {
        "chaos": {"rpc_timeout": {"action": "timeout", "every": 3,
                                  "ops": ["step"], "replicas": ["r0"]}},
        "transport": {"retry": {"max_retries": 0},
                      "breaker_failures": 1, "breaker_open_s": 0.05,
                      "breaker_open_max_s": 5.0,
                      "breaker_flap_window_s": 60.0},
        "settle": lambda r: (r.stats["breaker_opens"] >= 2 and
                             _no_open_breakers(r)),
        "check": _check_flap,
    },
}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="deterministic wire-chaos campaign over the "
                    "2-worker subprocess fleet")
    ap.add_argument("--scenarios", default="all",
                    help="comma-separated scenario names, or 'all' "
                         f"(have: {', '.join(SCENARIOS)})")
    ap.add_argument("--seed", type=int, default=0,
                    help="campaign seed (prompts + injector rng)")
    ap.add_argument("--out", default=None,
                    help="keep per-scenario telemetry under this dir")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    names = (list(SCENARIOS) if args.scenarios == "all"
             else [s.strip() for s in args.scenarios.split(",")
                   if s.strip()])
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        ap.error(f"unknown scenarios {unknown} "
                 f"(have: {', '.join(SCENARIOS)})")

    failures = 0
    for name in names:
        print(f"[ds_chaos] {name} (seed {args.seed}) ...", flush=True)
        out_dir = (os.path.join(args.out, name) if args.out else None)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        try:
            res = run_scenario(name, seed=args.seed, out_dir=out_dir,
                               verbose=args.verbose)
        except AssertionError as e:
            failures += 1
            print(f"[ds_chaos] {name}: FAIL — {e}", flush=True)
            continue
        print(f"[ds_chaos] {name}: ok "
              f"({res['finished']} finished, {res['terminated']} "
              f"typed terminals, {res['elapsed_s']:.1f}s)", flush=True)
    if failures:
        print(f"[ds_chaos] {failures}/{len(names)} scenarios FAILED")
        return 1
    print(f"[ds_chaos] campaign green: {len(names)} scenarios, "
          f"zero lost requests, bit-identical survivors")
    return 0


if __name__ == "__main__":
    sys.exit(main())
