"""Serving observability plane tests (PR 7): per-request lifecycle
tracing in the frozen JSONL stream, TTFT/TPOT/e2e/queue-wait SLO
histograms, SLO-attainment/goodput counters, the trace-completeness
invariant in ``leak_report()``, and the pull-based metrics exporter.

The discipline throughout: the registry histograms and the JSONL trace
are two views of ONE measurement — tests assert they agree exactly
(shared percentile convention, engine-clock timestamps)."""

import importlib.util
import json
import os
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.robustness import (RequestRejected,
                                                RequestTracer,
                                                TRACE_TERMINALS)
from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.monitor.export import MetricsExporter, prom_text
from deepspeed_tpu.monitor.telemetry import Histogram, Telemetry
from deepspeed_tpu.runtime.config import (TelemetryConfig,
                                          TelemetryExportConfig)

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, dt=1.0):
        self.t += dt


def _prompts(cfg, seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).tolist() for n in lengths]


def _load_script(name):
    path = os.path.join(REPO, "scripts", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _events(tmp_path, job):
    path = os.path.join(str(tmp_path), job, "events.jsonl")
    return [json.loads(l) for l in open(path) if l.strip()]


def _pct(sorted_vals, q):
    n = len(sorted_vals)
    return sorted_vals[min(n - 1, max(0, int(round(q / 100.0 * (n - 1)))))]


# ----------------------------------------------------------------------
# request lifecycle tracing
# ----------------------------------------------------------------------
def test_trace_lifecycle_exact_latencies(tiny, tmp_path):
    """Two requests through a 1-slot engine on a fake clock: every
    serve/request/* event lands in order with EXACT derived latencies,
    and the registry histograms carry the same values."""
    cfg, model, params = tiny
    clk = FakeClock()
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "trace"}), rank=0)
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=32, dtype=jnp.float32, clock=clk,
                        telemetry=tel)
    pa, pb = _prompts(cfg, 3, [4, 5])
    eng.add_request("a", pa, max_new_tokens=3)   # slot 0 at t=0
    eng.add_request("b", pb, max_new_tokens=3)   # queued behind it
    while eng.queue or eng.n_active:
        clk.tick(1.0)
        eng.step()
    assert eng.leak_report() == {}
    tel.close()

    reqs = [e for e in _events(tmp_path, "trace")
            if e["kind"] == "serve" and
            e["name"].startswith("serve/request/")]
    by = {}
    for e in reqs:
        a = e["attrs"]
        by.setdefault(a["req_id"], []).append(
            (e["name"].rsplit("/", 1)[1], a))
    # request a: admitted/prefilled/first token all at t=0; the step at
    # t=1 launches its first decode dispatch and leaves it running (the
    # engine runs one dispatch ahead of the host), so its three tokens
    # reach the host at t=0 (prefill), 2 and 3, and the step at t=3, which
    # has fed the last, finishes it; the critical-path attribution event
    # (PR 16) follows the terminal
    stages_a = [s for s, _ in by["a"]]
    assert stages_a == ["admitted", "prefill_start", "first_token",
                        "finish", "attr"]
    fin_a = dict(by["a"])["finish"]
    assert fin_a["queue_wait_ms"] == 0.0 and fin_a["ttft_ms"] == 0.0
    assert fin_a["e2e_ms"] == 3000.0
    # tpot is the mean of the gaps between tokens as they reached the
    # host (2000, 1000), not (terminal - first token) / (n - 1)
    assert fin_a["tpot_ms"] == 1500.0
    assert fin_a["n_generated"] == 3 and fin_a["slot"] == 0
    # request b: waited t=0..3 in queue, prefilled when a's slot freed
    fin_b = dict(by["b"])["finish"]
    assert fin_b["queue_wait_ms"] == 3000.0 and fin_b["ttft_ms"] == 3000.0
    assert fin_b["e2e_ms"] == 6000.0 and fin_b["tpot_ms"] == 1500.0
    # the tracer keeps every gap, on the engine's clock
    done = {tr.req_id: tr for tr in eng.tracer.completed}
    assert done["a"].token_times == [0.0, 2.0, 3.0]
    assert done["a"].tpot_gaps_ms() == [2000.0, 1000.0]
    assert done["b"].tpot_gaps_ms() == [2000.0, 1000.0]
    # registry histograms carry exactly the JSONL-derived samples
    assert sorted(tel.registry.histograms["serve/ttft_ms"].values()) == \
        [0.0, 3000.0]
    assert sorted(tel.registry.histograms["serve/e2e_ms"].values()) == \
        [3000.0, 6000.0]
    assert sorted(
        tel.registry.histograms["serve/queue_wait_ms"].values()) == \
        [0.0, 3000.0]
    assert tel.registry.histograms["serve/tpot_ms"].values() == \
        [1500.0, 1500.0]


def test_tracer_unit_invariants():
    """RequestTracer's own contract: double admits, unknown terminals and
    terminals on closed traces are recorded as errors; audit() reports
    orphans / untraced / count mismatches."""
    clk = FakeClock()
    tr = RequestTracer(clock=clk)
    tr.admit("r1")
    tr.admit("r1")                       # double admit
    assert tr.errors and "double admit" in tr.errors[0]
    assert tr.terminal("r1", "not_a_terminal") is None
    tr.terminal("r1", "finish", n_generated=2)
    assert tr.terminal("r1", "finish") is None   # already closed
    assert tr.prefill_start("ghost", 0) is None
    assert tr.first_token("ghost") is None
    audit = tr.audit(live_req_ids=[])
    assert "trace_errors" in audit
    tr2 = RequestTracer(clock=clk)
    tr2.admit("open")
    assert tr2.audit([]) == {"trace_open_orphans": ["open"]}
    assert tr2.audit(["open", "untracked"]) == \
        {"untraced_requests": ["untracked"]}
    assert set(tr2.terminals) == set(TRACE_TERMINALS)


def test_leak_report_flags_trace_orphan(tiny):
    """A trace opened with no live owner is a leak — the completeness
    invariant rides in the same audit as page leaks."""
    cfg, model, params = tiny
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=32, dtype=jnp.float32)
    assert eng.leak_report() == {}
    eng.tracer.admit("ghost")
    leaks = eng.leak_report()
    assert leaks.get("trace_open_orphans") == ["ghost"]


def test_trace_terminals_cover_all_exits(tiny, tmp_path):
    """shed (displaced + drained), deadline (queued + active), evict
    (injected fault) and finish each close a trace with the right
    terminal name, and completeness holds across all of them."""
    cfg, model, params = tiny
    clk = FakeClock()
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "exits"}), rank=0)
    eng = ServingEngine(
        model, params, max_batch=1, page_size=8, max_seq=64, num_pages=3,
        dtype=jnp.float32, clock=clk, telemetry=tel,
        serving={"max_queue": 2, "overload_policy": "shed-oldest",
                 "fault_injection": {"serve_sample": {"fail_at": [2]}}})
    ps = _prompts(cfg, 7, [4, 4, 4, 4, 4])
    # r0 active (slot 0, sampler faults on its 2nd sample -> evict);
    # r1/r2 fill the queue; r3 displaces r1 (shed-oldest)
    eng.add_request(0, ps[0], max_new_tokens=4)
    eng.add_request(1, ps[1], max_new_tokens=4)
    eng.add_request(2, ps[2], max_new_tokens=4, deadline_s=2.0)
    eng.add_request(3, ps[3], max_new_tokens=4)
    clk.tick(5.0)      # r2's deadline expires while queued
    steps = 0
    while (eng.queue or eng.n_active) and steps < 50:
        eng.step()
        clk.tick(1.0)
        steps += 1
    # r3 (or whoever is left) finished normally; queue drained itself
    assert eng.leak_report() == {}
    t = eng.tracer
    assert t.admitted == 4 and t.closed == 4 and not t.open
    assert t.terminals["shed"] == 1       # r1 displaced
    assert t.terminals["deadline"] == 1   # r2 expired queued
    assert t.terminals["evict"] == 1      # r0 sampler fault
    assert t.terminals["finish"] == 1     # r3
    tel.close()
    names = [e["name"] for e in _events(tmp_path, "exits")
             if e["name"].startswith("serve/request/")]
    assert names.count("serve/request/admitted") == 4
    terminal_names = [n for n in names
                      if n.rsplit("/", 1)[1] in TRACE_TERMINALS]
    assert len(terminal_names) == 4


def test_drain_closes_traces_as_shed(tiny):
    cfg, model, params = tiny
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=32, dtype=jnp.float32)
    ps = _prompts(cfg, 11, [4, 4, 4])
    for i, p in enumerate(ps):
        eng.add_request(i, p, max_new_tokens=20)
    eng.drain(max_steps=1)     # budget too small: active request is shed
    assert eng.leak_report() == {}
    t = eng.tracer
    assert t.admitted == t.closed == 3 and not t.open
    assert t.terminals["shed"] == 3      # "drained" folds into shed


# ----------------------------------------------------------------------
# SLO counters + goodput
# ----------------------------------------------------------------------
def test_slo_attainment_and_goodput(tiny, tmp_path):
    """A deadline request finishing on time counts attained; one expiring
    mid-flight counts missed; goodput counts only finished tokens."""
    cfg, model, params = tiny
    clk = FakeClock()
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "slo"}), rank=0)
    eng = ServingEngine(model, params, max_batch=2, page_size=8,
                        max_seq=32, dtype=jnp.float32, clock=clk,
                        telemetry=tel)
    pa, pb = _prompts(cfg, 5, [4, 4])
    eng.add_request("fast", pa, max_new_tokens=2, deadline_s=100.0)
    eng.add_request("slow", pb, max_new_tokens=20, deadline_s=3.0)
    steps = 0
    while (eng.queue or eng.n_active) and steps < 50:
        clk.tick(1.0)
        eng.step()
        steps += 1
    assert eng.leak_report() == {}
    assert eng.stats["slo_attained"] == 1
    assert eng.stats["slo_missed"] == 1
    assert eng.stats["goodput_tokens"] == 2      # only "fast" delivered
    assert tel.registry.counters["serve/slo_attained"].value == 1
    assert tel.registry.counters["serve/slo_missed"].value == 1
    assert tel.registry.counters["serve/goodput_tokens"].value == 2
    health = eng.health()
    assert health["slo"] == {"attained": 1, "missed": 1,
                             "goodput_tokens": 2}
    assert health["traces"]["open"] == 0
    assert health["latency"]["serve/ttft_ms"]["count"] == 2
    tel.close()


# ----------------------------------------------------------------------
# histogram windowed-stats satellite
# ----------------------------------------------------------------------
def test_histogram_prunes_on_every_path():
    h = Histogram("x", window_secs=10.0)
    h.observe(1.0, now=0.0)
    h.observe(2.0, now=5.0)
    # query-side pruning: sample at t=0 is stale by t=11 even though no
    # observe() ran since
    assert h.percentile(50, now=11.0) == 2.0
    assert h.summary(now=11.0)["count"] == 1
    # observe-side pruning: a fresh sample evicts the stale ones first
    h.observe(3.0, now=16.0)
    assert h.values(now=16.0) == [3.0]
    # fully-stale window: typed empty summary, never a raise/KeyError
    s = h.summary(now=1000.0)
    assert s == {"count": 0, "min": None, "max": None, "mean": None,
                 "p50": None, "p90": None, "p99": None}
    assert h.percentile(99, now=1000.0) is None


# ----------------------------------------------------------------------
# metrics exporter
# ----------------------------------------------------------------------
def test_exporter_endpoints(tmp_path):
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "exp",
                         "export": {"enabled": True, "port": 0}}), rank=0)
    assert tel.exporter is not None
    host, port = tel.exporter.address
    base = f"http://{host}:{port}"
    tel.gauge("engine/loss", 0.25)
    tel.count("serve/slo_attained", 2)
    txt = urllib.request.urlopen(base + "/metrics").read().decode()
    assert "ds_engine_loss 0.25" in txt
    assert "ds_serve_slo_attained 2" in txt
    for path in ("/metrics.json", "/snapshot"):
        snap = json.loads(urllib.request.urlopen(base + path).read())
        assert snap["gauges"]["engine/loss"]["value"] == 0.25
        assert "ts" in snap
    hz = json.loads(urllib.request.urlopen(base + "/healthz").read())
    assert hz == {"ok": True}
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(base + "/nope")
    # the meta event records where the exporter bound
    tel.close()
    assert tel.exporter is None
    metas = [e for e in _events(tmp_path, "exp")
             if e["name"] == "telemetry/export"]
    assert metas and metas[0]["attrs"]["port"] == port


def test_exporter_off_by_default(tmp_path):
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "noexp"}), rank=0)
    assert tel.exporter is None
    tel.close()


def test_export_config_block():
    cfg = TelemetryConfig({"export": {"enabled": True, "port": 1234}})
    assert isinstance(cfg.export, TelemetryExportConfig)
    assert cfg.export.enabled and cfg.export.port == 1234
    assert not TelemetryConfig({}).export.enabled
    with pytest.raises(ValueError):
        TelemetryConfig({"export": {"port": 70000}})


def test_telemetry_snapshot_api():
    tel = Telemetry()
    tel.enabled = True
    tel.registry.counter("c").inc(3)
    tel.registry.gauge("g").set(1.5)
    tel.registry.histogram("h").observe(2.0)
    snap = tel.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"]["value"] == 1.5
    assert snap["histograms"]["h"]["count"] == 1
    assert snap["ts"] > 0
    tel.close()


# ----------------------------------------------------------------------
# ISSUE acceptance: fault-injected overload + exporter consistency
# ----------------------------------------------------------------------
def test_acceptance_overload_trace_completeness_and_export(tiny, tmp_path):
    """ISSUE.md acceptance: under injected serve_step/page_alloc faults,
    an under-provisioned pool, deadlines and shed-oldest overload —
    (a) the trace-completeness audit passes: admitted == terminal
    serve/request/* events, zero orphans; (b) the exporter serves valid
    Prometheus text carrying both training and serve/* metrics; (c) the
    exported TTFT/TPOT percentiles equal the JSONL-derived ones."""
    cfg, model, params = tiny
    ps = _prompts(cfg, 19, [4, 5, 6, 7, 4, 5, 6, 7])
    clk = FakeClock()
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "accept7",
                         "export": {"enabled": True, "port": 0}}), rank=0)
    tel.gauge("engine/loss", 0.5)      # a training-side metric rides along
    eng = ServingEngine(
        model, params, max_batch=4, page_size=8, max_seq=64, num_pages=5,
        dtype=jnp.float32, clock=clk, telemetry=tel,
        serving={"max_queue": 4, "overload_policy": "shed-oldest",
                 "fault_injection": {"serve_step": {"fail_at": [2, 5]},
                                     "page_alloc": {"fail_at": [1]}}})
    admitted = 0
    for i in range(8):
        try:
            eng.add_request(i, ps[i], max_new_tokens=6,
                            deadline_s=3.0 if i == 5 else None)
            admitted += 1
        except RequestRejected:
            pass
    steps = 0
    while (eng.queue or eng.n_active) and steps < 200:
        eng.step()
        clk.tick(1.0)
        steps += 1
    eng.drain()
    eng.health()
    leaks = eng.leak_report()
    assert leaks == {}, leaks

    # -- (a) trace completeness: stream-side AND tracer-side ------------
    host, port = tel.exporter.address
    prom = urllib.request.urlopen(
        f"http://{host}:{port}/metrics").read().decode()
    registry_ttft = tel.registry.histograms["serve/ttft_ms"]
    reg_ttft_vals = sorted(registry_ttft.values())
    reg_tpot_vals = sorted(
        tel.registry.histograms["serve/tpot_ms"].values())
    tel.close()
    events = _events(tmp_path, "accept7")
    reqs = [e for e in events if e["kind"] == "serve" and
            e["name"].startswith("serve/request/")]
    n_admitted_ev = sum(1 for e in reqs
                        if e["name"] == "serve/request/admitted")
    terminals = [e for e in reqs
                 if e["name"].rsplit("/", 1)[1] in TRACE_TERMINALS]
    assert n_admitted_ev == admitted == eng.stats["admitted"]
    assert len(terminals) == admitted, "orphaned or duplicated terminals"
    assert len({e["attrs"]["req_id"] for e in terminals}) == admitted
    assert eng.tracer.admitted == eng.tracer.closed == admitted
    assert not eng.tracer.open and not eng.tracer.errors

    # -- (b) exporter: valid exposition, training + serve metrics -------
    checker = _load_script("check_telemetry_schema")
    assert checker.validate_file(
        os.path.join(str(tmp_path), "accept7", "events.jsonl")) == []
    assert checker.validate_prom_exposition(prom) == []
    assert "ds_engine_loss" in prom
    assert 'ds_serve_ttft_ms{quantile="0.5"}' in prom
    assert 'ds_serve_tpot_ms{quantile="0.99"}' in prom
    assert "ds_serving_queue_depth" in prom    # health() gauges rode along

    # -- (c) histogram <-> JSONL consistency ----------------------------
    jsonl_ttft = sorted(e["attrs"]["ttft_ms"] for e in reqs
                        if e["name"] == "serve/request/first_token")
    assert reg_ttft_vals == jsonl_ttft
    jsonl_tpot = sorted(e["attrs"]["tpot_ms"] for e in terminals
                        if e["name"] == "serve/request/finish"
                        and "tpot_ms" in e["attrs"])
    assert reg_tpot_vals == jsonl_tpot
    for q in (50, 90, 99):
        assert registry_ttft.percentile(q) == _pct(jsonl_ttft, q)
    # the scraped p50 is the same number (text round-trips via repr)
    p50_line = [l for l in prom.splitlines()
                if l.startswith('ds_serve_ttft_ms{quantile="0.5"}')][0]
    assert float(p50_line.split()[-1]) == _pct(jsonl_ttft, 50)


# ----------------------------------------------------------------------
# report script + bench plumbing
# ----------------------------------------------------------------------
def test_report_request_latency_table(tiny, tmp_path, capsys):
    cfg, model, params = tiny
    clk = FakeClock()
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "rep"}), rank=0)
    eng = ServingEngine(model, params, max_batch=2, page_size=8,
                        max_seq=32, dtype=jnp.float32, clock=clk,
                        telemetry=tel,
                        serving={"max_queue": 2,
                                 "overload_policy": "shed-oldest"})
    ps = _prompts(cfg, 23, [4, 5, 4, 5, 4])
    for i, p in enumerate(ps):
        try:
            eng.add_request(i, p, max_new_tokens=3, deadline_s=50.0)
        except RequestRejected:
            pass
    steps = 0
    while (eng.queue or eng.n_active) and steps < 60:
        clk.tick(1.0)
        eng.step()
        steps += 1
    assert eng.leak_report() == {}
    tel.close()
    report = _load_script("ds_telemetry_report")
    files = report.discover_files(os.path.join(str(tmp_path), "rep"))
    summary = report.summarize(report.aggregate(report.load_events(files)))
    rl = summary["request_latency"]
    assert rl["traces"] == eng.stats["admitted"]
    assert rl["orphans"] == 0
    assert sum(rl["terminals"].values()) == rl["traces"]
    assert rl["slo"]["ok"] == eng.stats["slo_attained"]
    assert rl["latency"]["ttft_ms"]["count"] > 0
    assert rl["slowest"] and rl["slowest"][0]["e2e_ms"] >= \
        rl["slowest"][-1]["e2e_ms"]
    report.print_tables(summary)
    out = capsys.readouterr().out
    assert "request latency" in out and "slowest requests" in out


def test_prom_text_renders_engine_snapshot(tiny, tmp_path):
    """prom_text over a real engine run stays exporter-servable without
    an HTTP round-trip (MetricsExporter import works standalone too)."""
    cfg, model, params = tiny
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "pt"}), rank=0)
    eng = ServingEngine(model, params, max_batch=2, page_size=8,
                        max_seq=32, dtype=jnp.float32, telemetry=tel)
    eng.generate(_prompts(cfg, 29, [4, 5]), max_new_tokens=2)
    eng.health()
    text = prom_text(tel.snapshot())
    checker = _load_script("check_telemetry_schema")
    assert checker.validate_prom_exposition(text) == []
    assert "ds_serve_ttft_ms" in text
    exp = MetricsExporter(tel, port=0)
    exp.start()
    host, port = exp.address
    live = urllib.request.urlopen(
        f"http://{host}:{port}/metrics").read().decode()
    assert "ds_serve_ttft_ms" in live
    exp.close()
    tel.close()


def test_exporter_close_releases_port_for_rebind(tmp_path):
    """Regression: close()/drain() must CLOSE the listening socket so
    the same address is immediately rebindable (drain → restart on a
    pinned port), must not hang when start() never ran (the constructor
    binds, but ``shutdown()`` only unblocks a running ``serve_forever``
    loop), and must be idempotent."""
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "lc"}), rank=0)
    try:
        exp = MetricsExporter(tel, port=0)
        exp.start()
        host, port = exp.address
        exp.drain()                     # lifecycle alias for close()
        # bind-after-close: a fresh exporter takes the SAME address
        exp2 = MetricsExporter(tel, host=host, port=port)
        exp2.start()
        assert exp2.address == (host, port)
        urllib.request.urlopen(f"http://{host}:{port}/metrics",
                               timeout=5).read()
        exp2.close()
        exp2.close()                    # idempotent
        # close() without start(): must return, not wait forever
        exp3 = MetricsExporter(tel, port=0)
        exp3.close()
        with pytest.raises(RuntimeError):
            exp3.start()                # a closed exporter stays closed
    finally:
        tel.close()


def test_exporter_scrape_is_thread_safe(tmp_path):
    """Regression: a /metrics scrape while writers hammer observe()/set()
    must neither raise ("deque mutated during iteration") nor tear the
    gauge value-above-peak invariant."""
    import threading

    tel = Telemetry().configure(TelemetryConfig(
        {"enabled": True, "output_path": str(tmp_path), "job_name": "race",
         "export": {"enabled": True, "port": 0}}), rank=0)
    host, port = tel.exporter.address
    base = f"http://{host}:{port}"
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            i += 1
            tel.registry.histogram("serve/ttft_ms").observe(i % 97)
            tel.registry.gauge("serve/queue_depth").set(i % 13)

    threads = [threading.Thread(target=writer, daemon=True)
               for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(30):
            txt = urllib.request.urlopen(base + "/metrics").read().decode()
            assert "serve" in txt or txt == ""      # parses, no 500
            snap = json.loads(
                urllib.request.urlopen(base + "/metrics.json").read())
            for g in snap.get("gauges", {}).values():
                if isinstance(g, dict) and "peak" in g:
                    assert g["value"] <= g["peak"]  # no torn reads
            tel.snapshot()
    except Exception as e:                          # pragma: no cover
        errors.append(e)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5.0)
        tel.close()
    assert errors == []
