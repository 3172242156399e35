"""Serving hardening layer tests (inference/robustness.py + the serving
surgery): typed rejection, admission control + load shedding, deadlines,
per-request fault isolation, graceful drain, health/leak auditing, and the
fault-injected overload acceptance scenario.

Oracle discipline: surviving requests must be BIT-IDENTICAL to what they
would have produced served alone — the hardening layer may cancel a
request, never perturb one."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.robustness import (
    OVERLOAD_POLICIES, REJECT_REASONS, AdmissionController, RequestRejected,
    ServingRobustnessConfig, ServingStalled)
from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.runtime.resilience import FAULT_SITES, FaultInjector


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _dense_greedy(model, params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        logits = model.apply(params, jnp.asarray(seq)[None, :], train=False)
        seq.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return seq


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


# ----------------------------------------------------------------------
# typed admission-time validation
# ----------------------------------------------------------------------
def test_typed_rejections(tiny):
    cfg, model, params = tiny
    eng = ServingEngine(model, params, max_batch=2, page_size=8,
                        max_seq=64, num_pages=4, dtype=jnp.float32)
    p = _prompts(cfg, 0, [4])[0]

    with pytest.raises(RequestRejected) as ei:
        eng.add_request("big", list(range(60)), max_new_tokens=10)
    assert ei.value.reason == "oversized_prompt"
    assert "max_seq" in ei.value.detail

    # fits max_seq but not the (under-provisioned, 3-page) pool
    with pytest.raises(RequestRejected) as ei:
        eng.add_request("wide", list(range(20)), max_new_tokens=12)
    assert ei.value.reason == "infeasible_pages"

    with pytest.raises(RequestRejected) as ei:
        eng.add_request("empty", [], max_new_tokens=4)
    assert ei.value.reason == "bad_request"
    with pytest.raises(RequestRejected) as ei:
        eng.add_request("zero", p, max_new_tokens=0)
    assert ei.value.reason == "bad_request"

    for bad in (dict(top_p=0.0), dict(top_p=1.5), dict(top_k=-1),
                dict(temperature=-0.5)):
        with pytest.raises(RequestRejected) as ei:
            eng.add_request("samp", p, max_new_tokens=4, **bad)
        assert ei.value.reason == "bad_sampling", bad

    eng.add_request("ok", p, max_new_tokens=4)
    with pytest.raises(RequestRejected) as ei:
        eng.add_request("ok", p, max_new_tokens=4)   # active duplicate
    assert ei.value.reason == "duplicate_id"

    # every rejection left the engine consistent
    assert eng.stats["rejected"] == 9
    assert eng.leak_report() == {}
    assert all(r in REJECT_REASONS for r in
               ("oversized_prompt", "infeasible_pages", "duplicate_id",
                "bad_sampling", "bad_request"))


def test_rejection_leaves_state_untouched(tiny):
    cfg, model, params = tiny
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=32, dtype=jnp.float32)
    before = (eng.alloc.free_page_count, len(eng.queue), eng.n_active)
    with pytest.raises(RequestRejected):
        eng.add_request("big", list(range(30)), max_new_tokens=10)
    assert (eng.alloc.free_page_count, len(eng.queue),
            eng.n_active) == before


# ----------------------------------------------------------------------
# admission control + load shedding
# ----------------------------------------------------------------------
def test_reject_policy_queue_full(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 1, [4, 5, 6, 7])
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=64, dtype=jnp.float32,
                        serving={"max_queue": 2})
    eng.add_request(0, ps[0], max_new_tokens=4)        # -> active
    eng.add_request(1, ps[1], max_new_tokens=4)        # queued
    eng.add_request(2, ps[2], max_new_tokens=4)        # queued (at cap)
    with pytest.raises(RequestRejected) as ei:
        eng.add_request(3, ps[3], max_new_tokens=4)
    assert ei.value.reason == "queue_full"
    assert len(eng.queue) == 2


def test_shed_oldest_policy(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 2, [4, 5, 6, 7])
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=64, dtype=jnp.float32,
                        serving={"max_queue": 2,
                                 "overload_policy": "shed-oldest"})
    for i in range(3):
        eng.add_request(i, ps[i], max_new_tokens=4)
    eng.add_request(3, ps[3], max_new_tokens=4)   # displaces request 1
    assert [r.req_id for r in eng.queue] == [2, 3]
    res = eng.pop_terminated()[1]
    assert res.status == "shed" and res.reason == "shed_oldest"
    assert res.tokens == ps[1] and res.n_generated == 0
    assert eng.stats["shed"] == 1
    # the survivors serve to completion, bit-identical
    done = {}
    while eng.queue or eng.n_active:
        done.update(eng.step())
    for rid in (0, 2, 3):
        assert done[rid] == _dense_greedy(model, params, ps[rid], 4), rid
    assert eng.leak_report() == {}


def test_block_policy_waits_for_space(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 3, [4, 5, 6])
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=64, dtype=jnp.float32,
                        serving={"max_queue": 1, "overload_policy": "block",
                                 "block_max_steps": 64})
    eng.add_request(0, ps[0], max_new_tokens=3)
    eng.add_request(1, ps[1], max_new_tokens=3)   # queue at cap
    eng.add_request(2, ps[2], max_new_tokens=3)   # blocks: steps until room
    assert eng.stats["finished"] >= 1             # progress was made inline
    done = dict(eng.finished)
    eng.finished.clear()
    while eng.queue or eng.n_active:
        done.update(eng.step())
    for rid in range(3):
        assert done[rid] == _dense_greedy(model, params, ps[rid], 3), rid


def test_block_policy_budget_exhausted_rejects(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 4, [4, 5, 6])
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=64, dtype=jnp.float32,
                        serving={"max_queue": 1, "overload_policy": "block",
                                 "block_max_steps": 0})
    eng.add_request(0, ps[0], max_new_tokens=3)
    eng.add_request(1, ps[1], max_new_tokens=3)
    with pytest.raises(RequestRejected) as ei:
        eng.add_request(2, ps[2], max_new_tokens=3)
    assert ei.value.reason == "queue_full"


def test_admission_watermark_hysteresis():
    ctl = AdmissionController(ServingRobustnessConfig(
        {"queue_high_watermark": 4, "queue_low_watermark": 1,
         "free_page_low_watermark": 2}))
    assert not ctl.update(queue_depth=3, free_pages=10)
    assert ctl.update(queue_depth=4, free_pages=10)      # engages (queue)
    assert ctl.update(queue_depth=2, free_pages=10)      # stays: above low
    assert not ctl.update(queue_depth=1, free_pages=10)  # releases
    assert ctl.update(queue_depth=0, free_pages=2)       # engages (pages)
    assert ctl.update(queue_depth=0, free_pages=2)       # stays
    assert not ctl.update(queue_depth=0, free_pages=3)   # releases
    assert "block" in OVERLOAD_POLICIES


def test_config_validation():
    with pytest.raises(ValueError):
        ServingRobustnessConfig({"overload_policy": "nope"})
    with pytest.raises(ValueError):
        ServingRobustnessConfig({"max_queue": -1})
    with pytest.raises(ValueError):
        ServingRobustnessConfig({"queue_high_watermark": 2,
                                 "queue_low_watermark": 5})


# ----------------------------------------------------------------------
# deadlines
# ----------------------------------------------------------------------
def test_deadline_expires_queued_request(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 5, [4, 5])
    clk = FakeClock()
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=64, dtype=jnp.float32, clock=clk)
    eng.add_request(0, ps[0], max_new_tokens=8)
    eng.add_request(1, ps[1], max_new_tokens=8, deadline_s=3.0)
    clk.tick(5.0)
    eng.step()
    res = eng.pop_terminated()[1]
    assert res.status == "deadline" and res.reason == "deadline"
    assert res.tokens == ps[1]
    assert not eng.queue and eng.stats["deadline"] == 1
    # request 0 is untouched by its neighbour's cancellation
    done = {}
    while eng.queue or eng.n_active:
        done.update(eng.step())
    assert done[0] == _dense_greedy(model, params, ps[0], 8)
    assert eng.leak_report() == {}


def test_deadline_cancels_midflight_and_frees_pages(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 6, [5])
    clk = FakeClock()
    eng = ServingEngine(model, params, max_batch=2, page_size=8,
                        max_seq=64, dtype=jnp.float32, clock=clk)
    full = eng.alloc.free_page_count
    eng.add_request(0, ps[0], max_new_tokens=16, deadline_s=4.0)
    eng.step()
    eng.step()
    assert eng.n_active == 1
    clk.tick(10.0)
    eng.step()
    assert eng.n_active == 0
    res = eng.pop_terminated()[0]
    assert res.status == "deadline" and res.n_generated >= 1
    assert res.tokens[:len(ps[0])] == ps[0]    # partial output preserved
    assert eng.alloc.free_page_count == full   # pages freed immediately
    assert eng.leak_report() == {}


def test_default_deadline_from_config(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 7, [4])
    clk = FakeClock()
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=64, dtype=jnp.float32, clock=clk,
                        serving={"default_deadline_s": 2.0})
    eng.add_request(0, ps[0], max_new_tokens=32)
    clk.tick(3.0)
    eng.step()
    assert eng.pop_terminated()[0].reason == "deadline"


# ----------------------------------------------------------------------
# per-request fault isolation
# ----------------------------------------------------------------------
def test_sampler_fault_evicts_one_slot_rest_unaffected(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 8, [4, 6])
    # serve_sample call index: 0,1 = the two prefills; then one call per
    # unfinished slot per step in slot order — index 4 is slot 0 at its
    # second decode step
    inj = FaultInjector({"serve_sample": {"fail_at": [4], "msg": "boom"}})
    eng = ServingEngine(model, params, max_batch=2, page_size=8,
                        max_seq=64, dtype=jnp.float32, injector=inj)
    full = eng.alloc.free_page_count
    eng.add_request(0, ps[0], max_new_tokens=5)
    eng.add_request(1, ps[1], max_new_tokens=5)
    done = {}
    while eng.queue or eng.n_active:
        done.update(eng.step())
    res = eng.pop_terminated()[0]
    assert res.status == "evicted" and res.reason == "fault"
    assert res.tokens[:len(ps[0])] == ps[0] and res.n_generated == 2
    assert eng.stats["evicted"] == 1
    # the co-resident request is BIT-IDENTICAL to being served alone
    assert done[1] == _dense_greedy(model, params, ps[1], 5)
    assert eng.alloc.free_page_count == full
    assert eng.leak_report() == {}


def test_transient_step_faults_outputs_bit_identical(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 9, [4, 7, 5])
    clean = ServingEngine(model, params, max_batch=2, page_size=8,
                          max_seq=64, dtype=jnp.float32)
    expect = clean.generate(ps, max_new_tokens=5)
    inj = FaultInjector({"serve_step": {"fail_at": [1, 3, 4]}})
    eng = ServingEngine(model, params, max_batch=2, page_size=8,
                        max_seq=64, dtype=jnp.float32, injector=inj)
    got = eng.generate(ps, max_new_tokens=5)
    assert got == expect                      # faulted steps retried cleanly
    assert eng.stats["step_faults"] == 3
    assert eng.leak_report() == {}


def test_page_alloc_faults_retry_without_corruption(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 10, [4, 6, 5])
    clean = ServingEngine(model, params, max_batch=2, page_size=8,
                          max_seq=64, dtype=jnp.float32)
    expect = clean.generate(ps, max_new_tokens=4)
    eng = ServingEngine(
        model, params, max_batch=2, page_size=8, max_seq=64,
        dtype=jnp.float32,
        serving={"fault_injection": {"page_alloc": {"fail_times": 2}}})
    got = eng.generate(ps, max_new_tokens=4)
    assert got == expect
    assert eng.leak_report() == {}


def test_step_fault_limit_escalates(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 11, [4])
    eng = ServingEngine(
        model, params, max_batch=1, page_size=8, max_seq=64,
        dtype=jnp.float32,
        serving={"step_fault_limit": 2,
                 "fault_injection": {"serve_step": {"fail_times": 100}}})
    eng.add_request(0, ps[0], max_new_tokens=4)
    assert eng.step() == {} and eng.step() == {}   # tolerated
    with pytest.raises(OSError):
        eng.step()                                  # limit exceeded


# ----------------------------------------------------------------------
# graceful drain, stall, health, leaks
# ----------------------------------------------------------------------
def test_drain_finishes_active_sheds_queued(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 12, [4, 5, 6])
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=64, dtype=jnp.float32)
    for i in range(3):
        eng.add_request(i, ps[i], max_new_tokens=4)
    report = eng.drain()
    assert report["finished"][0] == _dense_greedy(model, params, ps[0], 4)
    assert sorted(report["shed"]) == [1, 2]
    assert eng.n_active == 0 and not eng.alloc.seq_pages
    assert eng.alloc.free_page_count == eng.alloc.num_pages - 1
    assert eng.leak_report() == {}
    term = eng.pop_terminated()
    assert term[1].reason == "drain" and term[2].reason == "drain"
    assert report["health"]["draining"] is True
    with pytest.raises(RequestRejected) as ei:
        eng.add_request(9, ps[0], max_new_tokens=4)
    assert ei.value.reason == "draining"


def test_drain_zero_budget_sheds_inflight_with_partials(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 13, [4])
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=64, dtype=jnp.float32)
    eng.add_request(0, ps[0], max_new_tokens=32)
    eng.step()
    report = eng.drain(max_steps=0)
    assert report["finished"] == {} and report["shed"] == [0]
    res = eng.pop_terminated()[0]
    assert res.status == "drained" and res.tokens[:len(ps[0])] == ps[0]
    assert eng.n_active == 0 and not eng.alloc.seq_pages
    assert eng.leak_report() == {}


def test_generate_stall_raises_typed_with_partial(tiny):
    cfg, model, params = tiny
    ps = _prompts(cfg, 14, [4, 5])
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=64, dtype=jnp.float32)
    real_admit, calls = eng._admit, [0]

    def crippled_admit():
        calls[0] += 1
        if calls[0] <= 2:        # enough to admit request 0, then wedge
            real_admit()
    eng._admit = crippled_admit
    with pytest.raises(ServingStalled) as ei:
        eng.generate(ps, max_new_tokens=4)
    err = ei.value
    # the completed result SURVIVES (the assert this replaces destroyed it)
    assert err.partial[0] == _dense_greedy(model, params, ps[0], 4)
    assert err.stuck_req_ids == [1] and err.queue_depth == 1
    assert err.free_pages > 0 and err.steps > 0


def test_health_snapshot_and_gauges(tiny, tmp_path):
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig
    cfg, model, params = tiny
    ps = _prompts(cfg, 15, [4, 5, 6])
    clk = FakeClock()
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "health"}), rank=0)
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=64, dtype=jnp.float32, clock=clk,
                        telemetry=tel)
    for i in range(3):
        eng.add_request(i, ps[i], max_new_tokens=4)
    clk.tick(2.5)
    h = eng.health()
    assert h["active_slots"] == 1 and h["queue_depth"] == 2
    assert h["oldest_request_age_s"] == 2.5
    assert h["free_pages"] + 1 == h["total_pages"]  # 1 page reserved
    assert h["counters"]["admitted"] == 3
    assert tel.registry.gauge("serving/queue_depth").value == 2.0
    tel.close()


def test_every_exit_path_is_leak_free(tiny):
    """finish + shed-oldest + deadline + evict + drain in ONE engine: the
    invariant audit stays clean after each stage."""
    cfg, model, params = tiny
    ps = _prompts(cfg, 16, [4, 5, 6, 4, 5, 6])
    clk = FakeClock()
    inj = FaultInjector({"serve_sample": {"fail_at": [9]}})
    eng = ServingEngine(model, params, max_batch=2, page_size=8,
                        max_seq=64, dtype=jnp.float32, clock=clk,
                        injector=inj,
                        serving={"max_queue": 2,
                                 "overload_policy": "shed-oldest"})
    eng.add_request(0, ps[0], max_new_tokens=3)            # will finish
    eng.add_request(1, ps[1], max_new_tokens=3)            # fault-evicted
    eng.add_request(2, ps[2], max_new_tokens=3, deadline_s=1.0)  # expires
    eng.add_request(3, ps[3], max_new_tokens=3)
    eng.add_request(4, ps[4], max_new_tokens=3)            # sheds 2
    assert eng.leak_report() == {}
    clk.tick(2.0)                 # expire request 2 (already shed or queued)
    for _ in range(6):
        eng.step()
        assert eng.leak_report() == {}
    eng.add_request(5, ps[5], max_new_tokens=16)
    eng.drain()
    assert eng.leak_report() == {}
    assert eng.n_active == 0 and not eng.alloc.seq_pages and not eng._rng
    statuses = {r.req_id: r.status for r in eng.pop_terminated().values()}
    assert statuses.get(2) in ("shed", "deadline")


def test_randomized_interleaving_survivors_bit_identical(tiny):
    """Stress: random arrivals, deadlines, and injected sampler faults —
    every request that finishes normally matches the dense oracle."""
    cfg, model, params = tiny
    rng = np.random.default_rng(17)
    lengths = rng.integers(3, 10, 10).tolist()
    ps = _prompts(cfg, 18, lengths)
    budgets = rng.integers(2, 6, 10).tolist()
    clk = FakeClock()
    inj = FaultInjector({"serve_sample": {"fail_at": [7, 19]}})
    eng = ServingEngine(model, params, max_batch=3, page_size=8,
                        max_seq=64, dtype=jnp.float32, clock=clk,
                        injector=inj,
                        serving={"max_queue": 4,
                                 "overload_policy": "shed-oldest"})
    done, i = {}, 0
    while i < 10 or eng.queue or eng.n_active:
        for _ in range(int(rng.integers(0, 3))):
            if i >= 10:
                break
            ttl = float(rng.integers(2, 9)) if rng.random() < 0.3 else None
            try:
                eng.add_request(i, ps[i], max_new_tokens=int(budgets[i]),
                                deadline_s=ttl)
            except RequestRejected:
                pass
            i += 1
        done.update(eng.step())
        clk.tick(1.0)
        assert eng.leak_report() == {}
    for rid, toks in done.items():
        assert toks == _dense_greedy(model, params, ps[rid],
                                     int(budgets[rid])), rid
    # terminated requests all carry typed reasons + intact prompt prefixes
    for res in eng.pop_terminated().values():
        assert res.reason in ("shed_oldest", "deadline", "fault", "drain")
        assert res.tokens[:len(ps[res.req_id])] == ps[res.req_id]


# ----------------------------------------------------------------------
# the ISSUE acceptance scenario + frozen telemetry
# ----------------------------------------------------------------------
def _load_schema_checker():
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    path = os.path.join(repo, "scripts", "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("cts_accept", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_acceptance_fault_injected_overload(tiny, tmp_path):
    """ISSUE.md acceptance: injected serve_step/page_alloc faults, an
    under-provisioned page pool, deadlines on a subset, shed-oldest under
    overload — (a) every surviving request bit-identical to an unfaulted
    isolated run, (b) shed/cancelled requests typed in serve/* telemetry,
    (c) drain() leaves zero slots/pages/RNG/table state."""
    import json
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig
    cfg, model, params = tiny
    ps = _prompts(cfg, 19, [4, 5, 6, 7, 4, 5, 6, 7])
    clk = FakeClock()
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "accept"}), rank=0)
    # pool of 4 usable pages @ need 2/request -> only 2 requests resident
    eng = ServingEngine(
        model, params, max_batch=4, page_size=8, max_seq=64, num_pages=5,
        dtype=jnp.float32, clock=clk, telemetry=tel,
        serving={"max_queue": 4, "overload_policy": "shed-oldest",
                 "fault_injection": {"serve_step": {"fail_at": [2, 5]},
                                     "page_alloc": {"fail_at": [1]}}})
    for i in range(8):
        # request 5 carries a deadline it cannot meet from the queue back
        eng.add_request(i, ps[i], max_new_tokens=6,
                        deadline_s=3.0 if i == 5 else None)
    done = {}
    steps = 0
    while (eng.queue or eng.n_active) and steps < 200:
        done.update(eng.step())
        clk.tick(1.0)
        steps += 1
    # (a) bit-identical survivors
    assert done, "no request survived the overload run"
    for rid, toks in done.items():
        assert toks == _dense_greedy(model, params, ps[rid], 6), rid
    # (b) typed reasons for every non-survivor, visible in telemetry
    term = dict(eng.terminated)
    assert set(done) | set(term) == set(range(8))
    assert term, "overload never shed anything"
    assert any(r.reason == "shed_oldest" for r in term.values())
    assert term[5].reason == "deadline"
    report = eng.drain()
    # (c) fully quiesced: nothing active, allocated, or cached
    assert eng.n_active == 0 and not eng.alloc.seq_pages and not eng._rng
    assert eng.alloc.free_page_count == eng.alloc.num_pages - 1
    assert eng.leak_report() == {}
    assert report["health"]["active_slots"] == 0
    tel.close()
    events_path = os.path.join(str(tmp_path), "accept", "events.jsonl")
    checker = _load_schema_checker()
    assert checker.validate_file(events_path) == []
    events = [json.loads(l) for l in open(events_path) if l.strip()]
    serve_events = [e for e in events if e["kind"] == "serve"]
    reasons = {(e.get("attrs") or {}).get("reason") for e in serve_events}
    names = {e["name"] for e in serve_events}
    assert {"serve/admit", "serve/shed", "serve/deadline", "serve/fault",
            "serve/finish", "serve/drain"} <= names
    assert {"shed_oldest", "deadline"} <= reasons
    assert eng.stats["step_faults"] >= 2


def test_serving_fault_sites_frozen():
    assert {"serve_step", "serve_sample", "page_alloc"} <= set(FAULT_SITES)


def test_inference_config_carries_serving_block():
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    c = DeepSpeedInferenceConfig({"serving": {"max_queue": 9,
                                              "overload_policy": "block"}})
    assert isinstance(c.serving, ServingRobustnessConfig)
    assert c.serving.max_queue == 9 and c.serving.overload_policy == "block"
    with pytest.raises(ValueError):
        DeepSpeedInferenceConfig({"serving": {"overload_policy": "nah"}})
