"""The one-token decode step runs one dispatch ahead of the host: step N+1
is launched on the device's own picks before step N's ids reach the host
(``scheduler._decode_once``), each decode dispatch of ``last_step`` counts
``ahead`` and ``redone``, and no token differs from the loop that waits.

The loop that waits is the same engine serving the same prompts as
SAMPLED requests with ``top_k=1`` (the one surviving token is the greedy
one, but the engine cannot run ahead of a sampler: it fetches every step's
ids before it launches the next, as every step did before).

One engine a (family, policy) for the whole module, at toy sizes in
float32 on the CPU: the three families of ``test_device_picks`` under the
monolithic policy, and the two that take prompts in chunks (dense pages,
latent pools without a selection) under the chunked one."""

import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import serve_cell
from chipbench.reducers import dispatch_counter_ratio
from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import CausalTransformerLM
from deepspeed_tpu.monitor.telemetry import get_telemetry
from deepspeed_tpu.runtime.resilience import FaultInjector
from unit import test_dense_latent_serving, test_device_picks
from unit.test_scheduler import FakeClock

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
CHUNKED = {"scheduler": {"policy": "chunked", "prefill_chunk_tokens": 8,
                         "max_prefill_chunks_per_step": 1}}
WAITS = dict(temperature=1e-3, top_k=1)     # sampled, and always the pick


def _dense_latent():
    return test_dense_latent_serving.toy("kimi_k2")[1].config


ENGINES = {
    "dense-monolithic": ("dense", {}),
    "dense-chunked": ("dense", CHUNKED),
    "latent-monolithic": ("latent", {}),
    "window-monolithic": ("window", {}),
    "dense_latent-chunked": (_dense_latent, CHUNKED),
}


@pytest.fixture(scope="module", params=list(ENGINES))
def served(request):
    """(config, engine, its clock): every test leaves the engine empty."""
    family, serving = ENGINES[request.param]
    make, kwargs = test_device_picks.FAMILIES.get(
        family, (family, dict(max_batch=4, page_size=8, max_seq=160)))
    kwargs = dict(kwargs, serving={**kwargs.get("serving", {}), **serving})
    config = make()
    model = CausalTransformerLM(config)
    params = model.init(jax.random.key(3), jnp.float32)
    clock = FakeClock()
    return config, ServingEngine(model, params, dtype=jnp.float32,
                                 clock=clock, **kwargs), clock


# requests that start and end at different steps: (arrives before step,
# prompt tokens, new tokens); six on four slots, so two wait for a slot
ARRIVALS = ((0, 5, 7), (0, 11, 3), (0, 19, 10), (2, 7, 1), (3, 9, 6),
            (5, 13, 8))


def _requests(config, seed, avoid=()):
    rng = np.random.default_rng(seed)
    allowed = np.setdiff1d(np.arange(config.vocab_size), avoid)
    return [(at, rng.choice(allowed, n).tolist(), new)
            for at, n, new in ARRIVALS]


def _serve(engine, requests, sampling=None, each_step=None, **every):
    """Serve ``requests`` to the end, each submitted before the step it
    arrives at: ({id: tokens}, the step reports of this run)."""
    mark = time.perf_counter_ns()
    done, step = {}, 0
    waiting = list(enumerate(requests))
    while waiting or engine.queue or engine.n_active:
        while waiting and waiting[0][1][0] <= step:
            rid, (_, prompt, new) = waiting.pop(0)
            engine.add_request(rid, prompt, max_new_tokens=new,
                               **{**every, **(sampling or {}).get(rid, {})})
        done.update(engine.step())
        _owed_requests_are_active(engine)
        if each_step is not None:
            each_step(step)
        step += 1
    assert engine.leak_report() == {}
    assert engine.scheduler._ahead == []
    return done, [r for r in engine.step_reports() if r["t0_ns"] >= mark]


def _owed_requests_are_active(engine):
    """A request whose pick is in flight sits in its slot: ``n_active``
    cannot read 0 while a token is owed."""
    for dispatch in engine.scheduler._ahead:
        assert dispatch.owed and engine.n_active > 0
        for slot, req in dispatch.owed.items():
            assert engine.slots[slot] is req


def _decodes(reports):
    return [d for r in reports for d in r["dispatches"]
            if d["phase"] == "decode"]


def _total(reports, counter):
    return sum(d[counter] for d in _decodes(reports))


def _emitted(reports):
    out = {}
    for report in reports:
        for rid, n, _ in report["emitted"]:
            out[rid] = out.get(rid, 0) + n
    return out


def test_the_tokens_are_those_of_the_loop_that_waits(served):
    config, engine, _ = served
    requests = _requests(config, 1)
    done, reports = _serve(engine, requests)
    waited, theirs = _serve(engine, requests, **WAITS)
    assert done == waited
    assert [len(done[i]) for i in range(len(requests))] == \
        [n + new for _, n, new in ARRIVALS]
    # every token reached the host once, whichever loop served it
    wanted = {i: new for i, (_, _, new) in enumerate(ARRIVALS)}
    assert _emitted(reports) == _emitted(theirs) == wanted
    picked = sum(d.get("picked", 0) for r in reports
                 for d in r["dispatches"])
    assert picked == sum(wanted.values())
    # a dispatch's picks had been fed to the next one before the host held
    # them, unless a sampled prefill was fetched between the two launches
    # (it brings the older dispatch's ids along): those steps are fed from
    # the host
    flat = [d for r in reports for d in r["dispatches"]]
    fed_early = 0
    for i, d in enumerate(flat):
        if d["phase"] != "decode":
            continue
        later = flat[i + 1:]
        upto = next((j for j, n in enumerate(later)
                     if n["phase"] == "decode"), len(later))
        fetched_between = any(n["phase"] == "prefill" and n["head_rows"]
                              for n in later[:upto])
        assert d["redone"] == 0
        assert d["ahead"] == (0 if fetched_between else d["picked"])
        fed_early += d["ahead"]
    assert 0 < fed_early == _total(reports, "ahead")
    # the loop that waits launches nothing on a pick
    assert _total(theirs, "ahead") == _total(theirs, "redone") == 0
    assert len(_decodes(theirs)) == len(_decodes(reports))
    # both counters ride the dispatch's serve/step span
    spans = [s for s in get_telemetry().spans(
        since_ns=reports[0]["t0_ns"], until_ns=reports[-1]["t1_ns"])
        if s.name == "serve/step" and s.attrs["phase"] == "decode"]
    assert sum(s.attrs["ahead"] for s in spans) == fed_early
    assert all(s.attrs["redone"] == 0 for s in spans)


def test_an_eos_ends_the_request_and_costs_no_row(served):
    """EOS is tested on the token FED, which the host holds before the
    next launch: the request ends where the waiting loop ends it, its last
    dispatch is the one that writes the EOS, and no row is launched
    behind it."""
    config, engine, _ = served
    requests = _requests(config, 2)
    free, _ = _serve(engine, requests)
    # the third token request 2 generates, wherever else it comes up
    eos = engine.eos = free[2][len(requests[2][1]) + 2]
    try:
        done, reports = _serve(engine, requests)
        waited, theirs = _serve(engine, requests, **WAITS)
    finally:
        engine.eos = None
    assert done == waited
    assert done[2][-1] == eos
    assert len(requests[2][1]) < len(done[2]) <= len(requests[2][1]) + 3
    for rid, tokens in done.items():
        assert tokens == free[rid][:len(tokens)]
    assert _total(reports, "redone") == 0
    assert len(_decodes(reports)) == len(_decodes(theirs))
    # the requests that waited for a slot follow the ended ones into it
    assert set(done) == set(range(len(requests)))


@pytest.mark.parametrize("policy", [{}, CHUNKED], ids=["monolithic",
                                                       "chunked"])
def test_a_finished_sequences_pages_serve_the_next_prompt(policy):
    """A request is handed back while the dispatch that writes its last
    token may still run: a prompt that continues the finished sequence
    attaches its pages from the prefix cache and gets the waiting loop's
    tokens (whatever reads those pages is launched behind the write)."""
    make, kwargs = test_device_picks.FAMILIES["dense"]
    config = make()
    model = CausalTransformerLM(config)
    params = model.init(jax.random.key(3), jnp.float32)
    outputs = []
    for every in ({}, WAITS):
        engine = ServingEngine(
            model, params, dtype=jnp.float32, **dict(
                kwargs, serving={"prefix_cache": {"enabled": True},
                                 **policy}))
        first, _ = _serve(engine, _requests(config, 3)[:3], **every)
        # the longest finished sequence, and two tokens more
        again = [(0, first[2] + [1, 2], 5), (0, first[0] + [3], 4)]
        second, _ = _serve(engine, again, **every)
        assert engine.stats["prefix_hits"] >= 2
        outputs.append((first, second))
    assert outputs[0] == outputs[1]


def test_a_sampler_that_returns_another_token_has_the_row_redone(served):
    config, engine, _ = served
    requests = _requests(config, 4)
    original, calls = engine._sample, {}

    def sample(req, row):
        """Request 2's fourth and fifth tokens, and request 4's second,
        are one more than the pick."""
        token = original(req, row)
        calls[req.req_id] = calls.get(req.req_id, 0) + 1
        if (req.req_id, calls[req.req_id]) in ((2, 4), (2, 5), (4, 2)):
            return (token + 1) % config.vocab_size
        return token

    engine._sample = sample
    try:
        done, reports = _serve(engine, requests)
        calls.clear()
        waited, theirs = _serve(engine, requests, **WAITS)
    finally:
        engine._sample = original
    free, _ = _serve(engine, requests)
    assert done == waited
    assert done[2] != free[2] and done[4] != free[4]
    assert done[0] == free[0] and done[1] == free[1]
    # each of the three was fed as the pick and launched for nothing
    assert _total(reports, "redone") == 3
    assert _total(theirs, "redone") == 0
    assert _emitted(reports) == _emitted(theirs)


def test_a_sampled_request_in_the_batch_is_waited_for(served):
    """One sampled request and two greedy ones: the tokens of the loop
    that waits on the same seeds, and no step runs ahead while the
    sampled request is decoding."""
    config, engine, _ = served
    requests = [(0, p, new) for _, p, new in _requests(config, 5)[:3]]
    sampling = {1: dict(temperature=0.8, seed=11, top_k=0)}
    seen = []

    def watch(step):
        """While the sampled request decodes (a token of it is booked)."""
        if any(r is not None and r.temperature > 0 and r.out
               for r in engine.slots):
            seen.append(len(engine.scheduler._ahead))

    done, reports = _serve(engine, requests, sampling, each_step=watch)
    waited, _ = _serve(engine, requests, sampling, **WAITS)
    greedy, _ = _serve(engine, requests)
    assert done == waited
    assert done[0] == greedy[0] and done[2] == greedy[2]
    assert done[1] != greedy[1]
    assert seen and not any(seen)
    # request 1 ends first (3 tokens): the other two run ahead after it
    assert _total(reports, "ahead") > 0
    assert _total(reports, "redone") == 0


def _terminated(engine):
    return {rid: (r.status, r.tokens)
            for rid, r in engine.pop_terminated().items()}


def test_a_deadline_with_a_row_in_flight_ends_that_request_alone(served):
    config, engine, clock = served
    requests = _requests(config, 6)[:3]
    free, _ = _serve(engine, requests)
    flying = []

    def expire(step):
        """Once request 2 has two tokens and is owed its next."""
        owed = [d for d in engine.scheduler._ahead
                if any(req.req_id == 2 and len(req.out) >= 2
                       for req in d.owed.values())]
        if owed and not flying:
            flying.extend(owed)
            clock.t += 100.0

    done, reports = _serve(engine, requests,
                           sampling={2: dict(deadline_s=50.0)},
                           each_step=expire)
    ended = _terminated(engine)
    assert set(ended) == {2} and ended[2][0] == "deadline"
    assert ended[2][1] == free[2][:len(ended[2][1])]
    assert len(requests[2][1]) < len(ended[2][1]) < len(free[2])
    assert done == {0: free[0], 1: free[1]}
    assert flying[0].logits.record["redone"] == 1
    assert _total(reports, "redone") == 1


def test_a_sampler_fault_with_a_row_in_flight_evicts_that_request(served):
    config, engine, _ = served
    requests = [(0, p, new) for _, p, new in _requests(config, 7)[:2]]
    order, original = [], engine._sample
    engine._sample = lambda req, row: (order.append(req.req_id),
                                       original(req, row))[1]
    try:
        free, _ = _serve(engine, requests)
    finally:
        engine._sample = original
    # the serve_sample check that is request 0's third token (the pick of
    # its second decode step): the third dispatch has been fed it by then
    third = [i for i, rid in enumerate(order) if rid == 0][2]
    engine.injector = FaultInjector({"serve_sample": {"fail_at": [third],
                                                      "msg": "boom"}})
    try:
        done, reports = _serve(engine, requests)
    finally:
        engine.injector = None
    ended = _terminated(engine)
    assert set(ended) == {0} and ended[0][0] == "evicted"
    assert ended[0][1] == free[0][:len(requests[0][1]) + 2]
    assert done == {1: free[1]}
    assert _total(reports, "redone") == 1


def test_a_step_fault_leaves_the_dispatch_in_flight_where_it_is(served):
    config, engine, _ = served
    requests = _requests(config, 8)
    free, _ = _serve(engine, requests)
    engine.injector = FaultInjector({"serve_step": {"fail_at": [2, 3, 6]}})
    before = {}

    def step_and_watch(real_step=engine.step):
        flying = list(engine.scheduler._ahead)
        owed = [dict(d.owed) for d in flying]
        books = ([list(r.out) for r in engine.slots if r is not None],
                 engine.lengths.copy())
        out = real_step()
        if engine.stats["step_faults"] > before.get("faults", 0):
            before["faults"] = engine.stats["step_faults"]
            assert out == {}
            assert engine.scheduler._ahead == flying
            assert [dict(d.owed) for d in flying] == owed
            assert [list(r.out) for r in engine.slots
                    if r is not None] == books[0]
            assert (engine.lengths == books[1]).all()
        return out

    engine.step = step_and_watch
    try:
        done, _ = _serve(engine, requests)
    finally:
        engine.injector = None
        del engine.step
    assert before["faults"] == 3
    assert done == free


def test_a_drain_and_generate_leave_nothing_in_flight(served):
    config, engine, _ = served
    requests = _requests(config, 9)
    free, _ = _serve(engine, requests)
    for rid, (_, prompt, new) in enumerate(requests):
        engine.add_request(rid, prompt, max_new_tokens=new)
    for _ in range(3):
        engine.step()
    assert engine.scheduler._ahead
    # two more steps, then whatever decodes is shed, its row in flight
    report = engine.drain(max_steps=2)
    engine.draining = False
    assert engine.scheduler._ahead == [] and engine.n_active == 0
    assert engine.leak_report() == {}
    shed = _terminated(engine)
    assert set(report["shed"]) == set(shed) and shed
    for rid, (status, tokens) in shed.items():
        assert status == "drained" and tokens == free[rid][:len(tokens)]
    for rid, tokens in report["finished"].items():
        assert tokens == free[rid]
    # generate(): to the end, in order, nothing owed afterwards
    prompts = [p for _, p, _ in requests[:4]]
    out = engine.generate(prompts, max_new_tokens=5)
    assert engine.scheduler._ahead == [] and engine.n_active == 0
    waited, _ = _serve(engine, [(0, p, 5) for p in prompts], **WAITS)
    assert out == [waited[i] for i in range(len(prompts))]
    assert engine.leak_report() == {}


def test_the_harness_check_gets_one_bit_equal_row_a_token(served):
    """``chipbench/serve_cell.py:_logits_rows`` around an engine that runs
    ahead: one float32 row a token, now read a step after its dispatch
    was launched, the rows of the loop that waits to the bit, and nothing
    compiles once the first two decode steps have run."""
    config, engine, _ = served
    requests = _requests(config, 10)
    _serve(engine, requests)            # every shape has compiled
    _serve(engine, requests, **WAITS)
    mark = time.perf_counter_ns()
    with serve_cell._logits_rows(engine) as rows:
        done, reports = _serve(engine, requests)
    with serve_cell._logits_rows(engine) as theirs:
        waited, _ = _serve(engine, requests, **WAITS)
    assert get_telemetry().compile_log(since_ns=mark) == []
    assert done == waited
    for rid, (_, prompt, new) in enumerate(requests):
        assert len(rows[rid]) == new
        assert all(r.dtype == np.float32 for r in rows[rid])
        np.testing.assert_array_equal(np.stack(rows[rid]),
                                      np.stack(theirs[rid]))
        assert [int(np.argmax(r)) for r in rows[rid]] == \
            done[rid][len(prompt):]
    host_rows = sum(d.get("host_rows", 0) for r in reports
                    for d in r["dispatches"])
    assert host_rows == sum(new for _, _, new in ARRIVALS)
    assert _total(reports, "ahead") > 0


def test_one_decode_program_whether_fed_from_the_host_or_the_device(served):
    """The decode jit has compiled once however its rows were fed: the
    first step's zeros, the host's tokens, the picks of the dispatch
    before it."""
    _, engine, _ = served
    assert engine._step_fn._cache_size() == 1


@pytest.mark.parametrize("name", ["ahead_pct.chat", "ahead_pct.docbatch"])
def test_a_metrics_file_agrees_with_its_entry_and_reads_the_counter(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert {k: spec[k] for k in entry} == entry
    assert set(spec) - set(entry) == {"reducer", "args"}
    assert spec["reducer"] == "dispatch_counter_ratio"
    assert spec["args"] == {"over": "ahead", "under": "picked",
                            "scale": 100.0}
    assert entry["better"] == "higher"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == \
        "serve loop (inference/serving.py, scheduler.py)"
    (cell,) = entry["workloads"]
    moved = {"ahead_pct.chat": ("serve-olmo2-1b-chat", "tpot_p90_ms"),
             "ahead_pct.docbatch": ("serve-olmo2-1b-docbatch",
                                    "serve_tok_s")}[name]
    assert (cell, entry["moves"]) == moved

    def run(*dispatches):
        return types.SimpleNamespace(
            steps=[{"dispatches": list(dispatches)}], model={})

    read = dispatch_counter_ratio.read
    # the parent's dispatches carry no ``ahead``: nothing to read
    assert read(run({"phase": "decode", "picked": 3, "host_rows": 0},
                    {"phase": "prefill", "picked": 1, "host_rows": 0}),
                **spec["args"]) is None
    # a prefill's pick is in no decode dispatch's count
    assert read(run({"phase": "decode", "picked": 3, "ahead": 3,
                     "redone": 0},
                    {"phase": "prefill", "picked": 1, "host_rows": 0},
                    {"phase": "decode", "picked": 1, "ahead": 0,
                     "redone": 1}), **spec["args"]) == 75.0
