"""The program names its own work (PR 24): the span ring under
``Telemetry.span``, the serving engine's per-step report and token stream,
``op_scopes()``, and the fixed names on kernels and jits.

CPU, toy sizes, Pallas kernels interpreted.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.monitor.telemetry import (OP_PHASES, SPAN_NAMES, SpanRing,
                                             Telemetry, get_telemetry,
                                             op_scopes, parse_op_scopes,
                                             phase_of)
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.runtime.config import TelemetryConfig


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    return cfg, model, model.init(jax.random.key(0))


def _prompts(cfg, seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).tolist() for n in lengths]


# ----------------------------------------------------------------------
# the ring
# ----------------------------------------------------------------------
def test_spans_are_recorded_with_telemetry_disabled():
    tel = Telemetry()
    assert not tel.enabled
    mark = time.perf_counter_ns()
    with tel.span("engine/train_batch", step=7):
        with tel.span("engine/input", step=7):
            pass
        with tel.span("serve/prefill", req_id="r1", attrs={"bucket": 8}):
            pass
    outer, first, second = tel.spans(since_ns=mark)
    assert [s.name for s in (outer, first, second)] == \
        ["engine/train_batch", "engine/input", "serve/prefill"]
    assert outer.parent is None
    assert first.parent == outer.id and second.parent == outer.id
    assert outer.key == 7 and second.key == "r1"
    assert second.attrs == {"bucket": 8}
    assert outer.t0_ns <= first.t0_ns <= first.t1_ns <= second.t0_ns \
        <= second.t1_ns <= outer.t1_ns
    # disabled: no histogram, no event, the ring alone
    assert tel.registry.histograms == {}


def test_each_telemetry_object_has_its_own_ring_and_parents_cross():
    mine = Telemetry()
    mark = time.perf_counter_ns()
    with mine.span("serve/loop"):
        with get_telemetry().span("serve/admit"):
            pass
    (loop,), (admit,) = mine.spans(), get_telemetry().spans(since_ns=mark)
    assert admit.parent == loop.id      # one thread, one stack of spans
    assert mine.ring is not get_telemetry().ring


def test_enabled_adds_histogram_and_event(tmp_path):
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": str(tmp_path),
                         "job_name": "ring"}), rank=0)
    mark = time.perf_counter_ns()
    with tel.span("serve/prefill", req_id="r9", attrs={"bucket": 16}):
        pass
    tel.close()
    assert [s.name for s in tel.spans(since_ns=mark)] == ["serve/prefill"]
    assert len(tel.registry.histograms["span/serve/prefill"].values()) == 1
    import json
    events = [json.loads(line) for line in
              open(tmp_path / "ring" / "events.jsonl")]
    span = [e for e in events if e["kind"] == "span"][0]
    assert span["name"] == "serve/prefill"
    assert span["attrs"] == {"bucket": 16, "req_id": "r9"}


def test_parent_is_the_span_open_on_the_same_thread():
    tel = Telemetry()
    mark = time.perf_counter_ns()
    inside = threading.Event()
    release = threading.Event()

    def worker():
        with tel.span("engine/input_wait"):
            with tel.span("engine/input"):
                inside.set()
                release.wait(5)

    thread = threading.Thread(target=worker)
    with tel.span("engine/train_batch"):
        thread.start()
        inside.wait(5)
        with tel.span("engine/dispatch"):    # while the worker's are open
            pass
        release.set()
        thread.join()
    by_name = {s.name: s for s in tel.spans(since_ns=mark)}
    assert by_name["engine/dispatch"].parent == \
        by_name["engine/train_batch"].id
    assert by_name["engine/input_wait"].parent is None
    assert by_name["engine/input"].parent == by_name["engine/input_wait"].id


def test_ring_wraps_around_and_filters_by_time():
    tel = Telemetry()
    small = tel.ring = SpanRing(capacity=4)
    for i in range(10):
        with tel.span("serve/loop", step=i):
            pass
    kept = tel.spans()
    assert len(small) == 4 and [s.key for s in kept] == [6, 7, 8, 9]
    assert [s.key for s in tel.spans(since_ns=kept[2].t0_ns)] == [8, 9]
    assert [s.key for s in tel.spans(until_ns=kept[1].t1_ns)] == [6, 7]
    assert [s.key for s in tel.spans(kept[1].t0_ns, kept[2].t1_ns)] == [7, 8]
    assert tel.spans(since_ns=kept[-1].t1_ns + 1) == []


def test_span_cost_is_inside_the_budget():
    """At most 8 spans a serving step in under 20 us, 4 a training step
    in under 10 us: 2.5 us a span, nobody reading.  Measured as the
    thread's own CPU time in short rounds, the best of them, and for up to
    ten seconds, so that the other test processes on the machine delay
    this test and do not fail it."""
    tel = Telemetry()
    attrs = {"batch": 32, "ready": 20, "tokens": 1}

    def one_round(n=1000):
        t0 = time.thread_time_ns()
        for i in range(n):
            with tel.span("serve/decode", attrs=attrs):
                pass
        return (time.thread_time_ns() - t0) / n / 1e3

    best_us, deadline = one_round(), time.monotonic() + 10.0
    while best_us >= 2.5 and time.monotonic() < deadline:
        best_us = min([best_us] + [one_round() for _ in range(10)])
        time.sleep(0.02)
    assert 8 * best_us < 20.0 and 4 * best_us < 10.0, best_us


def test_span_vocabulary_matches_the_checker():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "scripts", "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("checker", path)
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    assert tuple(checker.SPAN_NAMES) == tuple(SPAN_NAMES)
    assert len(set(SPAN_NAMES)) == len(SPAN_NAMES)


# ----------------------------------------------------------------------
# ServingEngine.last_step / step_reports()
# ----------------------------------------------------------------------
SCHEDULERS = {
    "monolithic": dict(),
    "chunked": dict(serving={"scheduler": {"policy": "chunked",
                                           "prefill_chunk_tokens": 8}}),
    "decode_chunk": dict(decode_chunk=4),
    "speculative": dict(serving={"scheduler": {
        "policy": "chunked", "prefill_chunk_tokens": 8,
        "speculative": {"enabled": True, "num_draft_tokens": 3}}}),
}
# spans one step() may open, its prefills' apart: loop, admit, decode,
# build, step, fetch, sample; the speculative step has a second dispatch
# (the draft) with its fetch
SPAN_BUDGET = {"monolithic": 8, "chunked": 8, "decode_chunk": 8,
               "speculative": 10}


def _engine(tiny, mode):
    cfg, model, params = tiny
    extra = dict(SCHEDULERS[mode])
    if mode == "speculative":
        extra.update(draft_model=model, draft_params=params)
    return ServingEngine(model, params, max_batch=4, page_size=8,
                         max_seq=64, dtype=jnp.float32, **extra)


@pytest.mark.parametrize("mode", list(SCHEDULERS))
def test_step_report_accounts_for_every_token(tiny, mode):
    cfg, _, _ = tiny
    eng = _engine(tiny, mode)
    prompts = _prompts(cfg, 11, (5, 12, 3, 9, 17, 6))
    budgets = [7, 4, 9, 5, 6, 8]
    mark = time.perf_counter_ns()
    for i, (p, n) in enumerate(zip(prompts, budgets)):
        eng.add_request(i, p, max_new_tokens=n)
    done, steps = {}, 0
    while eng.queue or eng.n_active:
        before = time.perf_counter_ns()
        done.update(eng.step())
        steps += 1
        report = eng.last_step
        assert before <= report["t0_ns"] <= report["t1_ns"] \
            <= time.perf_counter_ns()
        assert report["active"] == eng.n_active
        assert report["queued"] == len(eng.queue)
        for d in report["dispatches"]:
            assert {"phase", "batch", "tokens", "t0_ns", "t1_ns"} <= set(d)
            assert d["t0_ns"] <= d["t1_ns"] <= report["t1_ns"]
    assert eng.leak_report() == {}
    reports = eng.step_reports()
    assert len(reports) == steps and reports[-1] is eng.last_step
    # every generated token is in exactly one report, stamped in order
    emitted = {}
    for report in reports:
        for rid, n, t_ns in report["emitted"]:
            assert n >= 1 and mark <= t_ns <= report["t1_ns"]
            emitted[rid] = emitted.get(rid, 0) + n
    generated = {rid: len(seq) - len(prompts[rid])
                 for rid, seq in done.items()}
    assert emitted == generated == dict(enumerate(budgets))
    # every prompt token became available in exactly one report
    assert sum(r["prompt_tokens"] for r in reports) == \
        sum(len(p) for p in prompts)
    prefills = [d for r in reports for d in r["dispatches"]
                if d["phase"] == "prefill"]
    assert sum(d["real"] for d in prefills) == sum(len(p) for p in prompts)
    assert all(d["real"] <= d["tokens"] and d["context"] >= d["real"]
               for d in prefills)
    decodes = [d for r in reports for d in r["dispatches"]
               if d["phase"] in ("decode", "decode_chunk", "spec_verify")]
    assert decodes and all(d["contexts"] for d in decodes)
    # the tracer's per-token stamps come from the same place
    for tr in eng.tracer.completed:
        assert len(tr.token_times) == budgets[tr.req_id]
        assert len(tr.tpot_gaps_ms()) == budgets[tr.req_id] - 1
    # the span tree: one serve/loop a step, within the budget
    spans = get_telemetry().spans(since_ns=mark)
    loops = [s for s in spans if s.name == "serve/loop"]
    assert len(loops) == steps
    assert {s.name for s in spans} <= set(SPAN_NAMES)
    kids = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)

    def count(span, inside_prefill=False):
        # the programs a first step compiles are the compile account's
        # ``compile`` spans, not spans the step opened
        inside_prefill = inside_prefill or span.name == "serve/prefill"
        return (not inside_prefill and span.name != "compile") + sum(
            count(c, inside_prefill) for c in kids.get(span.id, ()))
    assert max(count(loop) for loop in loops) <= SPAN_BUDGET[mode]
    for s in spans:
        if s.name == "serve/prefill":
            assert {"bucket", "real", "cached"} <= set(s.attrs)
            assert len(kids.get(s.id, ())) <= 4     # 5 with itself


def _watch_sample(eng):
    """A Probe-style patch of ``_sample``, as ``chipbench/serve_cell.py``
    makes it: the host time of each call, by request."""
    seen = {}
    original = eng._sample

    def sample(req, row):
        seen.setdefault(req.req_id, []).append(time.perf_counter_ns())
        return original(req, row)
    eng._sample = sample
    return seen


def _drive(eng, cfg):
    prompts = _prompts(cfg, 5, (6, 10, 4))
    for i, p in enumerate(prompts):
        eng.add_request(i, p, max_new_tokens=9)
    while eng.queue or eng.n_active:
        eng.step()
    stamps = {}
    for report in eng.step_reports():
        for rid, n, t_ns in report["emitted"]:
            stamps.setdefault(rid, []).extend([t_ns] * n)
    return stamps


def test_report_stamps_match_a_patched_sample_under_monolithic(tiny):
    eng = _engine(tiny, "monolithic")
    seen = _watch_sample(eng)
    stamps = _drive(eng, tiny[0])
    assert {r: len(t) for r, t in seen.items()} == {0: 9, 1: 9, 2: 9}
    for rid, times in seen.items():
        assert len(stamps[rid]) == len(times)
        assert max(abs(a - b) for a, b in zip(stamps[rid], times)) < 1e6


def test_report_sees_tokens_a_patched_sample_cannot(tiny):
    """``decode_chunk=4`` samples on the device: the patch sees the
    prefill's token of each request and nothing after it; the report
    sees all nine."""
    eng = _engine(tiny, "decode_chunk")
    seen = _watch_sample(eng)
    stamps = _drive(eng, tiny[0])
    assert {r: len(t) for r, t in seen.items()} == {0: 1, 1: 1, 2: 1}
    assert {r: len(t) for r, t in stamps.items()} == {0: 9, 1: 9, 2: 9}


def test_arrived_at_counts_queue_wait_from_arrival(tiny):
    cfg, model, params = tiny
    now = [100.0]
    eng = ServingEngine(model, params, max_batch=1, page_size=8, max_seq=32,
                        dtype=jnp.float32, clock=lambda: now[0])
    eng.add_request("late", _prompts(cfg, 1, (4,))[0], max_new_tokens=2,
                    arrived_at=97.5)
    eng.add_request("plain", _prompts(cfg, 2, (4,))[0], max_new_tokens=2)
    while eng.queue or eng.n_active:
        now[0] += 1.0
        eng.step()
    done = {tr.req_id: tr for tr in eng.tracer.completed}
    assert done["late"].queue_wait_ms() == 2500.0
    assert done["late"].ttft_ms() == 2500.0
    assert done["late"].e2e_ms() == 2000.0       # admission to terminal
    assert done["plain"].queue_wait_ms() == 2000.0


# ----------------------------------------------------------------------
# names on the device's lines
# ----------------------------------------------------------------------
def test_phase_of_paths():
    step = "jit(train_step)/while/body/closed_call/"
    assert phase_of(step + "jvp(fwd)/while/body/closed_call/attn/"
                    "dot_general") == "fwd"
    assert phase_of(step + "transpose(jvp(fwd))/while/body/closed_call/"
                    "checkpoint/rematted_computation/mlp/dot_general") \
        == "remat"
    assert phase_of(step + "transpose(jvp(fwd))/while/body/closed_call/"
                    "checkpoint/rematted_computation/transpose(jvp(mlp))/"
                    "dot_general") == "bwd"
    assert phase_of(step + "transpose(jvp(fwd))/mlp/dot_general") == "bwd"
    assert phase_of(step + "jvp(fwd)/loss_head/while/body/dot_general") \
        == "loss_head"
    assert phase_of(step + "transpose(jvp(fwd))/loss_head/mul") \
        == "loss_head"
    assert phase_of("jit(train_step)/optimizer/add") == "optimizer"
    assert phase_of("jit(train_step)/grad_reduce/convert") == "grad_reduce"
    assert phase_of("jit(train_step)/bwd/div") == "bwd"
    assert phase_of("jit(train_step)/jit(_threefry_split)/xor") == "other"
    assert {phase_of(p) for p in ("a/b", "")} == {"other"}


def test_parse_op_scopes_on_made_up_text():
    text = '''HloModule jit_train_step

%fused_computation.1 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %add.9 = f32[8]{0} add(%p.1, %p.1), metadata={op_name="jit(train_step)/transpose(jvp(fwd))/mlp/add"}
}

ENTRY %main.3 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  %fusion.4 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.1
  %copy.2 = f32[8]{0} copy(%fusion.4)
  ROOT %fusion.5 = f32[8]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/optimizer/mul"}
}
'''
    table = parse_op_scopes(text)
    assert table["add.9"] == "bwd"
    assert table["fusion.4"] == "bwd"       # no name of its own: its body's
    assert table["fusion.5"] == "optimizer"
    # compiler-made, calling nothing: the next instruction's phase
    assert table["copy.2"] == "optimizer" and table["x.1"] == "bwd"
    assert table["p.1"] == "bwd"
    assert set(table.values()) <= set(OP_PHASES)
    # ... else the one before; an op_name that names no phase stays other
    tail = parse_op_scopes(text.replace(
        "  ROOT %fusion.5", '  %rng.1 = u32[2]{0} xor(%x.1, %x.1), metadata='
        '{op_name="jit(train_step)/jit(_threefry_split)/xor"}\n'
        "  %copy.7 = f32[8]{0} copy(%copy.2)\n  ROOT %fusion.5").replace(
        ', metadata={op_name="jit(train_step)/optimizer/mul"}', ""))
    assert tail["rng.1"] == "other"
    assert tail["copy.2"] == tail["copy.7"] == tail["fusion.5"] == "bwd"


@pytest.fixture(scope="module")
def toy_trainer():
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, remat=True,
                                 remat_policy="dots_saveable",
                                 loss_chunk_size=32)
    model = CausalTransformerLM(cfg)
    groups.reset_mesh()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.key(0)),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3},
                "mesh": {"fsdp": 8}})
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16, 32),
                                            dtype=np.int32)
    yield engine, {"input_ids": ids}
    groups.reset_mesh()


def test_train_batch_spans_and_op_scopes(toy_trainer):
    engine, batch = toy_trainer
    assert not engine.telemetry.enabled
    mark = time.perf_counter_ns()
    losses = [float(engine.train_batch(batch=batch)) for _ in range(2)]
    assert losses[1] < losses[0]
    # (the first call's program is the compile account's ``compile`` span)
    spans = [s for s in get_telemetry().spans(since_ns=mark)
             if s.name != "compile"]
    assert [s.name for s in spans] == ["engine/train_batch", "engine/input",
                                       "engine/dispatch"] * 2
    outer = spans[0]
    assert outer.key == 0 and spans[3].key == 1         # the step
    assert {s.parent for s in spans[1:3]} == {outer.id}
    # the table of the program this engine compiled, asked for by site
    table = op_scopes("engine/train_step")
    assert table == op_scopes("engine/train_step:2")
    found = {phase: sum(p == phase for p in table.values())
             for phase in OP_PHASES}
    for phase in ("fwd", "bwd", "remat", "loss_head", "optimizer"):
        assert found[phase] >= 1, found
    assert op_scopes("engine/no_such_site") == {}


def test_a_site_prefix_finds_the_newest_program_never_a_mixture():
    """Two live owners, one site prefix (two trainers' ``:1`` and ``:2``):
    the table is the later one's alone."""
    from deepspeed_tpu.monitor.telemetry import register_compiled

    def older(x):
        with jax.named_scope("optimizer"):
            return x * 2.0

    def newer(x):
        with jax.named_scope("loss_head"):
            return jnp.sin(x) @ x

    # registered in the other order than the names sort
    second = register_compiled(jax.jit(older), "toy/site:2")
    first = register_compiled(jax.jit(newer), "toy/site:1")
    second(jnp.ones((4, 4)))
    first(jnp.ones((4, 4)))
    table = op_scopes("toy/site")
    assert table and table == op_scopes("toy/site:1")
    assert "loss_head" in table.values()
    assert "optimizer" not in table.values()
    assert "optimizer" in op_scopes("toy/site:2").values()
    del first
    assert "optimizer" in op_scopes("toy/site").values()


def test_train_step_text_names_the_flash_kernels():
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q) \
        .as_text(debug_info=True)
    for name in ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv"):
        assert name in text, name


def test_serve_programs_name_their_jits_and_kernels(tiny):
    cfg, model, params = tiny
    eng = ServingEngine(model, params, max_batch=2, page_size=8, max_seq=32,
                        dtype=jnp.float32,
                        serving={"attention_backend": "pallas-interpret"})
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)   # noqa
    width = eng.tables.shape[1]
    prefill = eng._prefill_fn.lower(
        eng.params, ints(1, 16), eng.caches, ints(1, width), ints(1),
        ints(1, 1))
    decode = eng._step_fn.lower(
        eng.params, ints(2, 1), eng.caches, ints(2, width), ints(2),
        ints(2, 1))
    assert "jit_serve_prefill" in prefill.as_text()
    assert "jit_serve_decode" in decode.as_text()
    assert "ragged_paged_attention_prefill" in \
        prefill.as_text(debug_info=True)
    assert "ragged_paged_attention_decode" in decode.as_text(debug_info=True)
    assert "ragged_paged_attention_prefill" not in \
        decode.as_text(debug_info=True)
    # the engine dispatches each phase through its own jit
    mark = time.perf_counter_ns()
    eng.add_request("r", _prompts(cfg, 3, (5,))[0], max_new_tokens=3)
    while eng.queue or eng.n_active:
        eng.step()
    phases = [s.attrs["phase"] for s in get_telemetry().spans(since_ns=mark)
              if s.name == "serve/step"]
    assert phases == ["prefill", "decode", "decode", "decode"]
    # both are registered sites: each has a table of its own
    assert op_scopes("serve/step_fn") and op_scopes("serve/prefill_fn")
