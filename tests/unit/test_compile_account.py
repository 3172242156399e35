"""The compile account (PR 39): one record for every program JAX traces,
lowers and compiles or reads from the persistent cache, with the site and
the span it happened in, and the ``setup/*`` spans, kept apart from the
span ring; ``Telemetry.compile_log`` and ``Telemetry.startup_report``.

CPU, toy sizes.
"""

import contextlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

import deepspeed_tpu
from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.monitor.telemetry import (SpanRing, get_telemetry,
                                             register_compiled, setup_span)
from deepspeed_tpu.parallel import groups

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_OPTIONS = {"jax_compilation_cache_dir": None,
                 "jax_persistent_cache_min_compile_time_secs": 0,
                 "jax_persistent_cache_min_entry_size_bytes": -1}


class Listener:
    """The test's own count of backend-compile events, as
    ``chipbench/device.py:CompileCounter`` keeps it; ``jax.monitoring``
    has no way to take a listener off, so one serves every test."""

    def __init__(self):
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, _secs, **_kw):
        self.compiles += event == BACKEND_COMPILE


@pytest.fixture(scope="module")
def listener():
    return Listener()


@contextlib.contextmanager
def _cache_at(directory):
    """JAX's persistent cache at ``directory``, taking every program (no
    cache with None), for the body alone."""
    before = {name: getattr(jax.config, name) for name in CACHE_OPTIONS}
    for name, value in dict(CACHE_OPTIONS,
                            jax_compilation_cache_dir=directory).items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


@pytest.fixture
def cache_dir(tmp_path):
    with _cache_at(str(tmp_path)):
        yield tmp_path


def _double(x):
    return x * 2 + 1


def test_a_site_call_is_a_miss_then_a_hit_and_a_repeat(cache_dir):
    tel = get_telemetry()
    site = register_compiled(jax.jit(_double), "test/double")
    x = jnp.arange(6.0).reshape(2, 3)
    mark = time.perf_counter_ns()
    site(x)
    site(x)                     # compiled: no record
    (first,) = [r for r in tel.compile_log(since_ns=mark) if r["site"]]
    assert first["cache"] == "miss" and "retrieval_s" not in first
    assert first["backend_s"] > 0 and first["trace_s"] > 0
    assert first["lower_s"] > 0 and not first["repeat"]
    assert (first["site"], first["shapes"]) == ("test/double", ((2, 3),))
    assert first["name"] == "jit(_double)"
    assert first["t0_ns"] < first["t1_ns"] <= time.perf_counter_ns()
    assert any(cache_dir.iterdir())
    jax.clear_caches()          # the executable is forgotten, the file not
    site(x)
    _, again = [r for r in tel.compile_log(since_ns=mark) if r["site"]]
    assert again["cache"] == "hit" and again["retrieval_s"] > 0
    assert again["repeat"] and again["shapes"] == ((2, 3),)
    site(jnp.arange(4.0))       # other shapes: a program of its own
    other = tel.compile_log(since_ns=mark)[-1]
    assert other["shapes"] == ((4,),) and not other["repeat"]
    report = tel.startup_report()
    mine = report["by_site"]["test/double"]
    assert (mine["programs"], mine["cache_hits"], mine["cache_misses"],
            mine["repeat_compiles"]) == (3, 1, 2, 1)
    assert mine["seconds"]["cache_read"] == again["backend_s"]
    assert mine["seconds"]["compile"] == \
        first["backend_s"] + other["backend_s"]


def test_a_lazy_reading_of_a_sites_text_is_a_repeat_of_that_site():
    site = register_compiled(jax.jit(lambda x: x - 3), "test/text")
    x = jnp.ones((5,))
    site(x)
    mark = time.perf_counter_ns()
    assert site.compiled_text()
    (record,) = [r for r in get_telemetry().compile_log(since_ns=mark)
                 if r["site"]]
    assert (record["site"], record["shapes"], record["repeat"]) == \
        ("test/text", ((5,),), True)


def test_a_records_seconds_fit_inside_the_call_that_made_it():
    """Every ``jnp`` function is a jit of its own, traced inside the outer
    trace and reported before it: hundreds of inner traces, one after
    another, are the outer trace's time and not time beside it."""
    def many(x):
        for i in range(300):
            x = jnp.sin(x) + i
        return x

    tel = get_telemetry()
    x = jnp.ones((4,))
    jax.eval_shape(jnp.sin, x)      # a trace that leads to no program
    mark = time.perf_counter_ns()
    jax.jit(many)(x)
    wall_s = (time.perf_counter_ns() - mark) / 1e9
    (record,) = [r for r in tel.compile_log(since_ns=mark)
                 if r["name"] == "jit(many)"]
    assert 0 < record["trace_s"] and 0 < record["lower_s"]
    assert record["trace_s"] + record["lower_s"] + record["backend_s"] \
        <= wall_s
    assert mark <= record["t0_ns"] < record["t1_ns"]


def test_without_a_cache_directory_a_record_says_off():
    mark = time.perf_counter_ns()
    with _cache_at(None):
        jax.jit(lambda x: x * 5)(jnp.ones((7,)))
    record = get_telemetry().compile_log(since_ns=mark)[-1]
    assert record["cache"] == "off" and record["backend_s"] > 0
    report = get_telemetry().startup_report()
    assert report["seconds"]["compile"] >= record["backend_s"]


def test_a_program_outside_any_site_names_the_span_it_ran_in():
    tel = get_telemetry()
    mark = time.perf_counter_ns()
    attrs = {"phase": "decode", "batch": 4, "tokens": 1}
    with tel.span("serve/loop"):
        with tel.span("serve/step", attrs=attrs):
            jax.jit(lambda x: x + 11)(jnp.ones((3,)))
    jax.jit(lambda x: x + 12)(jnp.ones((3,)))
    inside, outside = [r for r in tel.compile_log(since_ns=mark)
                       if r["name"] == "jit(<lambda>)"]
    assert inside["site"] is None and inside["shapes"] is None
    assert inside["span"] == "serve/step" and inside["span_attrs"] == attrs
    assert outside["span"] is None and outside["span_ids"] == ()
    by_name = {s.name: s for s in tel.spans(since_ns=mark)
               if s.name != "compile"}
    assert inside["span_ids"] == (by_name["serve/step"].id,
                                  by_name["serve/loop"].id)
    # ... and each record is a ``compile`` span of the ring
    compiles = [s for s in tel.spans(since_ns=mark) if s.name == "compile"]
    nested = [s for s in compiles if s.parent == by_name["serve/step"].id]
    assert nested and all(s.attrs == {"site": None, "cache": inside["cache"]}
                          for s in nested)
    assert (nested[-1].t0_ns, nested[-1].t1_ns) == \
        (inside["t0_ns"], inside["t1_ns"])
    assert compiles[-1].parent is None


def test_records_made_on_a_worker_thread_take_that_threads_span():
    tel = get_telemetry()
    mark = time.perf_counter_ns()

    def worker():
        with tel.span("engine/input_wait"):
            jax.jit(lambda x: x + 21)(jnp.ones((2,)))

    with tel.span("engine/train_batch"):
        thread = threading.Thread(target=worker)
        thread.start()
        thread.join()
        jax.jit(lambda x: x + 22)(jnp.ones((2,)))
    theirs, ours = [r for r in tel.compile_log(since_ns=mark)
                    if r["name"] == "jit(<lambda>)"]
    assert theirs["span"] == "engine/input_wait" and \
        len(theirs["span_ids"]) == 1
    assert ours["span"] == "engine/train_batch"


def test_the_account_outlives_a_ring_long_since_wrapped(monkeypatch,
                                                        listener):
    tel = get_telemetry()
    monkeypatch.setattr(tel, "ring", SpanRing(capacity=8))
    mark, before = time.perf_counter_ns(), listener.compiles
    with setup_span("setup/engine", kind="serving"):
        for i in range(3):
            jax.jit(lambda x, i=i: x + 31 + i)(jnp.ones((2,)))
    for i in range(100):
        with tel.span("serve/loop", step=i):
            pass
    assert len(tel.ring) == 8
    assert {s.name for s in tel.spans()} == {"serve/loop"}
    report = tel.startup_report()
    assert [s.name for s in report["spans"]].count("setup/import") == 1
    (engine,) = [s for s in report["spans"] if s.t0_ns >= mark]
    assert (engine.name, engine.attrs) == ("setup/engine",
                                           {"kind": "serving"})
    records = tel.compile_log(since_ns=mark)
    assert len(records) == listener.compiles - before >= 3
    assert all(r["span"] == "setup/engine" for r in records)
    assert report["seconds"]["import"] > 0


def test_a_setup_spans_seconds_leave_out_the_programs_made_inside_it():
    tel = get_telemetry()
    before = tel.startup_report()
    mark = time.perf_counter_ns()
    with setup_span("setup/engine", kind="train") as outer:
        with setup_span("setup/engine", kind="train"):      # its like
            time.sleep(0.02)
        with setup_span("setup/engine/state"):
            time.sleep(0.03)
            jax.jit(lambda x: x + 41)(jnp.ones((9,)))
    after = tel.startup_report()
    made = tel.compile_log(since_ns=mark)
    spent = sum(r["trace_s"] + r["lower_s"] + r["backend_s"] for r in made)
    outer_s = (after["spans"][-1].t1_ns - after["spans"][-1].t0_ns) / 1e9
    assert after["spans"][-1].id == outer.id
    grew = {k: after["seconds"][k] - before["seconds"].get(k, 0.0)
            for k in after["seconds"]}
    # the outer span alone counts as ``engine``, less the programs; its
    # part ``engine/state`` likewise
    assert grew["engine"] == pytest.approx(outer_s - spent, abs=1e-6)
    assert 0.03 <= grew["engine/state"] < grew["engine"]
    assert grew["import"] == 0.0
    assert grew["trace"] + grew["lower"] + grew["compile"] \
        + grew["cache_read"] == pytest.approx(spent, abs=1e-9)
    assert after["programs"] - before["programs"] == len(made)


def test_until_ns_cuts_records_and_spans():
    tel = get_telemetry()
    jax.jit(lambda x: x + 51)(jnp.ones((2,)))
    with setup_span("setup/engine", kind="inference"):
        pass
    cut = time.perf_counter_ns()
    jax.jit(lambda x: x + 52)(jnp.ones((2,)))
    with setup_span("setup/engine", kind="inference"):
        time.sleep(0.01)
    early, whole = tel.startup_report(until_ns=cut), tel.startup_report()
    assert early["programs"] == len(tel.compile_log(until_ns=cut)) \
        < whole["programs"]
    assert len(early["spans"]) == len(whole["spans"]) - 1
    assert whole["seconds"]["engine"] >= early["seconds"]["engine"] + 0.01
    assert all(r["t1_ns"] <= cut for r in early["slowest"])
    assert len(whole["slowest"]) <= 10
    assert tel.startup_report(until_ns=0)["programs"] == 0
    assert tel.startup_report(until_ns=0)["seconds"]["import"] == 0.0


# ----------------------------------------------------------------------
# the engines
# ----------------------------------------------------------------------
def _serving():
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    eng = ServingEngine(model, params, max_batch=2, page_size=8, max_seq=32,
                        dtype=jnp.float32)
    eng.add_request(0, list(range(1, 7)), max_new_tokens=3)
    while eng.queue or eng.n_active:
        eng.step()
    return {"serve/prefill_fn", "serve/step_fn"}


def _inference():
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    groups.reset_mesh()
    deepspeed_tpu.init_inference(model=model,
                                 params=model.init(jax.random.key(0)),
                                 dtype="float32")
    return set()


def _train():
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4)
    model = CausalTransformerLM(cfg)
    groups.reset_mesh()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.key(0)),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3}, "mesh": {"fsdp": 8}})
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 16),
                                            dtype=np.int32)
    for _ in range(2):
        engine.train_batch(batch={"input_ids": ids})
    return {"engine/train_step:1"}


@pytest.mark.parametrize("build, kind, part", [
    (_serving, "serving", "setup/engine/pools"),
    (_inference, "inference", "setup/engine/weights"),
    (_train, "train", "setup/engine/state")])
def test_an_engine_leaves_its_setup_spans_and_every_program(
        listener, build, kind, part):
    tel = get_telemetry()
    mark, before = time.perf_counter_ns(), listener.compiles
    sites = build()
    report = tel.startup_report()
    spans = [s for s in report["spans"] if s.t0_ns >= mark]
    (engine,) = [s for s in spans if s.name == "setup/engine"]
    assert engine.attrs == {"kind": kind}
    (child,) = [s for s in spans if s.name == part]
    assert child.parent == engine.id
    assert engine.t0_ns <= child.t0_ns <= child.t1_ns <= engine.t1_ns
    # the ring holds them too
    assert {s.name for s in tel.spans(since_ns=mark)} >= {"setup/engine",
                                                          part}
    records = tel.compile_log(since_ns=mark)
    assert len(records) == listener.compiles - before > 0
    assert sites <= {r["site"] for r in records}
    assert not any(r["repeat"] for r in records)
    first_steps = [r for r in records if r["site"] in sites]
    assert all(r["span"] in ("serve/step", "engine/dispatch")
               for r in first_steps)
    assert all(engine.id in r["span_ids"] for r in records
               if r["span"] and r["span"].startswith("setup/"))
