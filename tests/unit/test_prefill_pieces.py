"""A long prompt's whole-prompt prefill pads to the next 512 rows and goes
as pieces of shapes the engine compiles anyway (PR 44).

``MonolithicScheduler.prefill_pieces`` shapes a suffix longer than
``serving.PREFILL_PIECE_ROWS`` as the binary expansion of its next multiple
of that, largest first; ``ServingEngine._prefill`` launches the pieces back
to back, each at the position the one before it reached, and fetches and
samples from the last alone.  Held here, at toy widths in float32 on the
CPU (an OLMo-2-like block: flat RMS norm on q and k, norms after the
sublayers, SwiGLU), ``jnp`` and the Pallas kernels through the interpreter:
the logits rows and greedy tokens are those of the same prompt sent in one
bucket, with and without a cached prefix; the shapes are a table; a window
model and a latent model with an indexer keep one bucket; the step report
and the one ``serve/prefill`` span account for every piece; a fault at the
second piece costs that request alone; and the mix of the docbatch cell
compiles three prefill programs, all of them buckets, once.
"""

import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import serve_cell
from deepspeed_tpu.inference.scheduler import MonolithicScheduler
from deepspeed_tpu.inference.serving import (PREFILL_PIECE_ROWS,
                                             ServingEngine)
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.monitor.telemetry import get_telemetry
from unit import (test_dense_latent_serving, test_latent_serving,
                  test_window_paged_serving)

PAGE, MAX_SEQ, NEW = 64, 4096, 4
BACKENDS = ("jnp", "pallas-interpret")
# float32 on both sides; a piece's products are tiled otherwise than a
# bucket's, so rows differ by summation order (1e-6 of the largest logit
# here, where a dropped or misplaced page reads of the order of 1)
TOL = 2e-5
# on both sides of every multiple of 512 a 4,096 context leaves room for:
# one bucket (512, 513 -> 1,024, 3,585 -> 4,096), two pieces, three
LENGTHS = (512, 513, 1025, 1536, 1537, 2049, 2561, 3072, 3073, 3584, 3585)
# the four documents of docbatch's cycle (chipbench/traffic/
# docbatch-closed.json through traffic.quantile_grid)
DOCBATCH = (1344, 1984, 2624, 3264)


def _pieces(n):
    """What the issue says of a suffix of ``n`` under a cap of 4,096."""
    if n <= 512:
        return [max(8, 1 << (n - 1).bit_length())]
    padded = -(-n // 512) * 512
    return [p for p in (4096, 2048, 1024, 512) if padded & p]


@pytest.fixture(scope="module")
def toy():
    model = CausalTransformerLM(TransformerConfig.tiny(
        hidden_size=64, n_heads=4, n_kv_heads=2, qk_norm="rms_flat",
        post_norm_only=True, activation="silu", max_seq_len=MAX_SEQ))
    return model, model.init(jax.random.key(0), jnp.float32)


def _engine(toy, backend="jnp", kind="pieces"):
    model, params = toy
    engine = ServingEngine(
        model, params, max_batch=2, page_size=PAGE, max_seq=MAX_SEQ,
        dtype=jnp.float32, serving={
            "attention_backend": backend,
            "prefix_cache": {"enabled": kind == "prefix-cache"}})
    if kind == "one-bucket":
        # the other side of the comparison: every prompt one bucket, as an
        # engine that builds no prefill onto a context has it
        engine.prefill_piece_rows = 0
    return engine


@pytest.fixture(scope="module")
def engines(toy):
    """``engines(backend, kind)``: the engine as built (``pieces``), one
    held to one bucket, one with a prefix cache; each is built once for
    the module, so its programs compile once, and every test leaves it
    empty."""
    built = {}

    def get(backend="jnp", kind="pieces"):
        if (backend, kind) not in built:
            built[backend, kind] = _engine(toy, backend, kind)
        return built[backend, kind]
    return get


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, n).tolist()


def _serve(engine, prompts, new=NEW):
    """Serve ``prompts`` to the end: ({id: the logits rows it was sampled
    from}, {id: tokens}, the prefill dispatches, the ``serve/prefill``
    spans) of this run."""
    mark = time.perf_counter_ns()
    with serve_cell._logits_rows(engine) as rows:
        for rid, prompt in enumerate(prompts):
            engine.add_request(rid, prompt, max_new_tokens=new)
        done = {}
        while engine.queue or engine.n_active:
            done.update(engine.step())
    assert engine.leak_report() == {}
    dispatches = [d for r in engine.step_reports() if r["t0_ns"] >= mark
                  for d in r["dispatches"] if d["phase"] == "prefill"]
    spans = [s for s in get_telemetry().spans(mark)
             if s.name == "serve/prefill"]
    return ({rid: np.stack(r) for rid, r in rows.items()}, done, dispatches,
            spans)


def _same(rows, want):
    assert rows.shape == want.shape
    assert np.max(np.abs(rows - want)) <= TOL * np.max(np.abs(want))


# ----------------------------------------------------------------------
# the result is the same result
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n", LENGTHS)
def test_pieces_give_the_rows_and_tokens_of_one_bucket(engines, backend, n):
    split, whole = engines(backend), engines(backend, "one-bucket")
    prompt = _prompt(n, seed=n)
    rows, tokens, dispatches, _ = _serve(split, [prompt])
    want_rows, want_tokens, one, _ = _serve(whole, [prompt])
    assert [d["tokens"] for d in dispatches] == _pieces(n)
    assert [d["tokens"] for d in one] == [min(split._bucket(n), MAX_SEQ)]
    assert tokens == want_tokens
    _same(rows[0], want_rows[0])


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("suffix", (1100, 2100))
def test_pieces_after_a_cached_prefix(engines, backend, suffix):
    """The pieces start from the cached tokens, page-aligned or not (a
    partial page is copied on write): 640 of the document's 700 are shared
    as whole pages, 30 more on a copied one."""
    split = engines(backend, "prefix-cache")
    whole = engines(backend, "one-bucket")
    document = _prompt(700, seed=suffix + 1)
    prompt = document[:670] + _prompt(suffix, seed=suffix)
    hits = split.stats["prefix_hits"]
    _serve(split, [document])
    rows, tokens, dispatches, spans = _serve(split, [prompt])
    want_rows, want_tokens, _, _ = _serve(whole, [prompt])
    cached = spans[0].attrs["cached"]
    assert 640 <= cached <= 670 and split.stats["prefix_hits"] == hits + 1
    assert [d["tokens"] for d in dispatches] == _pieces(len(prompt) - cached)
    assert len(dispatches) == 2
    assert [d["context"] - d["real"] for d in dispatches] == \
        [cached, cached + dispatches[0]["tokens"]]
    assert tokens == want_tokens
    _same(rows[0], want_rows[0])


def test_a_latent_model_without_a_selection_takes_pieces_too():
    """``mix_latent_dense`` attends from entries already in the pool (the
    chunked policy runs it so): under the monolithic policy a long prompt
    is pieces, and the row it samples from is the whole model's."""
    _, model, _ = test_dense_latent_serving.toy("kimi_k2")
    params = model.init(jax.random.key(11), jnp.float32)
    engine = ServingEngine(model, params, max_batch=2, page_size=PAGE,
                           max_seq=2048, dtype=jnp.float32)
    assert engine.prefill_piece_rows == PREFILL_PIECE_ROWS
    prompt = _prompt(1100)
    rows, _, dispatches, _ = _serve(engine, [prompt], new=1)
    assert [d["tokens"] for d in dispatches] == [1024, 512]
    want = jax.jit(lambda ids: model.apply(params, ids, train=False)[0, -1:])(
        jnp.asarray(prompt)[None, :])
    _same(rows[0], np.asarray(want, np.float32))


# ----------------------------------------------------------------------
# the shapes
# ----------------------------------------------------------------------
def _scheduler(max_seq, rows=PREFILL_PIECE_ROWS):
    return MonolithicScheduler(types.SimpleNamespace(
        prefill_piece_rows=rows, max_seq=max_seq,
        _bucket=lambda n: ServingEngine._bucket(None, n)), None)


@pytest.mark.parametrize("max_seq", (256, 2048, 4096, 3000, 16384))
def test_padded_lengths_are_a_table(max_seq):
    sched = _scheduler(max_seq)
    buckets = {sched.engine._bucket(n) for n in range(1, max_seq + 1)}
    for n in range(1, max_seq + 1):
        pieces = sched.prefill_pieces(n)
        padded = sched.prefill_padded_len(n)
        one_bucket = min(sched.engine._bucket(n), max_seq)
        assert padded == sum(pieces) >= n
        if n <= PREFILL_PIECE_ROWS:
            assert pieces == [one_bucket]
        elif -(-n // 512) * 512 <= max_seq:
            assert padded == -(-n // 512) * 512 <= one_bucket
            assert pieces == sorted(pieces, reverse=True)
            assert len(set(pieces)) == len(pieces)
            assert set(pieces) <= buckets and min(pieces) >= 512
            assert (len(pieces) == 1) == (padded == one_bucket)
        else:       # a cap that is no multiple of 512: its own shape
            assert pieces == [max_seq] == [one_bucket]
    assert PREFILL_PIECE_ROWS == 512


def test_the_issues_examples():
    sched = _scheduler(4096)
    assert {n: sched.prefill_pieces(n) for n in
            (8, 300, 512, 1536, 2000, 2560, 3584, 3585)} == {
        8: [8], 300: [512], 512: [512], 1536: [1024, 512], 2000: [2048],
        2560: [2048, 512], 3584: [2048, 1024, 512], 3585: [4096]}
    assert [sched.prefill_padded_len(n) for n in DOCBATCH] == \
        [1536, 2048, 3072, 3584]


@pytest.mark.parametrize("family", ("window", "indexer", "page"))
def test_engines_without_a_prefill_onto_a_context_keep_one_bucket(family):
    """Not by name: a window layer's ring is filled from an empty context,
    a selection over cached index keys takes one query.  Nor where a piece
    would not start on a page."""
    page = 8
    if family == "window":
        config = test_window_paged_serving.config()
    elif family == "indexer":
        config = test_latent_serving.config()
    else:
        config, page = TransformerConfig.tiny(max_seq_len=2048), 96
    model = CausalTransformerLM(config)
    engine = ServingEngine(
        model, model.init(jax.random.key(3), jnp.float32), max_batch=2,
        page_size=page, max_seq=2048, num_pages=2048 // page + 40,
        dtype=jnp.float32, serving={"attention_backend": "jnp"})
    assert engine.prefill_piece_rows == 0
    assert engine.scheduler.meta()["prefill_piece_rows"] == 0
    for n in range(1, 2049):
        assert engine.scheduler.prefill_pieces(n) == \
            [min(engine._bucket(n), 2048)]
    _, tokens, dispatches, spans = _serve(engine, [_prompt(1100)], new=2)
    assert [(d["tokens"], d["real"]) for d in dispatches] == [(2048, 1100)]
    assert spans[0].attrs["bucket"] == 2048 and spans[0].attrs["pieces"] == 1
    assert engine.scheduler.snapshot()["prefills_split"] == 0
    assert len(tokens[0]) == 1102


# ----------------------------------------------------------------------
# the records
# ----------------------------------------------------------------------
def test_the_report_and_the_span_account_for_every_piece(engines):
    engine = engines()
    before = dict(engine.scheduler.sched_stats)
    prompts = [_prompt(n, seed=n) for n in (3264, 700, 300)]
    _, _, dispatches, spans = _serve(engine, prompts)
    by_request, at = [], 0
    for n in (3264, 700, 300):
        k = len(_pieces(n))
        by_request.append(dispatches[at:at + k])
        at += k
    assert at == len(dispatches) == 5
    for n, pieces, span in zip((3264, 700, 300), by_request, spans):
        assert [d["tokens"] for d in pieces] == _pieces(n)
        # every piece but the last is all prompt; each starts where the
        # one before it ended, and the last ends at the prompt's end
        assert [d["real"] for d in pieces[:-1]] == _pieces(n)[:-1]
        assert sum(d["real"] for d in pieces) == n
        assert [d["context"] for d in pieces] == \
            list(np.cumsum([d["real"] for d in pieces]))
        # the same program its shape always has: the head on one row, and
        # only the last piece's is picked from
        assert [d["head_rows"] for d in pieces] == [1] * len(pieces)
        assert [d["picked"] for d in pieces] == [0] * (len(pieces) - 1) + [1]
        assert span.attrs["bucket"] == sum(_pieces(n))
        assert span.attrs["pieces"] == len(pieces)
        assert (span.attrs["real"], span.attrs["cached"]) == (n, 0)
    # what the padding reducer reads: 3,584 + 1,024 + 512 positions for
    # 4,264 tokens, where one bucket each would be 4,096 + 1,024 + 512
    assert sum(d["tokens"] - d["real"] for d in dispatches) == 5120 - 4264
    stats = engine.scheduler.sched_stats
    assert stats["prefill_pieces"] - before["prefill_pieces"] == 5
    assert stats["prefills_split"] - before["prefills_split"] == 1
    assert engine.scheduler.meta()["prefill_piece_rows"] == 512
    assert engine.health()["scheduler"]["prefill_pieces"] == \
        stats["prefill_pieces"]


def test_a_fault_at_the_second_piece_evicts_that_request_alone(engines):
    engine = engines()
    launch, prefills = engine._run_step, []

    def faulty(ids, tables, lengths, phase="decode"):
        if phase == "prefill":
            prefills.append(ids.shape[1])
            if len(prefills) == 2:
                raise RuntimeError("injected at the second piece")
        return launch(ids, tables, lengths, phase=phase)

    free = engine.alloc.free_page_count
    engine._run_step = faulty
    try:
        engine.add_request("lost", _prompt(1300), max_new_tokens=NEW)
        engine.add_request("kept", _prompt(1400, seed=2), max_new_tokens=NEW)
        done = {}
        while engine.queue or engine.n_active:
            done.update(engine.step())
    finally:
        engine._run_step = launch
    # the first request's first piece ran, its second raised; the second
    # request's two pieces followed
    assert prefills == [1024, 512, 1024, 512]
    assert list(done) == ["kept"] and len(done["kept"]) == 1400 + NEW
    lost = engine.pop_terminated()["lost"]
    assert (lost.status, lost.n_generated) == ("evicted", 0)
    assert "second piece" in lost.detail
    assert engine.alloc.free_page_count == free
    assert engine.leak_report() == {}
    # and the slot serves the same prompt afterwards
    _, tokens, _, _ = _serve(engine, [_prompt(1300)])
    assert len(tokens[0]) == 1300 + NEW


# ----------------------------------------------------------------------
# no new compiled shape
# ----------------------------------------------------------------------
def test_docbatch_lengths_compile_three_buckets_once(toy):
    """The four documents of docbatch's cycle run the prefill programs of
    512, 1,024 and 2,048 rows, which the power-of-two policy compiles for
    prompts of 512 to 2,048 tokens, and no other; a second pass compiles
    nothing."""
    engine = _engine(toy)
    buckets = {engine._bucket(n) for n in range(512, 2049)}
    assert buckets == {512, 1024, 2048}
    compiles = []

    def listener(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        prompts = [_prompt(n, seed=n) for n in DOCBATCH]
        _, _, dispatches, _ = _serve(engine, prompts, new=2)
        assert {d["tokens"] for d in dispatches} == buckets
        assert sum(d["tokens"] for d in dispatches) == 10240
        assert engine._prefill_fn._cache_size() == len(buckets)
        assert engine._step_fn._cache_size() == 1
        first = len(compiles)
        assert first >= 4           # three prefill programs and the decode
        _serve(engine, [_prompt(n, seed=n + 1) for n in DOCBATCH], new=2)
        assert len(compiles) == first
        assert engine._prefill_fn._cache_size() == len(buckets)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    # one bucket each prefills 12,288 positions for the same 9,216 tokens
    one_bucket = _scheduler(MAX_SEQ, rows=0)
    assert sum(one_bucket.prefill_padded_len(n) for n in DOCBATCH) == 12288
