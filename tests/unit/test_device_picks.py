"""The serving programs pick the greedy token themselves and a step
fetches token ids: ``serve_prefill`` / ``serve_decode`` return ``picks``
beside their float32 logits, ``_sample(req, row)`` is handed a
``LogitsRow`` that stays on the device until something reads it, and each
dispatch of ``last_step`` counts ``picked`` and ``host_rows``.

One engine a family (dense, latent pools with device counters, window
layers with ring pages), at toy sizes in float32 on the CPU; every case
runs on all three.  "The parent's path" is the same ``_sample`` handed the
row as a host ``np.ndarray``, which is what it got before."""

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import serve_cell
from deepspeed_tpu.inference.serving import (LogitsRow, ServingEngine,
                                             greedy_token)
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.monitor.telemetry import get_telemetry
from deepspeed_tpu.runtime.resilience import FaultInjector
from unit import test_latent_serving, test_window_paged_serving

FAMILIES = {
    "dense": (lambda: TransformerConfig.tiny(hidden_size=64, n_heads=4,
                                             n_kv_heads=2),
              dict(max_batch=4, page_size=8, max_seq=64)),
    "latent": (test_latent_serving.config,
               dict(max_batch=4, page_size=8, max_seq=64)),
    "window": (test_window_paged_serving.config,
               dict(max_batch=4, page_size=8, max_seq=160, num_pages=81,
                    serving={"attention_backend": "jnp"})),
}
PROMPTS, NEW = (5, 11, 19, 7), 6


@pytest.fixture(scope="module", params=list(FAMILIES))
def served(request):
    """(config, seeded params, engine): one engine a family for the whole
    module, so its programs compile once; every test leaves it empty."""
    make, kwargs = FAMILIES[request.param]
    config = make()
    model = CausalTransformerLM(config)
    params = model.init(jax.random.key(3), jnp.float32)
    return config, params, ServingEngine(model, params, dtype=jnp.float32,
                                         **kwargs)


def _prompts(config, seed=0, avoid=()):
    rng = np.random.default_rng(seed)
    allowed = np.setdiff1d(np.arange(config.vocab_size), avoid)
    return [rng.choice(allowed, n).tolist() for n in PROMPTS]


def _serve(engine, prompts, sampling=None, new=NEW):
    """Serve ``prompts`` to the end: ({id: tokens}, the step reports of
    this run).  ``sampling``: keyword arguments of ``add_request`` by id."""
    mark = time.perf_counter_ns()
    for rid, prompt in enumerate(prompts):
        engine.add_request(rid, prompt, max_new_tokens=new,
                           **(sampling or {}).get(rid, {}))
    done = {}
    while engine.queue or engine.n_active:
        done.update(engine.step())
    assert engine.leak_report() == {}
    return done, [r for r in engine.step_reports() if r["t0_ns"] >= mark]


@contextlib.contextmanager
def _rows_on_the_host(engine):
    """The parent's path: while open, ``_sample`` is handed each row as the
    float32 ``np.ndarray`` it used to be handed."""
    original = engine._sample
    engine._sample = lambda req, row: original(req,
                                               np.array(row, np.float32))
    try:
        yield
    finally:
        engine._sample = original


def _counts(reports):
    """(picked, host_rows, emitted) summed over the reports, and the same
    a report, for the dispatches that carry the counters."""
    per = [(sum(d.get("picked", 0) for d in r["dispatches"]),
            sum(d.get("host_rows", 0) for d in r["dispatches"]),
            sum(n for _, n, _ in r["emitted"])) for r in reports]
    return tuple(map(sum, zip(*per))), per


def _with_head(config, params, change):
    """``params`` with ``change`` applied to the head's [d, V] table."""
    if config.tie_embeddings:
        return dict(params, tok_embed=change(params["tok_embed"].T).T)
    return dict(params, lm_head=change(params["lm_head"]))


NAN_TOKEN = 9
HEADS = {
    "seeded": lambda table: table,
    # every even column repeated in the next: each maximum is a tie, and
    # the pick is the first of the two
    "ties": lambda table: jnp.repeat(table[:, ::2], 2, axis=1),
    # one token's logit is NaN in every row: a NaN counts as the maximum
    "nan": lambda table: table.at[:, NAN_TOKEN].set(jnp.nan),
}


@pytest.mark.parametrize("head", list(HEADS))
def test_the_programs_pick_is_greedy_token_of_the_fetched_row(served, head):
    config, params, engine = served
    seen, original = [], engine._sample

    def sample(req, row):
        assert isinstance(row, LogitsRow)
        fetched = np.asarray(row)
        assert fetched.dtype == np.float32 and fetched.ndim == 1
        seen.append((row.pick, greedy_token(fetched),
                     int(np.argmax(fetched)), fetched))
        return original(req, row)

    engine._sample, engine.params = sample, _with_head(config, params,
                                                       HEADS[head])
    try:
        done, _ = _serve(engine, _prompts(config, 1, avoid=[NAN_TOKEN]))
    finally:
        engine._sample, engine.params = original, params
    assert len(seen) == len(PROMPTS) * NEW
    assert all(pick == greedy == plain for pick, greedy, plain, _ in seen)
    rows = np.stack([row for *_, row in seen])
    if head == "ties":
        assert (rows[:, ::2] == rows[:, 1::2]).all()
        assert all(pick % 2 == 0 for pick, *_ in seen)
    if head == "nan":
        # the first tokens are the NaN's; fed back, a tied table's NaN
        # embedding makes whole rows NaN, whose pick is the first place
        assert np.isnan(rows[:, NAN_TOKEN]).all()
        assert {pick for pick, *_ in seen} <= {NAN_TOKEN, 0}
        assert seen[0][0] == NAN_TOKEN
    assert [len(done[i]) for i in range(len(PROMPTS))] == \
        [n + NEW for n in PROMPTS]


def test_a_greedy_run_reads_no_row_and_yields_the_parents_ids(served):
    config, _, engine = served
    prompts = _prompts(config, 2)
    mark = time.perf_counter_ns()
    done, reports = _serve(engine, prompts)
    with _rows_on_the_host(engine):
        parents, theirs = _serve(engine, prompts)
    assert done == parents
    (picked, host_rows, emitted), per = _counts(reports)
    assert picked == emitted == len(PROMPTS) * NEW and host_rows == 0
    # a decode dispatch's tokens reach the host in the step after the one
    # that launched it (the engine runs ahead): the sums agree, not each
    # report's
    assert all(h == 0 for _, h, _ in per)
    for report in reports:
        for d in report["dispatches"]:
            assert ("picked" in d) == (d["phase"] in ("prefill", "decode"))
    # the parent's path is told apart: a row that came as an array is
    # counted by no dispatch
    assert _counts(theirs)[0][:2] == (0, 0)
    spans = [s for s in get_telemetry().spans(since_ns=mark,
                                              until_ns=reports[-1]["t1_ns"])
             if s.name == "serve/step"]
    assert all(s.attrs["host_rows"] == 0 for s in spans)
    assert sum(s.attrs["picked"] for s in spans) == picked


def test_the_harness_check_still_gets_every_row(served):
    """``chipbench/serve_cell.py:_logits_rows``, the accepted check's own
    wrapper: ``np.array(row, np.float32)``, then the original."""
    config, _, engine = served
    prompts = _prompts(config, 4)
    _serve(engine, prompts)             # every shape has compiled
    theirs, original = {}, engine._sample

    def programs(req, row):
        """Beneath the harness's wrapper: the same row of the array the
        program returned."""
        theirs.setdefault(req.req_id, []).append(
            np.asarray(row.block.device)[row.at])
        return original(req, row)

    engine._sample = programs
    mark = time.perf_counter_ns()
    try:
        with serve_cell._logits_rows(engine) as rows:
            done, reports = _serve(engine, prompts)
        assert engine._sample is programs
    finally:
        engine._sample = original
    assert get_telemetry().compile_log(since_ns=mark) == []
    for rid, prompt in enumerate(prompts):
        assert len(rows[rid]) == len(done[rid]) - len(prompt) == NEW
        assert all(r.dtype == np.float32 and r.base is None
                   for r in rows[rid])
        np.testing.assert_array_equal(np.stack(rows[rid]),
                                      np.stack(theirs[rid]))
        assert [int(np.argmax(r)) for r in rows[rid]] == \
            done[rid][len(prompt):]
    (picked, host_rows, emitted), per = _counts(reports)
    assert picked == host_rows == emitted == len(PROMPTS) * NEW
    assert all(p == h for p, h, _ in per)


SAMPLING = {1: dict(temperature=0.8, seed=11),
            2: dict(temperature=1.3, seed=12, top_k=5),
            3: dict(temperature=0.7, seed=13, top_p=0.6)}


def test_sampled_requests_read_their_rows_and_keep_their_tokens(served):
    """A batch of one greedy request and three sampled ones: each gets the
    tokens the parent's path gives it on the same seed, and only the
    sampled ones' rows are read."""
    config, _, engine = served
    prompts = _prompts(config, 5)
    done, reports = _serve(engine, prompts, SAMPLING)
    with _rows_on_the_host(engine):
        parents, _ = _serve(engine, prompts, SAMPLING)
    assert done == parents
    greedy, _ = _serve(engine, prompts)
    assert done[0] == greedy[0]
    assert any(done[rid] != greedy[rid] for rid in SAMPLING)
    (picked, host_rows, emitted), per = _counts(reports)
    assert picked == emitted == len(PROMPTS) * NEW
    assert host_rows == len(SAMPLING) * NEW
    assert all(h <= p for p, h, _ in per)


def test_a_sampler_fault_evicts_that_request_and_no_other(served):
    config, _, engine = served
    prompts = _prompts(config, 6)[:2]
    clean, _ = _serve(engine, prompts)
    # serve_sample calls 0, 1 are the two prefills, then one a live slot
    # a step in slot order: 4 is request 0 at its second decode step
    engine.injector = FaultInjector({"serve_sample": {"fail_at": [4],
                                                      "msg": "boom"}})
    try:
        done, reports = _serve(engine, prompts)
    finally:
        engine.injector = None
    (result,) = engine.pop_terminated().values()
    assert result.req_id == 0 and result.status == "evicted"
    assert result.n_generated == 2
    assert result.tokens == clean[0][:len(prompts[0]) + 2]
    assert done == {1: clean[1]}
    (picked, host_rows, emitted), _ = _counts(reports)
    assert picked == emitted == 2 + NEW and host_rows == 0
