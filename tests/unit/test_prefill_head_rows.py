"""A prefill takes the head on the row it samples from (PR 33).

``apply_with_paged_cache(..., head_rows=[B, R])`` runs the final norm and
the head on those rows alone; the serving engine asks a prefill for its
prompt's last row, an intermediate chunk of the chunked policy for none,
and a decode step or verify window for every row, as before.  Held here:
the row is the row of the all-rows call on the same inputs (bit-equal on
the CPU, where a one-row product is the same dot products in the same
order; on the chip it may be tiled otherwise than a row of a 4,096-row
one, a bf16 rounding: PERF.md §6, PR 33), the first token of every kind of
prefill is the one the whole model gives, and each dispatch of
``engine.last_step`` says how many rows its head computed.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.serving import ServingEngine, greedy_token
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.monitor.telemetry import get_telemetry
from tests.unit.test_latent_serving import config as toy_glm

PAGE = 8


@pytest.fixture(scope="module")
def tiny():
    model = CausalTransformerLM(TransformerConfig.tiny(
        hidden_size=64, n_heads=4, n_kv_heads=2))
    return model, model.init(jax.random.key(0))


@pytest.fixture(scope="module")
def latent():
    model = CausalTransformerLM(toy_glm())
    return model, model.init(jax.random.key(7), jnp.float32)


def _prompt(model, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, model.config.vocab_size, (n,)).tolist()


def _first_token(model, params, prompt):
    """What the whole model gives after ``prompt``, greedy."""
    logits = model.apply(params, jnp.asarray(prompt)[None, :], train=False)
    return int(np.argmax(np.asarray(logits[0, -1])))


# ----------------------------------------------------------------------
# the model's call: rows of the all-rows result
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kind,backend,interpret", [
    ("mix_paged", "jnp", False), ("mix_paged", "pallas", True),
    ("mix_latent", None, False)])
def test_head_rows_are_rows_of_the_all_rows_call(tiny, latent, kind, backend,
                                                 interpret):
    model, params = latent if kind == "mix_latent" else tiny
    B, T = 2, 16
    ids = jax.random.randint(jax.random.key(1), (B, T), 0,
                             model.config.vocab_size)
    tables = jnp.arange(1, 1 + B * T // PAGE, dtype=jnp.int32).reshape(B, -1)
    starts = jnp.zeros(B, jnp.int32)
    real = {"real_lengths": jnp.asarray([4, 13], jnp.int32)} \
        if model.config.counts_serving else {}
    call = jax.jit(lambda rows: model.apply_with_paged_cache(
        params, ids, model.init_paged_caches(1 + B * T // PAGE, PAGE,
                                             jnp.float32),
        tables, starts, attn_backend=backend, attn_interpret=interpret,
        head_rows=rows, **real))
    every = call(None)
    assert every[0].shape == (B, T, model.config.vocab_size)
    for rows in ([[3], [12]], [[0, 15], [15, 7]], [[], []]):
        rows = np.asarray(rows, np.int32).reshape(B, -1)
        some = call(jnp.asarray(rows))
        assert some[0].dtype == jnp.float32
        assert some[0].shape == (B, rows.shape[1], model.config.vocab_size)
        np.testing.assert_array_equal(
            some[0], np.take_along_axis(np.asarray(every[0]),
                                        rows[:, :, None], axis=1))
        # the pools, the lengths and a counted model's counters are the
        # dispatch's whatever rows the head ran on
        for got, want in zip(jax.tree_util.tree_leaves(some[1:]),
                             jax.tree_util.tree_leaves(every[1:])):
            np.testing.assert_array_equal(got, want)


# ----------------------------------------------------------------------
# the engine's prefills: the row handed to the sampler
# ----------------------------------------------------------------------
def _sampled_rows(eng):
    """Record the logits row of every ``_sample`` call, by request."""
    rows, sample = {}, eng._sample

    def recording(req, row):
        rows.setdefault(req.req_id, []).append(np.array(row))
        return sample(req, row)

    eng._sample = recording
    return rows


def _all_rows_last(model, params, prompt, width, table_width, backend,
                   interpret):
    """The prompt prefilled ``width`` tokens a dispatch (padded, as the
    engine pads; a block table as wide as the engine's, since the ragged
    kernel picks its tiles by it) through the all-rows call: the last real
    row of the last dispatch."""
    pages = -(-len(prompt) // width) * width // PAGE
    caches = model.init_paged_caches(pages + 1, PAGE, jnp.float32)
    tables = jnp.zeros((1, table_width), jnp.int32).at[0, :pages].set(
        jnp.arange(1, pages + 1))
    call = jax.jit(model.apply_with_paged_cache,
                   static_argnames=("attn_backend", "attn_interpret"))
    for start in range(0, len(prompt), width):
        toks = prompt[start:start + width]
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(toks)] = toks
        real = {"real_lengths": jnp.asarray([len(toks)], jnp.int32)} \
            if model.config.counts_serving else {}
        logits, caches, *_ = call(
            params, jnp.asarray(ids), caches, tables,
            jnp.full((1,), start, jnp.int32), attn_backend=backend,
            attn_interpret=interpret, **real)
    return np.asarray(logits[0, len(toks) - 1])


@pytest.mark.parametrize("kind,serving,width", [
    ("mix_paged", {"attention_backend": "jnp"}, 32),
    ("mix_paged", {"attention_backend": "pallas-interpret"}, 32),
    ("mix_latent", {}, 32),
    ("last_chunk", {"attention_backend": "jnp",
                    "scheduler": {"policy": "chunked",
                                  "prefill_chunk_tokens": 8}}, 8),
], ids=["mix_paged", "mix_paged_pallas_interpret", "mix_latent",
        "chunked_last_chunk"])
def test_prefill_samples_from_the_row_of_the_all_rows_call(
        tiny, latent, kind, serving, width):
    model, params = latent if kind == "mix_latent" else tiny
    eng = ServingEngine(model, params, max_batch=2, page_size=PAGE,
                        max_seq=64, dtype=jnp.float32, serving=serving)
    rows = _sampled_rows(eng)
    prompt = _prompt(model, 21)
    eng.add_request("r", prompt, max_new_tokens=2)
    while eng.queue or eng.n_active:
        eng.step()
    assert width == (8 if kind == "last_chunk" else eng._bucket(21))
    backend = "jnp" if kind == "mix_latent" else eng.attention_impl
    want = _all_rows_last(
        model, params, prompt, width, eng.tables.shape[1], backend,
        "interpret" in serving.get("attention_backend", ""))
    assert rows["r"][0].shape == (model.config.vocab_size,)
    np.testing.assert_array_equal(rows["r"][0], want)
    assert eng.leak_report() == {}


def test_prefix_cached_suffix_prefill_samples_the_same_first_token(tiny):
    model, params = tiny
    eng = ServingEngine(model, params, max_batch=2, page_size=PAGE,
                        max_seq=64, dtype=jnp.float32,
                        serving={"prefix_cache": {"enabled": True}})
    shared = _prompt(model, 20, seed=1)
    firsts = {}
    for name, tail in (("a", 5), ("b", 9)):
        prompt = shared + _prompt(model, tail, seed=tail)
        eng.add_request(name, prompt, max_new_tokens=1)
        done = {}
        while name not in done:
            done.update(eng.step())
        firsts[name] = (done[name][len(prompt)],
                        _first_token(model, params, prompt))
    assert eng.stats["prefix_hits"] == 1
    prefill = [d for r in eng.step_reports() for d in r["dispatches"]
               if d["phase"] == "prefill"]
    # the second prompt prefilled only what the cache did not hold (the
    # 20 shared tokens: two pages attached, half a page copied), and its
    # head ran on the last row of THAT suffix
    assert [(d["real"], d["context"], d["head_rows"]) for d in prefill] == \
        [(25, 25, 1), (9, 29, 1)]
    assert all(got == want for got, want in firsts.values()), firsts


def test_prefill_only_handoff_carries_the_same_first_token(tiny):
    model, params = tiny
    eng = ServingEngine(model, params, max_batch=2, page_size=PAGE,
                        max_seq=64, dtype=jnp.float32)
    prompt = _prompt(model, 19, seed=3)
    eng.add_request("r", prompt, max_new_tokens=4, prefill_only=True)
    while not eng.handoffs:
        eng.step()
    handoff = eng.pop_prefilled()["r"]
    assert handoff.last_token == _first_token(model, params, prompt)
    eng.release_handoff("r")
    assert eng.leak_report() == {}


# ----------------------------------------------------------------------
# the counter: rows the head computed, a dispatch
# ----------------------------------------------------------------------
SPEC = {"policy": "chunked", "prefill_chunk_tokens": 8,
        "speculative": {"enabled": True, "num_draft_tokens": 3}}


@pytest.mark.parametrize("name,kwargs,want", [
    ("monolithic", {}, {"prefill": {1}, "decode": {1}}),
    ("decode_chunk", {"decode_chunk": 2},
     {"prefill": {1}, "decode_chunk": {2}}),
    ("chunked", {"serving": {"scheduler": {"policy": "chunked",
                                           "prefill_chunk_tokens": 8}}},
     {"prefill": {0, 1}, "decode": {1}}),
    ("speculative", {"serving": {"scheduler": SPEC}, "draft": True},
     {"prefill": {0, 1}, "spec_prefill": {0}, "spec_draft": {4},
      "spec_verify": {4}}),
], ids=lambda v: v if isinstance(v, str) else "")
def test_head_rows_of_every_dispatch(tiny, name, kwargs, want):
    """1 for a prefill that is sampled from, 0 for a chunk that is not
    (the prompt's 21 tokens are chunks of 8, 8 and 5: two without a head,
    then one with) and for the draft's prompt chunks, ``tokens`` wherever
    every row was asked for (a decode step, a ``decode_chunk`` scan, the
    verify window of 1 + 3 tokens, the draft's 3 + 1 proposals)."""
    model, params = tiny
    kwargs = dict(kwargs)
    if kwargs.pop("draft", False):
        kwargs.update(draft_model=model, draft_params=params)
    eng = ServingEngine(model, params, max_batch=2, page_size=PAGE,
                        max_seq=64, dtype=jnp.float32, **kwargs)
    mark = time.perf_counter_ns()
    eng.add_request("r", _prompt(model, 21), max_new_tokens=6)
    while eng.queue or eng.n_active:
        eng.step()
    dispatches = [d for r in eng.step_reports() for d in r["dispatches"]]
    seen = {}
    for d in dispatches:
        seen.setdefault(d["phase"], set()).add(d["head_rows"])
        if d["head_rows"] not in (0, 1) or d["phase"] == "decode":
            assert d["head_rows"] == d["tokens"]
    assert seen == want
    if name in ("chunked", "speculative"):
        assert [d["head_rows"] for d in dispatches
                if d["phase"] == "prefill"] == [0, 0, 1]
    # the dispatch's span carries the same number
    spans = [s.attrs["head_rows"] for s in get_telemetry().spans(
        since_ns=mark) if s.name == "serve/step"]
    assert spans == [d["head_rows"] for d in dispatches]
    assert eng.leak_report() == {}


# ----------------------------------------------------------------------
# the greedy pick: np.argmax's index by a max pass and two short argmaxes
# ----------------------------------------------------------------------
@pytest.mark.parametrize("size", [1, 7, 128, 1024, 1025, 19360, 100352])
def test_greedy_token_is_numpy_argmax(size):
    rng = np.random.default_rng(size)
    row = rng.standard_normal(size).astype(np.float32)
    assert greedy_token(row) == int(np.argmax(row))
    # ties: the first of equal maxima, in one block and across blocks
    for places in ((size // 3, size - 1), (0, size // 2), (size - 1,)):
        tied = row.copy()
        tied[list(places)] = 9.0
        assert greedy_token(tied) == int(np.argmax(tied)) == min(places)
    # a row that is not a number anywhere answers as numpy does
    broken = row.copy()
    broken[[size // 2, size - 1]] = np.nan
    assert greedy_token(broken) == int(np.argmax(broken)) == size // 2
    assert greedy_token(np.full(size, -np.inf, np.float32)) == 0
    # a view with a stride (a column of a batch) as well
    batch = rng.standard_normal((size, 3)).astype(np.float32)
    assert greedy_token(batch[:, 1]) == int(np.argmax(batch[:, 1]))
