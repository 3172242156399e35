"""Latent attention WITHOUT a selection (DeepSeek-V3 / Kimi-K2) on the
serving path: toy sizes of Kimi-K2's shape (``tests/chipbench/data/
tiny-kimi.json`` through ``chipbench/families/kimi_k2.py``: a dense first
layer, MLA with unlike nope / rope / v widths, YaRN, sigmoid routing with a
bias, 3 of 12 experts held), in float32 on the CPU, against the plain
reference ``chipbench/reference/kimi_k2.py``.  What the two latent
families share is parametrised over both (``FAMILIES``)."""

import dataclasses
import functools
import json
import math
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import glm_moe_dsa as glm_family
from chipbench.families import kimi_k2 as kimi_family
from chipbench.reference import glm_moe_dsa as glm_reference
from chipbench.reference import kimi_k2 as kimi_reference
from deepspeed_tpu.inference import serving
from deepspeed_tpu.inference.robustness import ServingUnsupported
from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              ServeCounts,
                                              TransformerConfig)
from deepspeed_tpu.monitor import telemetry
from deepspeed_tpu.ops import latent_attention as la

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "chipbench", "data")
# family -> (its toy configuration, published keys -> the program's model,
# its reference)
FAMILIES = {"kimi_k2": ("tiny-kimi", kimi_family, kimi_reference),
            "glm_moe_dsa": ("tiny-glm", glm_family, glm_reference)}
# float32 on both sides: the program's blocked running softmax and absorbed
# decode against the reference's plain softmax differ by summation order,
# 2e-6 of the largest logit here; bfloat16 in either place reads 1e-2
TOL = 2e-5


def toy(family, **changed):
    name, module, reference = FAMILIES[family]
    with open(os.path.join(DATA, name + ".json")) as f:
        cfg = dict(json.load(f), **changed)
    model = CausalTransformerLM(TransformerConfig(
        **module.transformer_kwargs(cfg), remat=False))
    return cfg, model, reference


@pytest.fixture(scope="module")
def kimi():
    cfg, model, reference = toy("kimi_k2")
    return cfg, model, model.init(jax.random.key(11), jnp.float32)


def _engine(model, params, chunk=None, **kwargs):
    if chunk:
        kwargs["serving"] = {"scheduler": {
            "policy": "chunked", "prefill_chunk_tokens": chunk,
            "max_prefill_chunks_per_step": 1}}
    return ServingEngine(model, params, max_batch=4, page_size=8,
                         max_seq=160, dtype=jnp.float32, **kwargs)


def _served_rows(engine, prompts, new=5):
    """Every logits row the engine sampled from, by request, and the
    tokens it gave."""
    rows, original = {}, engine._sample

    def sample(req, row):
        rows.setdefault(req.req_id, []).append(np.array(row, np.float32))
        return original(req, row)

    engine._sample = sample
    for rid, prompt in prompts.items():
        engine.add_request(rid, prompt, max_new_tokens=new)
    done = {}
    while engine.queue or engine.n_active:
        done.update(engine.step())
    engine._sample = original
    return {rid: np.stack(r) for rid, r in rows.items()}, done


PROMPTS = (70, 16, 33, 5, 121)      # one to eight chunks of 16, most padded


@pytest.fixture(scope="module")
def chunked(kimi):
    _, model, params = kimi
    rng = np.random.default_rng(2)
    prompts = {i: rng.integers(0, 512, n).astype(np.int32)
               for i, n in enumerate(PROMPTS)}
    mark = time.perf_counter_ns()
    engine = _engine(model, params, chunk=16)
    rows, done = _served_rows(engine, prompts)
    # the span ring is the process's: this engine's lie between the marks
    return engine, prompts, rows, done, (mark, time.perf_counter_ns())


def test_chunked_prefill_and_decode_through_the_pool_agree_with_the_reference(
        kimi, chunked):
    cfg, _, params = kimi
    engine, prompts, rows, done, _ = chunked
    assert engine.leak_report() == {}
    for rid, served in rows.items():
        ids = jnp.asarray(done[rid], jnp.int32)[None, :-1]
        want, decided = kimi_reference.logits(params, ids, cfg,
                                              last=len(served))
        assert decided.shape == (1, len(served))
        scale = float(jnp.max(jnp.abs(want)))
        error = np.max(np.abs(served - np.asarray(want)[0]), axis=-1) / scale
        # float32 on both sides: a routing flip needs a margin of 1e-6
        assert error.max() < TOL, (rid, error)


def test_a_chunked_prefill_is_the_monolithic_one(kimi, chunked):
    _, model, params = kimi
    _, prompts, rows, done, _ = chunked
    whole_rows, whole_done = _served_rows(_engine(model, params), prompts)
    assert whole_done == done
    for rid in prompts:
        np.testing.assert_allclose(rows[rid], whole_rows[rid], atol=TOL,
                                   rtol=0)


def test_a_chunk_from_an_empty_pool_is_the_fresh_prefill(kimi):
    """``lengths`` 0: no cached entry is walked and the chunk is what the
    whole-sequence mixer computes; from ``lengths`` > 0 the same tokens
    give the rows of a longer prompt."""
    _, model, params = kimi
    ids = jax.random.randint(jax.random.key(4), (1, 40), 0, 512)
    whole = jax.jit(functools.partial(model.apply, train=False))(params, ids)
    caches = model.init_paged_caches(7, 8, jnp.float32)
    tables = jnp.arange(1, 7, dtype=jnp.int32)[None]
    call = jax.jit(model.apply_with_paged_cache)
    first, caches, lengths, counts = call(params, ids[:, :24], caches, tables,
                                          jnp.zeros(1, jnp.int32))
    np.testing.assert_allclose(first, whole[:, :24], atol=2e-5)
    second, caches, lengths, counts = call(params, ids[:, 24:], caches,
                                           tables, lengths)
    np.testing.assert_allclose(second, whole[:, 24:], atol=2e-5)
    assert int(lengths[0]) == 40
    # causal keys of 16 queries at contexts 25..40, five layers; a dense
    # model attends to all of them
    assert int(counts[1]) == int(counts[0]) == 5 * sum(range(25, 41))


@pytest.mark.parametrize("family,experts,held", [("kimi_k2", 12, 3),
                                                 ("glm_moe_dsa", 32, 8)])
def test_the_shares_add_up_to_the_uncut_layer(family, experts, held):
    """The shares of ``held`` of the family's experts, the shared expert
    counted once, are the uncut layer, and the uncut layer is the
    reference's."""
    cfg, _, reference = toy(family)
    assert cfg["published"]["n_routed_experts"] == experts
    _, whole, _ = toy(family, n_routed_experts=experts)
    layer = whole.init(jax.random.key(7), jnp.float32)["layers"][2]
    h = jax.random.normal(jax.random.key(5), (1, 37, 64))
    uncut, _ = whole._mlp_delta(h, layer, train=False)
    counts, total = ServeCounts(jnp.ones((1, 37), bool)), 0.0
    _, share_model, _ = toy(family)
    for first in range(0, experts, held):
        share = CausalTransformerLM(dataclasses.replace(
            share_model.config, moe_experts_first=first))
        moe = dict(layer["moe"], **{
            k: layer["moe"][k][first:first + held]
            for k in ("w_gate", "w_up", "w_down")})
        if first:           # the shared expert on one chip alone
            moe.pop("shared")
        part, _ = share._mlp_delta(h, dict(layer, moe=moe), train=False,
                                   counts=counts)
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=1e-5)
    k = cfg["num_experts_per_tok"]
    assert int(counts.counts["expert_pairs"]) == 37 * k   # every pair, once
    sizes = getattr(reference, "Sizes", None) or reference._Sizes
    layer_fn = getattr(reference, "expert_layer", None) \
        or reference._expert_layer
    with jax.default_matmul_precision("highest"):
        want, _ = layer_fn(h[0], layer["moe"],
                           sizes(dict(cfg, n_routed_experts=experts)), 2)
    np.testing.assert_allclose(uncut[0], want, atol=1e-5)


PUBLISHED = {"factor": 32, "original_max_position_embeddings": 4096,
             "beta_fast": 1, "beta_slow": 1, "mscale": 1,
             "mscale_all_dim": 1, "type": "yarn"}


def test_yarn_by_hand_for_the_published_keys():
    """Kimi-K2's ``rope_scaling`` over its 64 rotary dimensions: d(1) =
    64 ln(4096 / 2 pi) / (2 ln 50000) = 19.165, so the ramp runs from 19
    to 20: frequencies 0..19 are kept, 20..31 divided by 32; the scale is
    192**-0.5 (0.1 ln 32 + 1)**2 = 0.13086."""
    yarn = la.RopeYarn(*kimi_family._yarn(PUBLISHED))
    inv = np.asarray(yarn.inv_freq(64, 50000.0))
    plain = 50000.0 ** (-np.arange(32) / 32.0)
    assert 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(50000)) == \
        pytest.approx(19.165, abs=1e-3)
    np.testing.assert_allclose(inv[:20], plain[:20], rtol=1e-12)
    np.testing.assert_allclose(inv[20:], plain[20:] / 32, rtol=1e-12)
    assert inv[19] == pytest.approx(50000.0 ** (-19 / 32))      # kept
    assert inv[20] == pytest.approx(50000.0 ** (-20 / 32) / 32)
    assert yarn.rotary_magnitude == 1.0
    assert yarn.magnitude(1.0) == pytest.approx(1.34657, abs=1e-5)
    cfg, _, _ = toy("kimi_k2", rope_scaling=PUBLISHED, qk_nope_head_dim=128,
                    qk_rope_head_dim=64)
    model = CausalTransformerLM(TransformerConfig(
        **kimi_family.transformer_kwargs(cfg)))
    assert model._latent_scale() == pytest.approx(0.13086, abs=1e-5)
    # the reference reckons the same numbers on its own
    np.testing.assert_allclose(
        kimi_reference.yarn_inv_freq(64, 50000.0, PUBLISHED), inv,
        rtol=1e-12)
    assert kimi_reference.softmax_scale(cfg) == pytest.approx(
        model._latent_scale(), rel=1e-12)
    # a ramp with two ends: beta_fast 32 starts it at d(32) = 64 ln(4096 /
    # 64 pi) / (2 ln 50000) = 8.91, floor 8, and it ends at 20
    wide = la.RopeYarn(32.0, 4096, 32.0, 1.0, 1.0, 1.0).inv_freq(64, 50000.0)
    ramp = 1 - (np.asarray(wide) / plain - 1 / 32) / (1 - 1 / 32)
    np.testing.assert_allclose(ramp, np.clip((np.arange(32) - 8) / 12, 0, 1),
                               atol=1e-12)
    # without a stretch nothing changes
    assert la.RopeYarn(1.0, 4096, 32.0, 1.0, 1.0, 1.0).magnitude(1.0) == 1.0


def test_yarn_turns_the_rotary_slice_as_the_reference_does(kimi):
    """The toy's own YaRN (factor 8 over 32 positions, so the ramp lies
    inside its 4 frequencies) reaches the logits: without it the program
    is another model."""
    cfg, model, params = kimi
    ids = jax.random.randint(jax.random.key(9), (1, 48), 0, 512)
    got = jax.jit(functools.partial(model.apply, train=False))(params, ids)
    want, _ = kimi_reference.logits(params, ids, cfg)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) / scale < TOL
    plain = CausalTransformerLM(dataclasses.replace(model.config,
                                                    rope_yarn=None))
    other = jax.jit(functools.partial(plain.apply, train=False))(params, ids)
    assert float(jnp.max(jnp.abs(other - want))) / scale > 100 * TOL


def test_a_model_without_a_selection_has_no_index_pool_and_sorts_nothing():
    """No bytes for index keys, and the decode program of the attention
    (a model with no expert layer, whose router would sort) holds no
    ``top_k`` and no sort; the selecting family's holds both."""
    def lowered(family, **changed):
        cfg, model, _ = toy(family, **changed)
        params = jax.eval_shape(
            lambda: model.init(jax.random.key(0), jnp.float32))
        caches = jax.eval_shape(
            lambda: model.init_paged_caches(9, 8, jnp.float32))
        ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731
        return caches, jax.jit(model.apply_with_paged_cache).lower(
            params, ints(4, 1), caches, ints(4, 8), ints(4)).as_text()

    dense = dict(first_k_dense_replace=5)           # every layer dense
    caches, text = lowered("kimi_k2", **dense)
    assert caches.index_pages.shape == (5, 9, 8, 0)
    assert sum(leaf.size for leaf in caches) == caches.latent_pages.size
    sorts = re.compile(r"\b(chlo\.top_k|stablehlo\.sort|mhlo\.topk)\b")
    assert not sorts.search(text)
    assert "stablehlo.while" in text    # the entries: a block a step
    caches, text = lowered("glm_moe_dsa", **dense)
    assert caches.index_pages.shape[-1] == 16
    assert sorts.search(text)


@pytest.mark.parametrize("family,kwargs,feature", [
    ("kimi_k2", {"serving": {"prefix_cache": {"enabled": True}}},
     "prefix_cache"),
    ("glm_moe_dsa", {"serving": {"prefix_cache": {"enabled": True}}},
     "prefix_cache"),
    ("glm_moe_dsa", {"serving": {"scheduler": {"policy": "chunked"}}},
     "index_topk"),
    ("kimi_k2", {"serving": {"scheduler": {"policy": "chunked",
                                           "speculative": {
                                               "enabled": True,
                                               "num_draft_tokens": 2}}}},
     "speculative"),
    ("kimi_k2", {"tp_size": 2}, "tp_size"),
    ("kimi_k2", {"ep_size": 2}, "ep_size"),
])
def test_the_refusals_that_stay_are_by_name(family, kwargs, feature):
    _, model, _ = toy(family)
    params = model.init(jax.random.key(0), jnp.float32)
    with pytest.raises(ServingUnsupported) as refused:
        _engine(model, params, **kwargs)
    assert feature in refused.value.feature
    assert "\n" not in str(refused.value)


def test_the_selecting_family_is_told_what_is_missing_for_chunks():
    _, model, _ = toy("glm_moe_dsa")
    params = model.init(jax.random.key(0), jnp.float32)
    with pytest.raises(ServingUnsupported,
                       match="selection over cached index keys"):
        _engine(model, params, chunk=16)


def test_a_chunk_reports_what_it_read_of_the_pool(chunked):
    """``ctx_entries`` and ``chunk`` on every prefill dispatch and its
    ``serve/step`` span, ``chunk`` and ``context`` on the ``serve/prefill``
    span; every counter over the real rows, and no ``selected``."""
    engine, prompts, _, _, mark = chunked
    dispatches = [d for r in engine.step_reports() for d in r["dispatches"]]
    prefills = [d for d in dispatches if d["phase"] == "prefill"]
    assert len(prefills) == sum(-(-n // 16) for n in PROMPTS)
    block = la.context_entries(1, engine.page_size)     # a block of keys
    for d in prefills:
        cached = d["context"] - d["real"]
        assert d["chunk"] == cached // 16 and d["tokens"] == 16
        assert d["ctx_entries"] == -(-cached // block) * block >= cached
        assert d["head_rows"] in (0, 1)
        assert d["context_keys"] == 5 * sum(range(cached + 1,
                                                  d["context"] + 1))
        assert 0 < d["expert_pairs"] <= 4 * 4 * d["real"]
    # the head on a prompt's last chunk alone
    assert sorted(d["context"] for d in prefills if d["head_rows"]) == \
        sorted(PROMPTS)
    for d in dispatches:
        assert "selected" not in d and d["experts"] == "jnp"
        if d["phase"] == "decode":
            assert d["context_keys"] == 5 * sum(d["contexts"])
            assert "ctx_entries" not in d and "chunk" not in d
    spans = engine.telemetry.spans(*mark)
    steps = [s for s in spans if s.name == "serve/step"
             and s.attrs["phase"] == "prefill"]
    assert steps and all(set(serving.CHUNK_COUNTS) <= set(s.attrs)
                         and "selected" not in s.attrs for s in steps)
    chunks = [s for s in spans if s.name == "serve/prefill"]
    assert sorted((s.attrs["chunk"], s.attrs["context"]) for s in chunks
                  if s.key == 0) == [(0, 16), (1, 32), (2, 48), (3, 64),
                                     (4, 70)]


def test_the_chunk_index_is_the_schedulers_whatever_the_model(kimi):
    """``chunk`` rides every prefill dispatch of the chunked policy, of a
    model with plain attention too, and none of the monolithic one;
    ``ctx_entries`` is the dense latent model's under either."""
    from deepspeed_tpu.models.transformer import TransformerConfig as TC
    plain = CausalTransformerLM(TC.tiny(hidden_size=64, n_heads=4,
                                        n_kv_heads=2))
    engine = _engine(plain, plain.init(jax.random.key(0)), chunk=16)
    engine.generate([list(range(1, 41))], max_new_tokens=2)
    prefills = [d for r in engine.step_reports() for d in r["dispatches"]
                if d["phase"] == "prefill"]
    assert [d["chunk"] for d in prefills] == [0, 1, 2]
    assert all("ctx_entries" not in d for d in prefills)
    _, model, params = kimi
    engine = _engine(model, params)             # monolithic
    engine.generate([list(range(1, 41))], max_new_tokens=2)
    whole, = [d for r in engine.step_reports() for d in r["dispatches"]
              if d["phase"] == "prefill"]
    assert "chunk" not in whole and whole["ctx_entries"] == 0


def test_a_request_between_chunks_is_no_leak(kimi):
    """Admission reserves the padded prefill (whole chunks) and the last
    chunk trims it: a request that still has chunks to go holds more pages
    than prompt + budget need, which the audit has to know (the cell's
    window ends with requests in flight: four of six chip runs read
    ``over_reserved_slots`` and not ``correct`` before it did) and to hold
    the request to: the padded prefill, not a page more."""
    _, model, params = kimi
    engine = _engine(model, params, chunk=16)
    engine.add_request("r", list(range(1, 36)), max_new_tokens=2)
    engine.step()                               # the first of three chunks
    req = engine.slots[0]
    assert 0 < req.prefilled < len(req.prompt)
    held = len(engine.alloc.seq_pages["r"])
    assert held == 6 > -(-(35 + 2) // 8)        # 48 padded rows of 8
    assert engine.leak_report() == {}
    # ... and holds it to exactly that: a page more between chunks is a
    # leak, and is reported
    engine.alloc.extend("r", 48 + 1)
    assert engine.leak_report()["over_reserved_slots"] == {
        "r": {"held": 7, "expected": 6}}
    engine.alloc.shrink("r", 48)
    assert engine.leak_report() == {}
    while engine.n_active:
        engine.step()
        if engine.slots[0] is not None and \
                engine.slots[0].prefilled == 35:
            assert len(engine.alloc.seq_pages["r"]) == 5
            assert engine.leak_report() == {}   # trimmed with the last
    assert engine.leak_report() == {}


def test_the_chunk_program_names_what_reads_the_pool(kimi):
    # its own engine: a site's table is its newest LIVE engine's
    _, model, params = kimi
    engine = _engine(model, params, chunk=16)
    engine.generate([list(range(40))], max_new_tokens=2)
    with_head = telemetry.op_scopes("serve/prefill_fn",
                                    arg_shapes={1: (1, 16)})
    assert {"latent_ctx", "latent_attn", "router", "experts",
            "shared_expert"} <= set(with_head.values())
    assert "select" not in with_head.values()
    # a chunk shape compiles two programs, with the head and without: the
    # benchmark's launch-order reader asks for each by the head's rows
    from chipbench.reducers import scope_pct_in_order
    tables = [scope_pct_in_order.table_of(("prefill", 1, 16, head))
              for head in (0, 1)]
    assert tables[0] is not tables[1] and tables[0] != tables[1]
    assert all("latent_ctx" in t.values() for t in tables)
    assert "loss_head" in tables[1].values()
    assert "loss_head" not in tables[0].values()
    assert scope_pct_in_order.table_of(("decode", 4, 1, 1))
    decode = set(telemetry.op_scopes("serve/step_fn").values())
    assert "latent_attn" in decode and "latent_ctx" not in decode
    assert telemetry.phase_of(
        "jit(f)/attn/latent_attn/latent_ctx/while/body/dot_general") == \
        "latent_ctx"
    assert engine.attention_impl == "jnp"


def test_the_chunk_counters_match_the_checker():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "scripts", "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("checker", path)
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    assert tuple(checker.CHUNK_COUNTS) == tuple(serving.CHUNK_COUNTS)
    assert "latent_ctx" in checker.SERVE_SCOPES
