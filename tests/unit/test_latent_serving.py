"""Latent attention with a learned key selection and a dropless share of
the experts, on the serving path: toy sizes of GLM-5's shape (a dense
first layer, MLA with unlike nope / rope / v widths, an indexer whose
top-k is under the toy contexts, sigmoid routing with a bias), in float32
on the CPU, against the plain reference of ``chipbench/reference/`` and
against brute force."""

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import glm_moe_dsa as reference
from deepspeed_tpu.inference.robustness import ServingUnsupported
from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import (SERVE_COUNTERS,
                                              CausalTransformerLM,
                                              ServeCounts,
                                              TransformerConfig)
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.monitor import telemetry
from deepspeed_tpu.ops import latent_attention as la

# the configuration file's keys, as the reference reads them
CFG = {"hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
       "intermediate_size": 96, "max_position_embeddings": 256,
       "rope_parameters": {"rope_theta": 1000000}, "rms_norm_eps": 1e-5,
       "tie_word_embeddings": False, "q_lora_rank": 48, "kv_lora_rank": 32,
       "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "index_n_heads": 2, "index_head_dim": 16, "index_topk": 8,
       "first_k_dense_replace": 1, "n_routed_experts": 16,
       "num_experts_per_tok": 4, "norm_topk_prob": True,
       "routed_scaling_factor": 2.5, "moe_intermediate_size": 32,
       "n_shared_experts": 1, "vocab_size": 128}


def config(**changed):
    c = dict(CFG, **changed)
    return TransformerConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        ffn_hidden_size=c["intermediate_size"], max_seq_len=256,
        rope_theta=1e6, remat=False, q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        index_n_heads=c["index_n_heads"],
        index_head_dim=c["index_head_dim"], index_topk=c["index_topk"],
        first_dense_layers=c["first_k_dense_replace"],
        moe_num_experts=c.get("experts_published", c["n_routed_experts"]),
        moe_experts_held=c["n_routed_experts"],
        moe_top_k=c["num_experts_per_tok"], moe_dropless=True,
        moe_scoring=c.get("scoring_func", "sigmoid"),
        moe_routed_scale=2.5, moe_ffn_hidden_size=c["moe_intermediate_size"],
        moe_shared_experts=1)


@pytest.fixture(scope="module")
def model():
    return CausalTransformerLM(config())


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(7), jnp.float32)


def _ids(n, seed=1):
    return jax.random.randint(jax.random.key(seed), (1, n), 0,
                              CFG["vocab_size"])


def _serve(model, params, ids, prefill, page=8):
    """Prefill ``prefill`` tokens of ``ids`` through the two pools, then
    decode the rest one at a time: logits [1, S, V] and the counters."""
    S = ids.shape[1]
    pages = -(-S // page)
    caches = model.init_paged_caches(pages + 1, page, jnp.float32)
    tables = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    call = jax.jit(model.apply_with_paged_cache)
    out, caches, _, counters = call(params, ids[:, :prefill], caches, tables,
                                    jnp.zeros(1, jnp.int32))
    rows, counts = [out], [np.asarray(counters)]
    for t in range(prefill, S):
        out, caches, _, counters = call(params, ids[:, t:t + 1], caches,
                                        tables, jnp.full(1, t, jnp.int32))
        rows.append(out)
        counts.append(np.asarray(counters))
    return jnp.concatenate(rows, axis=1), counts


@pytest.fixture(scope="module")
def served(model, params):
    """33 tokens: 9 prefilled, 24 decoded through both pools."""
    ids = _ids(33, seed=3)
    return (ids,) + _serve(model, params, ids, prefill=9)


def test_program_agrees_with_the_reference_through_both_pools(params,
                                                              served):
    ids, served, _ = served
    want, decided = reference.logits(params, ids, CFG)
    assert want.shape == served.shape and decided.shape == (1, 33)
    scale = float(jnp.max(jnp.abs(want)))
    error = np.max(np.abs(np.asarray(served - want)), axis=-1)[0] / scale
    assert decided.mean() > 0.5
    assert error[decided[0]].max() < 1e-4
    # the last rows alone, as the harness asks for them
    tail, tail_decided = reference.logits(params, ids, CFG, last=11)
    np.testing.assert_allclose(tail, want[:, -11:], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(tail_decided, decided[:, -11:])


def test_absorbed_decode_equals_decompressed_prefill(model, params, served):
    ids, mostly_decode, counts = served
    whole = jax.jit(functools.partial(model.apply, train=False))(params, ids)
    one_prefill, _ = _serve(model, params, ids, prefill=33)
    np.testing.assert_allclose(one_prefill, whole, atol=2e-5)
    np.testing.assert_allclose(mostly_decode, whole, atol=2e-5)
    # the program's own count: min(top-k, context) keys a query and layer
    k, layers = CFG["index_topk"], CFG["num_hidden_layers"]
    stats = dict(zip(SERVE_COUNTERS, counts[0]))
    assert stats["selected"] == layers * sum(min(k, t + 1) for t in range(9))
    assert stats["context_keys"] == layers * 9 * 10 // 2
    stats = dict(zip(SERVE_COUNTERS, counts[-1]))
    assert (stats["selected"], stats["context_keys"]) == (layers * k,
                                                          layers * 33)


def _brute_force(scores, valid, k):
    out = np.zeros(scores.shape, bool)
    for r, (row, ok) in enumerate(zip(scores, valid)):
        order = sorted(np.flatnonzero(ok), key=lambda s: (-row[s], s))
        out[r, order[:k]] = True
    return out


@pytest.mark.parametrize("k", [1, 5, 16, 40])
def test_selection_is_the_brute_force_top_k_ties_to_the_lower_index(k):
    rng = np.random.default_rng(k)
    # few distinct values, so that ties sit on the boundary; negative ones
    # and zeros of both signs among them
    scores = rng.choice(np.asarray([-2.5, -0.0, 0.0, 1e-30, 0.5, 3.0],
                                   np.float32), size=(12, 33))
    scores[0] = rng.normal(size=33)
    valid = np.tril(np.ones((33, 33), bool))[rng.integers(0, 33, 12)]
    want = _brute_force(np.where(scores == 0, 0.0, scores), valid, k)
    got = la.topk_mask(jnp.asarray(scores), jnp.asarray(valid), k)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(reference.select(
        jnp.asarray(scores), jnp.asarray(valid), k)), want)
    idx, live = la.topk_indices(jnp.asarray(scores), jnp.asarray(valid), k)
    by_index = np.zeros_like(want)
    for r in range(12):
        by_index[r, np.asarray(idx)[r][np.asarray(live)[r]]] = True
    np.testing.assert_array_equal(by_index, want)


def _glu(h, moe, e):
    return (jax.nn.silu(h @ moe["w_gate"][e]) * (h @ moe["w_up"][e])) \
        @ moe["w_down"][e]


def test_no_token_is_dropped_when_every_token_goes_to_one_expert(params):
    moe = params["layers"][1]["moe"]
    h = jax.random.normal(jax.random.key(2), (50, 64))
    # a router rigged by its bias: experts 3, 0, 9, 1 for every token
    bias = jnp.zeros(16).at[jnp.asarray([3, 0, 9, 1])].set(
        jnp.asarray([8.0, 7.0, 6.0, 5.0]))
    chosen, weights = sharded_moe.dropless_route(
        h, moe["wg"], bias, 4, scale=2.5)
    assert np.all(np.asarray(chosen) == [3, 0, 9, 1])
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    out, load, rows = sharded_moe.dropless_held_experts(
        h, chosen, weights, moe, jax.nn.silu, tile=16)
    assert load.tolist() == [50, 50, 0, 50] + [0] * 5 + [50] + [0] * 6
    want = sum(weights[:, j:j + 1] * _glu(h, moe, e)
               for j, e in enumerate([3, 0, 9, 1]))
    np.testing.assert_allclose(out, want, atol=1e-5)


def test_a_step_past_its_experts_load_adds_nothing_to_any_token(params):
    """Tokens 0 and 1 choose experts 0 and 1, tokens 2 and 3 experts 1 and
    2: expert 0's tile of 4 rows holds its own two pairs and two rows of
    padding, which no token reads back, so each token still gets exactly
    its own terms; and nothing is scattered: every pair's term is written
    at its sorted place and gathered back by its token."""
    moe = params["layers"][1]["moe"]
    h = jax.random.normal(jax.random.key(3), (4, 64))
    chosen = jnp.asarray([[0, 1], [0, 1], [1, 2], [1, 2]], jnp.int32)
    weights = jax.random.uniform(jax.random.key(4), (4, 2), minval=0.5)
    out, load, rows = sharded_moe.dropless_held_experts(
        h, chosen, weights, moe, jax.nn.silu, tile=4)
    assert out.shape == h.shape and load.tolist()[:4] == [2, 4, 2, 0]
    assert int(rows) == 12      # a tile each, rows of padding included
    want = jnp.stack([sum(weights[t, j] * _glu(h[t], moe, int(chosen[t, j]))
                          for j in range(2)) for t in range(4)])
    np.testing.assert_allclose(out, want, atol=1e-5)
    for impl in ("jnp", "pallas"):
        text = str(jax.make_jaxpr(
            lambda: sharded_moe.dropless_held_experts(
                h, chosen, weights, moe, jax.nn.silu, tile=4, impl=impl,
                interpret=True))())
        assert "scatter" not in text and "gather" in text
        assert ("pallas_call" in text) == (impl == "pallas")


def test_dropless_is_not_the_scoring_a_softmax_router_serves_it_too():
    """``moe_dropless`` picks the layer, ``moe_scoring`` its router's
    scores: softmax over all experts, no selection bias, every token its
    top-k with no capacity."""
    soft = CausalTransformerLM(config(scoring_func="softmax"))
    weights = soft.init(jax.random.key(7), jnp.float32)
    moe = weights["layers"][1]["moe"]
    assert "router_bias" not in moe and soft.gate is None
    h = jax.random.normal(jax.random.key(2), (1, 23, 64))
    out, _ = soft._mlp_delta(h, weights["layers"][1], train=False)
    scores = jax.nn.softmax(h[0] @ moe["wg"], axis=-1)
    top, chosen = jax.lax.top_k(scores, 4)
    top = 2.5 * top / top.sum(-1, keepdims=True)
    want = sum(top[t, j] * _glu(h[0, t], moe, int(chosen[t, j]))
               for t in range(23) for j in range(4))
    shared = moe["shared"]
    want_shared = (jax.nn.silu(h[0] @ shared["w_gate"])
                   * (h[0] @ shared["w_up"])) @ shared["w_down"]
    np.testing.assert_allclose(out[0].sum(0), want + want_shared.sum(0),
                               atol=2e-4)
    with pytest.raises(ValueError, match="moe_scoring"):
        sharded_moe.dropless_route(h[0], moe["wg"], None, 4, scoring="tanh")


def test_the_shares_add_up_to_the_uncut_layer(params):
    """4 shares of 4 of 16 experts, the shared expert counted once, are
    the uncut layer; and the uncut layer is the reference's."""
    layer = params["layers"][2]
    h = jax.random.normal(jax.random.key(5), (1, 37, 64))
    whole = CausalTransformerLM(config())
    uncut, _ = whole._mlp_delta(h, layer, train=False)
    counts, total = ServeCounts(jnp.ones((1, 37), bool)), 0.0
    for first in range(0, 16, 4):
        held = {k: v[first:first + 4] for k, v in layer["moe"].items()
                if k in ("w_gate", "w_up", "w_down")}
        share = CausalTransformerLM(dataclasses.replace(
            config(experts_published=16, n_routed_experts=4),
            moe_experts_first=first))
        moe = dict(layer["moe"], **held)
        if first:           # the shared expert on one chip alone
            moe.pop("shared")
        part, _ = share._mlp_delta(h, dict(layer, moe=moe), train=False,
                                   counts=counts)
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=1e-5)
    assert int(counts.counts["expert_pairs"]) == 37 * 4   # every pair, once
    sizes = reference._Sizes(CFG)
    with jax.default_matmul_precision("highest"):
        want, _ = reference._expert_layer(h[0], layer["moe"], sizes, 2)
    np.testing.assert_allclose(uncut[0], want, atol=1e-5)


def _engine(model, params, **kwargs):
    return ServingEngine(model, params, max_batch=4, page_size=8,
                         max_seq=64, dtype=jnp.float32, **kwargs)


def test_engine_serves_counts_and_leaks_nothing_over_both_pools(model,
                                                                params):
    now = [0.0]
    engine = _engine(model, params, clock=lambda: now[0])
    # 40 values an entry in a row of whole lane tiles; 16 an index key
    assert [leaf.shape[1:] for leaf in engine.caches] == \
        [(33, 8, 128), (33, 8, 16)]
    assert engine.attention_impl == "jnp"
    rng = np.random.default_rng(0)
    prompts = {i: rng.integers(0, 128, n).tolist()
               for i, n in enumerate((5, 17, 30, 9, 22, 12))}
    mark = time.perf_counter_ns()
    for rid, prompt in prompts.items():
        engine.add_request(rid, prompt, max_new_tokens=6,
                           deadline_s=1.0 if rid == 2 else None)
    done = {}
    for _ in range(3):
        done.update(engine.step())
    now[0] = 5.0            # request 2 is preempted in mid-flight
    while engine.queue or engine.n_active:
        done.update(engine.step())
    assert set(done) == set(prompts) - {2}
    assert set(engine.pop_terminated()) == {2}
    assert engine.leak_report() == {}
    assert engine.alloc.available_page_count == 32
    # greedy, against the whole model (one shape: padding after a row
    # changes nothing for it)
    whole = jax.jit(functools.partial(model.apply, train=False))
    for rid, tokens in done.items():
        seq = list(prompts[rid])
        for _ in range(6):
            padded = jnp.asarray([seq + [0] * (40 - len(seq))])
            seq.append(int(jnp.argmax(whole(params, padded)[0, len(seq) - 1])))
        assert tokens == seq
    # the counters ride each prefill and decode dispatch, and its span
    dispatches = [d for r in engine.step_reports() for d in r["dispatches"]]
    assert all(set(SERVE_COUNTERS) <= set(d) for d in dispatches)
    # over the REAL queries alone: a prefill's bucket padding and a decode
    # batch's idle slots select nothing and are nobody's expert pairs
    k, layers = CFG["index_topk"], CFG["num_hidden_layers"]
    expert_layers = layers - CFG["first_k_dense_replace"]
    for d in dispatches:
        contexts = d["contexts"] if d["phase"] == "decode" else \
            range(1, d["real"] + 1)
        assert d["context_keys"] == layers * sum(contexts)
        assert d["selected"] == layers * sum(min(k, c) for c in contexts)
        assert d["expert_pairs"] <= expert_layers * len(contexts) * \
            CFG["num_experts_per_tok"]
        # the grouped product's rows: the pairs in whole tiles of 16
        assert d["expert_rows"] >= d["expert_pairs"]
        assert d["expert_rows"] % 16 == 0 and d["experts"] == "jnp"
    decode = [d for d in dispatches if d["phase"] == "decode"][0]
    assert decode["expert_load_max"] <= 4 < decode["expert_pairs"] + 5
    spans = [s for s in engine.telemetry.spans(mark)
             if s.name == "serve/step"]
    assert spans and all(set(SERVE_COUNTERS) <= set(s.attrs) for s in spans)
    # a pool that lost pages is a leak
    latent, index = engine.caches
    engine.caches = la.LatentKVCache(latent, index[:, :-1])
    assert "pool_page_mismatch" in engine.leak_report()


@pytest.mark.parametrize("kwargs,feature", [
    ({"serving": {"prefix_cache": {"enabled": True}}}, "prefix_cache"),
    ({"serving": {"scheduler": {"policy": "chunked"}}}, "scheduler.policy"),
    ({"serving": {"scheduler": {"policy": "chunked", "speculative": {
        "enabled": True, "num_draft_tokens": 2}}}}, "scheduler.policy"),
    ({"tp_size": 2}, "tp_size"),
    ({"ep_size": 2}, "ep_size"),
])
def test_what_is_not_built_is_refused_by_name(model, params, kwargs,
                                              feature):
    with pytest.raises(ServingUnsupported) as refused:
        _engine(model, params, **kwargs)
    assert feature in refused.value.feature
    assert "\n" not in str(refused.value)
    with pytest.raises(NotImplementedError):
        model.init_caches(1, 16)


def test_the_serving_programs_name_their_scopes(model, params):
    engine = _engine(model, params)
    engine.generate([[1, 2, 3, 4, 5], list(range(20))], max_new_tokens=3)
    # a latent model's own five (the rest are a window model's:
    # test_window_paged_serving.py)
    want = set(telemetry.SERVE_SCOPES[:5])
    assert want <= set(telemetry.op_scopes("serve/step_fn").values())
    # one table a prefill bucket: the site compiled two
    for bucket in (8, 32):
        table = telemetry.op_scopes("serve/prefill_fn",
                                    arg_shapes={1: (1, bucket)})
        assert want <= set(table.values())
    assert telemetry.op_scopes("serve/prefill_fn",
                               arg_shapes={1: (1, 64)}) == {}
    assert telemetry.phase_of("jit(f)/attn/latent_attn/norm/mul") == \
        "latent_attn"
    assert telemetry.phase_of("jit(f)/mlp/experts/while/body/dot") == \
        "experts"


def test_counter_and_scope_names_match_the_checker():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "scripts", "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("checker", path)
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    assert tuple(checker.SERVE_COUNTERS) == tuple(SERVE_COUNTERS)
    assert tuple(checker.SERVE_SCOPES) == tuple(telemetry.SERVE_SCOPES)


def test_latent_attention_without_a_selection_attends_to_every_key():
    """``index_topk`` 0: plain MLA, no indexer weights, every causal key."""
    plain = CausalTransformerLM(config(index_topk=0))
    weights = plain.init(jax.random.key(7), jnp.float32)
    assert "idx_wq" not in weights["layers"][0]
    ids = _ids(20, seed=4)
    whole = jax.jit(functools.partial(plain.apply, train=False))(weights,
                                                                 ids)
    through_pools, counts = _serve(plain, weights, ids, prefill=8)
    np.testing.assert_allclose(through_pools, whole, atol=2e-5)
    stats = dict(zip(SERVE_COUNTERS, counts[-1]))
    assert stats["selected"] == stats["context_keys"] == 3 * 20


@pytest.mark.parametrize("listed", [True, False])
@pytest.mark.parametrize("std", [None, 1.0, 0.5])
def test_the_seeded_embeddings_spread_as_the_config_says(listed, std):
    """``init_embed_std`` moves the token embeddings alone: None keeps
    rows of norm 1 (1 / sqrt(hidden) an element), a number is the
    elements' spread; every other leaf is drawn as before, by either
    ``init`` (listed layers or the stacked scan)."""
    make = config if listed else functools.partial(
        TransformerConfig.tiny, hidden_size=64, n_heads=4)
    plain = CausalTransformerLM(make()).init(jax.random.key(3))
    seeded = CausalTransformerLM(dataclasses.replace(
        make(), init_embed_std=std)).init(jax.random.key(3))
    want = CFG["hidden_size"] ** -0.5 if std is None else std
    assert float(jnp.std(seeded["tok_embed"])) == pytest.approx(want,
                                                                rel=0.05)
    same = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)),
        dict(plain, tok_embed=0), dict(seeded, tok_embed=0))
    assert all(jax.tree_util.tree_leaves(same))
