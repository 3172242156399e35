"""Sliding-window and full-attention layers in one paged cache, on the
serving path: toy sizes of Trinity-Mini's shape (``afmoe``: group 8, a
window of 16, pages of 8, 2 dense layers, two periods of three window
layers and a full one, gated attention, 4 of 16 sigmoid-routed experts
held), in float32 on the CPU, against the plain reference of
``chipbench/reference/afmoe.py`` on logits.

Tolerances: float32 on both sides, the reference at full matmul precision,
so rows agree to rounding: 2e-5 of the largest reference logit (the
program's softmax and norms are float32 too; what is left is the order of
sums).  A wrong key, a lost ring page or a missing term reads 1e-2 and
more at these sizes."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import afmoe as family
from chipbench.reference import afmoe as reference
from deepspeed_tpu.inference import serving
from deepspeed_tpu.inference.robustness import ServingUnsupported
from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              ServeCounts, TransformerConfig)
from deepspeed_tpu.monitor import telemetry
from deepspeed_tpu.ops.paged_attention import (PagedKVCache, WindowedKVCache,
                                               ring_pages)
from deepspeed_tpu.ops.pallas.ragged_paged_attention import rect_item_map

WINDOW, PAGE = 16, 8
RING = ring_pages(WINDOW, PAGE)         # 3 pages: 24 rows a slot
TOL = 2e-5

# the configuration file's keys, as the family and the reference read them
CFG = {
    "global_attn_every_n_layers": 4, "head_dim": 16, "hidden_size": 64,
    "intermediate_size": 96,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "max_position_embeddings": 512, "moe_intermediate_size": 32,
    "mup_enabled": True, "num_attention_heads": 8, "num_dense_layers": 2,
    "num_experts": 4, "num_experts_per_tok": 2, "num_hidden_layers": 8,
    "num_key_value_heads": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-5,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": WINDOW,
    "tie_word_embeddings": False, "vocab_size": 128,
    "published": {"num_experts": 16}}


def config(**changed):
    return TransformerConfig(remat=False,
                             **family.transformer_kwargs(dict(CFG, **changed)))


@pytest.fixture(scope="module")
def model():
    return CausalTransformerLM(config())


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(11), jnp.float32)


def _engine(model, params, backend="jnp", **kwargs):
    kwargs.setdefault("serving", {"attention_backend": backend})
    return ServingEngine(model, params, max_batch=3, page_size=PAGE,
                         max_seq=160, num_pages=61, dtype=jnp.float32,
                         **kwargs)


def _serve_with_rows(engine, prompts, budgets):
    """Serve ``prompts`` ({id: tokens}) to the end: ({id: all tokens},
    {id: the logits rows the engine sampled from})."""
    rows, original = {}, engine._sample

    def sample(req, row):
        rows.setdefault(req.req_id, []).append(np.array(row, np.float32))
        return original(req, row)

    engine._sample = sample
    for rid, prompt in prompts.items():
        engine.add_request(rid, prompt, max_new_tokens=budgets[rid])
    done = {}
    while len(done) < len(prompts):
        done.update(engine.step())
    engine._sample = original
    return done, {rid: np.stack(r) for rid, r in rows.items()}


def _errors(params, done, rows):
    """id -> largest row error over the largest reference logit."""
    out = {}
    for rid, got in rows.items():
        ids = np.asarray(done[rid], np.int32)[None, :-1]
        want, decided = reference.logits(params, jnp.asarray(ids), CFG,
                                         last=len(got))
        assert decided.shape == (1, len(got))
        want = np.asarray(want)[0]
        out[rid] = float(np.abs(got - want).max() / np.abs(want).max())
    return out


# prompt lengths against a window of 16 (two pages) and a ring of 24 rows:
# shorter than the window, the window, a page boundary on either side of
# it, a little and several times the ring (the last beside short ones in
# one batch), and budgets that decode two turns of the ring and more
PROMPTS = (5, 16, 17, 24, 25, 100, 8, 64)
BUDGETS = (60, 50, 9, 8, 30, 52, 7, 24)
# the kernel in the interpreter is slow: the first, third and sixth
KERNEL_CASES = (0, 2, 5)


@pytest.fixture(scope="module", params=["jnp", "pallas-interpret"])
def served(request, model, params):
    engine = _engine(model, params, request.param)
    rng = np.random.default_rng(3)
    prompts = {i: rng.integers(0, CFG["vocab_size"], n).tolist()
               for i, n in enumerate(PROMPTS)
               if request.param == "jnp" or i in KERNEL_CASES}
    done, rows = _serve_with_rows(engine, prompts, dict(enumerate(BUDGETS)))
    return engine, done, rows


def test_engine_agrees_with_the_reference_across_the_window_and_the_ring(
        params, served):
    engine, done, rows = served
    assert engine.attention_impl == \
        ("jnp" if engine.attention_backend == "jnp" else "pallas")
    errors = _errors(params, done, rows)
    assert set(errors) == (set(range(len(PROMPTS)))
                           if engine.attention_backend == "jnp"
                           else set(KERNEL_CASES))
    assert max(errors.values()) < TOL, errors
    for rid in errors:
        assert rows[rid].shape == (BUDGETS[rid], CFG["vocab_size"])
        assert len(done[rid]) == PROMPTS[rid] + BUDGETS[rid]
    # request 0 decoded 60 tokens from 5: past two turns of its 24 rows
    assert PROMPTS[0] + BUDGETS[0] > 2 * RING * PAGE


def test_the_rings_come_back_and_nothing_leaks(served):
    engine = served[0]
    assert engine.leak_report() == {}
    alloc = engine.alloc
    assert alloc.seq_rings == {} and alloc.ring_pages_in_use == 0
    assert sorted(alloc.ring_free) == list(range(1, 1 + 3 * RING))
    assert not engine.tables.any()


def test_a_window_layers_memory_a_slot_does_not_grow_with_the_context(
        model, params):
    engine = _engine(model, params)
    assert isinstance(engine.caches, WindowedKVCache)
    full, ring = engine.caches
    # 2 full layers under the growing tables, 6 window layers in rings of
    # 3 pages a slot whatever max_seq (20 pages a sequence)
    assert full.k_pages.shape == (2, 61, 1, PAGE, 16)
    assert ring.k_pages.shape == (6, 3 * RING + 1, 1, PAGE, 16)
    assert engine.tables.shape == (3, 160 // PAGE + 1 + RING)
    longer = ServingEngine(model, params, max_batch=3, page_size=PAGE,
                           max_seq=480, dtype=jnp.float32)
    assert longer.caches.ring.k_pages.shape == ring.k_pages.shape
    assert longer.caches.full.k_pages.shape[1] == 3 * 60 + 1
    engine.add_request("a", list(range(90)), max_new_tokens=40)
    mine = list(engine.alloc.seq_rings["a"])
    seen = set()
    while engine.n_active:
        slot = engine.tables[0]
        assert list(slot[-RING:]) == mine == engine.alloc.seq_rings["a"]
        seen.add(int(engine.lengths[0]))
        report = engine.last_step or {"dispatches": []}
        for d in report["dispatches"]:
            assert d["pages_ring"] == RING
            assert d["pages_full"] == -(-130 // PAGE)
        engine.step()
    assert max(seen) >= 128 and len(mine) == RING
    assert engine.leak_report() == {}


def test_a_model_with_no_window_builds_the_pools_it_always_had(params):
    plain = CausalTransformerLM(TransformerConfig.tiny(hidden_size=64,
                                                       n_heads=4))
    engine = ServingEngine(plain, plain.init(jax.random.key(0)), max_batch=2,
                           page_size=PAGE, max_seq=64, dtype=jnp.float32)
    assert isinstance(engine.caches, PagedKVCache) and engine.ring_pages == 0
    assert engine.caches.k_pages.shape == (2, 2 * 8 + 1, 4, PAGE, 16)
    assert engine.tables.shape == (2, 8 + 1)
    assert engine.alloc.ring_free == [] and engine.alloc.ring_available()
    engine.generate([[1, 2, 3]], max_new_tokens=3)
    assert "pages_ring" not in engine.last_step["dispatches"][-1]
    assert engine.leak_report() == {}


def test_every_dispatch_carries_the_window_models_counts(model, params):
    engine = _engine(model, params)
    engine.add_request("long", list(range(40)), max_new_tokens=3)
    engine.add_request("short", list(range(5)), max_new_tokens=3)
    dispatches = list(engine._report["dispatches"])
    while engine.n_active:
        engine.step()
        dispatches += engine.last_step["dispatches"]
    want = set(serving.WINDOW_COUNTS) | {"expert_pairs", "expert_load_max",
                                         "expert_rows"}
    assert all(want <= set(d) and "selected" not in d for d in dispatches)
    # the grouped product's rows: every pair, in whole tiles (16 rows at
    # these sizes); the CPU's implementation by name
    assert all(d["expert_rows"] >= d["expert_pairs"]
               and d["expert_rows"] % 16 == 0 and d["experts"] == "jnp"
               for d in dispatches)
    prefill = next(d for d in dispatches if d["phase"] == "prefill")
    # 40 real queries of a 64 bucket: 8 layers see 1 + .. + 40 causal keys,
    # the 6 window layers at most 16 of them
    assert prefill["real"] == 40 and prefill["context_keys"] == 8 * 820
    assert prefill["attended_keys"] == 2 * 820 + 6 * (136 + 24 * 16)
    # 6 expert layers x 40 real tokens x 2 experts, of which 4 of 16 here
    assert 0 < prefill["expert_pairs"] < 6 * 40 * 2
    decode = next(d for d in dispatches if d["phase"] == "decode")
    assert decode["contexts"] == [41, 6]
    assert decode["context_keys"] == 8 * 47
    assert decode["attended_keys"] == 2 * 47 + 6 * (16 + 6)


def test_counts_and_scopes_are_the_frozen_ones(model, params):
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "scripts", "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("checker", path)
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    assert tuple(checker.WINDOW_COUNTS) == tuple(serving.WINDOW_COUNTS)
    engine = _engine(model, params)
    engine.generate([list(range(20))], max_new_tokens=3)
    want = {"attn_window", "attn_full", "attn_gate", "router", "experts",
            "shared_expert"}
    assert want <= set(telemetry.SERVE_SCOPES)
    assert want <= set(telemetry.op_scopes("serve/step_fn").values())
    assert want <= set(telemetry.op_scopes(
        "serve/prefill_fn", arg_shapes={1: (1, 32)}).values())
    assert telemetry.phase_of("jit(f)/attn/attn_window/while/body/dot") == \
        "attn_window"


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
    assert "sliding-window" in refused.value.feature
    assert "\n" not in str(refused.value)


@pytest.mark.parametrize("call,feature", [
    (lambda e: e.add_request("r", [1, 2, 3], prefill_only=True),
     "prefill_only"),
    (lambda e: e.export_pages([1, 2]), "export_pages"),
    (lambda e: e.import_pages(None, [1, 2]), "import_pages"),
    (lambda e: e.import_request(None), "import_request"),
])
def test_migration_is_refused_by_name(model, params, call, feature):
    engine = _engine(model, params)
    with pytest.raises(ServingUnsupported) as refused:
        call(engine)
    assert feature in refused.value.feature
    assert engine.leak_report() == {}


def test_alibi_keeps_its_own_refusal():
    bloom = CausalTransformerLM(TransformerConfig.tiny(
        hidden_size=64, n_heads=4, use_alibi=True, use_rope=False))
    with pytest.raises(AssertionError, match="ALiBi"):
        bloom.init_paged_caches(9, PAGE)


def test_stacked_periods_are_the_listed_layers(model, params):
    """``layer_period`` changes where the weights lie and how the serving
    forward loops, not one value: the same seed, listed."""
    listed_model = CausalTransformerLM(dataclasses.replace(
        model.config, layer_period=0))
    listed = listed_model.init(jax.random.key(11), jnp.float32)
    assert len(params["layers"]) == 4 and len(listed["layers"]) == 8
    assert [jax.tree_util.tree_leaves(p)[0].shape[0]
            for p in params["periods"]] == [1] * 4
    for a, b in zip(model.layer_list(params), listed["layers"]):
        jax.tree_util.tree_map(np.testing.assert_array_equal, a, b)
    ids = jax.random.randint(jax.random.key(2), (2, 45), 0, 128)
    whole = model.apply(params, ids, train=False)
    # since PR 43 ``apply`` SCANS the periods (one compiled body) where the
    # listed model unrolls its layers: float32 sums in another order, 2e-6
    # of logits of about 1 on ten of 11,520 values
    np.testing.assert_allclose(listed_model.apply(listed, ids, train=False),
                               whole, atol=3e-6)
    want, _ = reference.logits(params, ids, CFG)
    assert float(jnp.abs(whole - want).max() / jnp.abs(want).max()) < TOL
    prompts = {0: list(range(30)), 1: list(range(7))}
    rows = [_serve_with_rows(_engine(m, p), prompts, {0: 6, 1: 6})[1]
            for m, p in ((model, params), (listed_model, listed))]
    for rid in prompts:
        np.testing.assert_allclose(rows[0][rid], rows[1][rid], atol=1e-5)


def test_three_periods_scan_and_count_like_the_unrolled_loop():
    """Twelve layers: the scan runs two periods, each layer at its place
    in its kind's stack."""
    cfg = dict(CFG, num_hidden_layers=12,
               layer_types=CFG["layer_types"] + CFG["layer_types"][:4])
    scanned = CausalTransformerLM(TransformerConfig(
        remat=False, **family.transformer_kwargs(cfg)))
    params = scanned.init(jax.random.key(5), jnp.float32)
    engine = _engine(scanned, params)
    assert engine.caches.ring.k_pages.shape[0] == 9
    assert engine.caches.full.k_pages.shape[0] == 3
    done, rows = _serve_with_rows(engine, {0: list(range(37))}, {0: 12})
    ids = np.asarray(done[0], np.int32)[None, :-1]
    want, _ = reference.logits(params, jnp.asarray(ids), cfg, last=12)
    assert np.abs(rows[0] - np.asarray(want)[0]).max() \
        / np.abs(np.asarray(want)).max() < TOL
    listed = CausalTransformerLM(dataclasses.replace(scanned.config,
                                                     layer_period=0))
    other = _engine(listed, dict(params, layers=scanned.layer_list(params)))
    other.add_request(0, list(range(37)), max_new_tokens=30)
    engine.add_request(1, list(range(37)), max_new_tokens=30)
    for name in ("expert_pairs", "expert_load_max", "expert_rows",
                 "attended_keys"):
        assert engine._report["dispatches"][0][name] == \
            other._report["dispatches"][0][name]


def test_kernel_grid_counts_the_item_maps_of_both_kinds(model, params):
    """``kernel_grid`` on the host is what the kernels' item maps hold:
    the full layers' over the growing table, the window layers' over the
    ring (a decode step) or over the prefill's own rows."""
    engine = _engine(model, params, "pallas-interpret")
    ring, full = engine.caches.ring.k_pages, engine.caches.full.k_pages
    starts = np.array([100, 0, 21], np.int32)
    shape = (3, 1, 8, 16)
    items = int(rect_item_map(shape, full, engine.tables[:, :-RING],
                              jnp.asarray(starts + 1)).first[-1])
    ring_items = int(rect_item_map(
        shape, ring, engine.tables[:, -RING:], jnp.asarray(starts + 1),
        window=WINDOW, ring=RING).first[-1])
    run, whole = engine.kernel_grid("decode", 3, 1, starts)
    assert run == 2 * items + 6 * ring_items and run < whole
    shape = (1, 64, 8, 16)
    fresh = jax.ShapeDtypeStruct((1, 8, 1, PAGE, 16), jnp.float32)
    items = int(rect_item_map(shape, full, engine.tables[:1, :-RING],
                              jnp.asarray([64])).first[-1])
    fresh_items = int(rect_item_map(
        shape, fresh, jax.ShapeDtypeStruct((1, 8), jnp.int32),
        jnp.asarray([64]), window=WINDOW).first[-1])
    run, _ = engine.kernel_grid("prefill", 1, 64, np.zeros(1, np.int32))
    assert run == 2 * items + 6 * fresh_items


def test_ring_sizes():
    assert ring_pages(2048, 128) == 17 and ring_pages(16, 8) == 3
    assert ring_pages(2049, 128) == 18
    with pytest.raises(AssertionError, match="rows through a ring"):
        from deepspeed_tpu.ops.paged_attention import paged_decode_attention
        pool = PagedKVCache(jnp.zeros((1, 4, 1, 8, 16)),
                            jnp.zeros((1, 4, 1, 8, 16)))
        paged_decode_attention(
            jnp.zeros((1, 10, 8, 16)), pool, jnp.zeros((1, 3), jnp.int32),
            jnp.full((1,), 30), layer=0, window=16, ring=3, impl="jnp")


def test_the_shares_add_up_to_the_uncut_layer(params):
    """4 shares of 4 of 16 experts, the shared expert counted once, are
    the uncut layer; the uncut layer is the reference's; and the dense
    layers, which every chip computes alike, are the reference's too."""
    layer = params["layers"][2]
    h = jax.random.normal(jax.random.key(5), (1, 37, 64))
    share_model = CausalTransformerLM(config())
    key = jax.random.key(9)
    whole_moe = dict(layer["moe"], **{
        name: jax.random.normal(jax.random.fold_in(key, i), (16,) +
                                layer["moe"][name].shape[1:]) / 8
        for i, name in enumerate(("w_gate", "w_up", "w_down"))})
    uncut_model = CausalTransformerLM(config(num_experts=16))
    uncut, _ = uncut_model._mlp_delta(h, dict(layer, moe=whole_moe),
                                      train=False)
    counts, total = ServeCounts(jnp.ones((1, 37), bool)), 0.0
    for first in range(0, 16, 4):
        held = {k: whole_moe[k][first:first + 4]
                for k in ("w_gate", "w_up", "w_down")}
        share = CausalTransformerLM(dataclasses.replace(
            share_model.config, moe_experts_first=first))
        moe = dict(whole_moe, **held)
        if first:           # the shared expert on one chip alone
            moe.pop("shared")
        part, _ = share._mlp_delta(h, dict(layer, moe=moe), train=False,
                                   counts=counts)
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=1e-5)
    assert int(counts.counts["expert_pairs"]) == 37 * 2   # every pair, once
    sizes = reference._Sizes(dict(CFG, num_experts=16))
    with jax.default_matmul_precision("highest"):
        want, _ = reference._expert_layer(h[0], whole_moe, sizes)
        dense = params["layers"][0]
        want_dense = reference._glu(h[0], dense["w_gate"], dense["w_up"],
                                    dense["w_down"])
    np.testing.assert_allclose(uncut[0], want, atol=1e-5)
    got_dense, _ = share_model._mlp_delta(h, dense, train=False)
    np.testing.assert_allclose(got_dense[0], want_dense, atol=1e-5)
