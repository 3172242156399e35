"""The aliased ``paged_kv_write`` kernel against the jnp scatter.

A serving dispatch writes each layer's new K and V rows into the stacked
page pools with this kernel and reads them with the ragged kernel by
layer index (``apply_with_paged_cache``).  Writing moves data and nothing
else, so the pools after the kernel are BIT-equal to the jnp scatter's on
every page but the scratch page (page 0: idle slots, bucket padding and
the overrun column land there, and nobody reads it).  All kernel runs use
``interpret=True`` (CPU tier-1); ``test_aot_tpu_compile.py`` takes the
same shapes through Mosaic."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.ops.paged_attention import PagedKVCache, write_paged
from deepspeed_tpu.ops.pallas.ragged_paged_attention import (
    VMEM_BUDGET, paged_kv_write, pick_write_blocks)

LAYERS = 3

# name, T, kv heads, page, per sequence (start, pages held); a sequence
# holding no page is an idle slot (its table row is all scratch).  The
# table has one column more than the longest reservation: the engine's
# overrun column, permanently page 0.
CASES = [
    ("decode_offset_0", 1, 2, 16, [(0, 1), (16, 2), (32, 3)]),
    ("decode_mid_page", 1, 2, 16, [(5, 1), (23, 2), (40, 3)]),
    ("decode_last_row", 1, 2, 16, [(15, 1), (31, 2), (47, 3)]),
    ("prefill_from_0", 48, 2, 16, [(0, 3)]),
    ("prefill_prefix_hit", 20, 2, 16, [(7, 2), (29, 4)]),
    ("chunk_continuation", 16, 2, 8, [(24, 5), (3, 3)]),
    # positions 24..55 of a 3-page reservation: the bucket's padding runs
    # one page past it, onto the overrun column
    ("bucket_overrun", 32, 2, 16, [(24, 3)]),
    ("idle_slots_on_scratch", 1, 2, 16, [(5, 1), (0, 0), (21, 2), (0, 0)]),
    ("gqa_16_4_page16", 1, 4, 16, [(9, 1), (16, 2)]),
    ("gqa_16_4_page16_prefill", 40, 4, 16, [(0, 3)]),
    # a page of several row blocks (decode and the verify window move a
    # sublane group, not the page), starts on either side of a boundary
    ("page128_decode", 1, 2, 128, [(14, 1), (127, 1), (250, 2)]),
    ("page128_verify_window", 5, 2, 128, [(14, 1), (125, 2), (250, 2)]),
    ("page128_chunk_40", 40, 2, 128, [(100, 2), (0, 1)]),
    ("page_below_a_tile", 3, 2, 4, [(2, 2), (5, 3)]),
]


def _state(T, hkv, page, seqs, dtype, d=32, seed=0):
    rng = np.random.default_rng(seed)
    width = max(n for _, n in seqs) + 1
    tables = np.zeros((len(seqs), width), np.int32)
    nxt = 1
    for b, (_, n) in enumerate(seqs):
        tables[b, :n] = np.arange(nxt, nxt + n)
        nxt += n

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    pool = (LAYERS, nxt, hkv, page, d)
    new = (len(seqs), T, hkv, d)
    return (PagedKVCache(rand(*pool), rand(*pool)), jnp.asarray(tables),
            jnp.asarray([s for s, _ in seqs], jnp.int32), rand(*new),
            rand(*new))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("name,T,hkv,page,seqs", CASES,
                         ids=[c[0] for c in CASES])
def test_kernel_equals_scatter(name, T, hkv, page, seqs, dtype):
    cache, tables, starts, k_new, v_new = _state(T, hkv, page, seqs, dtype)
    for layer in (0, LAYERS - 1):
        want = write_paged(cache, layer, tables, starts, k_new, v_new,
                           impl="jnp")
        got = jax.jit(paged_kv_write, static_argnames="interpret")(
            cache.k_pages, cache.v_pages, jnp.int32(layer), tables, starts,
            k_new, v_new, interpret=True)
        for g, w, before in zip(got, want, cache):
            g, w, before = (np.asarray(x, np.float32)
                            for x in (g, w, before))
            np.testing.assert_array_equal(g[:, 1:], w[:, 1:])
            # something was written, and only into this layer
            assert (g[layer, 1:] != before[layer, 1:]).any()
            others = [i for i in range(LAYERS) if i != layer]
            np.testing.assert_array_equal(g[others], before[others])


@pytest.mark.parametrize("T,hkv,page,d,itemsize,rows,blocks", [
    (1, 16, 128, 128, 2, 16, 1),       # chat / docbatch decode
    (5, 16, 128, 128, 2, 16, 2),       # speculative verify window
    (128, 16, 128, 128, 2, 128, 2),    # the smallest prefill bucket
    (4096, 16, 128, 128, 2, 128, 33),  # the largest
    (1, 4, 16, 128, 2, 16, 1),         # the smoke server's pages of 16
    (1, 2, 8, 16, 4, 8, 1),
    (3, 2, 4, 16, 2, 4, 2),            # a page under a sublane tile
    (40, 8, 128, 128, 4, 64, 2),
])
def test_pick_write_blocks(T, hkv, page, d, itemsize, rows, blocks):
    choice = pick_write_blocks(T, hkv, page, d, itemsize)
    assert (choice.rows, choice.blocks) == (rows, blocks)
    assert page % choice.rows == 0 and hkv % choice.heads == 0
    assert 12 * choice.heads * choice.rows * max(d, 128) * itemsize \
        <= VMEM_BUDGET


# -- the whole dispatch ------------------------------------------------------

@pytest.fixture(scope="module", params=["scan_stack", "list_stack"])
def tiny(request):
    kw = dict(hidden_size=64, n_heads=4, n_kv_heads=2)
    if request.param == "list_stack":
        # every expert serves every token: no rounding error flips a route
        kw.update(moe_num_experts=2, moe_top_k=2)
    model = CausalTransformerLM(TransformerConfig.tiny(**kw))
    params = model.init(jax.random.key(0))
    assert isinstance(params["layers"], list) == \
        (request.param == "list_stack")
    return model, params


def _dispatch(model, params, backend, ids, caches, tables, lengths):
    impl, interpret = (("pallas", True) if backend == "pallas-interpret"
                       else ("jnp", False))
    return jax.jit(lambda *a: model.apply_with_paged_cache(
        *a, attn_backend=impl, attn_interpret=interpret))(
            params, ids, caches, tables, lengths)


def test_apply_with_paged_cache_equals_jnp_backend(tiny):
    """A prefill from an unaligned start, then a decode step: logits and
    both pools as the ``"jnp"`` backend leaves them off the scratch page:
    the first layer's rows to the bit, everything after a read to float32
    rounding (the two read paths sum in another order)."""
    model, params = tiny
    cfg = model.config
    page, B = 8, 3
    rng = np.random.default_rng(1)
    steps = [jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)))
             for T in (9, 1)]
    tables = np.zeros((B, 5), np.int32)
    tables[0, :3], tables[2, :4] = [1, 2, 3], [4, 5, 6, 7]  # slot 1 idle
    tables = jnp.asarray(tables)
    outs = {}
    for backend in ("jnp", "pallas-interpret"):
        caches = jax.tree_util.tree_map(
            lambda x: jnp.asarray(
                np.random.default_rng(2).standard_normal(x.shape), x.dtype),
            model.init_paged_caches(8, page, dtype=jnp.float32))
        lengths = jnp.asarray([3, 0, 10], jnp.int32)
        got = []
        for ids in steps:
            logits, caches, lengths = _dispatch(model, params, backend, ids,
                                                caches, tables, lengths)
            got.append(logits)
        outs[backend] = (got, caches)
    (want_logits, want), (got_logits, got) = outs["jnp"], \
        outs["pallas-interpret"]
    for g, w in zip(got_logits, want_logits):
        np.testing.assert_allclose(np.asarray(g)[[0, 2]],
                                   np.asarray(w)[[0, 2]],
                                   rtol=2e-5, atol=2e-5)
    for g, w in zip(got, want):
        g, w = np.asarray(g)[:, 1:], np.asarray(w)[:, 1:]
        np.testing.assert_array_equal(g[0], w[0])
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)


def test_decode_chunk_2_equals_jnp_backend(tiny):
    """``decode_chunk`` scans the paged call with the pools in ITS carry:
    the same tokens from both backends."""
    from deepspeed_tpu.inference.serving import ServingEngine
    model, params = tiny
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.config.vocab_size, (n,)).tolist()
               for n in (5, 11)]

    def run(backend):
        eng = ServingEngine(model, params, max_batch=2, page_size=8,
                            max_seq=32, dtype=jnp.float32, decode_chunk=2,
                            serving={"attention_backend": backend})
        out = eng.generate(prompts, max_new_tokens=5)
        assert eng.leak_report() == {}
        phases = {d["phase"]: d["kv_write"] for rep in eng.step_reports()
                  for d in rep["dispatches"]}
        return out, phases

    want, phases = run("jnp")
    assert phases == {"prefill": "jnp", "decode_chunk": "jnp"}
    got, phases = run("pallas-interpret")
    assert phases == {"prefill": "pallas", "decode_chunk": "pallas"}
    assert got == want


@pytest.mark.parametrize("scheduler", [
    {"policy": "chunked", "prefill_chunk_tokens": 5},
    {"policy": "chunked", "prefill_chunk_tokens": 5,
     "speculative": {"enabled": True, "num_draft_tokens": 3}},
], ids=["chunked_prefill", "speculative_verify"])
def test_unaligned_starts_through_the_engine(tiny, scheduler):
    """Chunks of 5 over pages of 8 start every chunk but the first off a
    page boundary, and the verify window writes 4 rows wherever a slot
    stands: the same tokens from both backends, nothing leaked."""
    from deepspeed_tpu.inference.serving import ServingEngine
    model, params = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, model.config.vocab_size, (n,)).tolist()
               for n in (13, 7, 18)]

    def run(backend):
        draft = ({"draft_model": model, "draft_params": params}
                 if "speculative" in scheduler else {})
        eng = ServingEngine(model, params, max_batch=2, page_size=8,
                            max_seq=40, dtype=jnp.float32,
                            serving={"attention_backend": backend,
                                     "scheduler": scheduler}, **draft)
        out = eng.generate(prompts, max_new_tokens=6)
        assert eng.leak_report() == {}
        return out

    assert run("pallas-interpret") == run("jnp")
