"""The dense latent prefill's Pallas kernel (PR 45;
``ops/pallas/latent_attention.py``), through the interpreter on the CPU.

The XLA walk stays as the oracle: ``la.context_attention`` over the cached
entries and ``la.prefill_attention(q_i=None)`` from its state over the
chunk's own decompressed keys.  The kernel reads cached entries and chunk
alike out of the pool (``write_latent`` goes first), so every case writes
the chunk at ``lengths`` before it calls it, over a pool that is random
everywhere else: what lies past a sequence's last real row must not be
seen.  Then the engine: a toy Kimi under the chunked policy and under the
monolithic policy's pieces serves the tokens and logits of the ``jnp``
backend and says ``latent: "pallas"`` on every prefill dispatch; a model
with an indexer says ``"jnp"`` and runs what it ran; a model without
latent attention says nothing.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import serving
from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.ops import latent_attention as la
from deepspeed_tpu.ops.pallas import latent_attention as lp
from unit import test_dense_latent_serving, test_latent_serving

# float32 on both sides: the kernel's steps and the walk's blocks cut the
# keys otherwise, so sums differ in their order (the ragged kernel's tests
# hold theirs to the same)
TOL = dict(rtol=2e-5, atol=2e-5)
KIMI = (128, 64, 128)       # nope, rope and value widths of Kimi-K2
OTHER = (16, 8, 24)         # a second pair: keys of 24, values of 24
PAGE, PAGES_A_SEQ = 8, 24


def _case(B, T, lengths, real, widths, heads=4, rank=32, seed=0):
    """A pool of random rows, ``lengths`` cached entries a sequence under
    shuffled pages and the chunk's T rows written at ``lengths``; the
    kernel's operands and the oracle's answer."""
    dn, dr, dv = widths
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(                     # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(B * PAGES_A_SEQ).reshape(
        B, PAGES_A_SEQ), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    cache = la.init_latent_pools(2, 1 + B * PAGES_A_SEQ, PAGE, rank + dr, 0,
                                 jnp.float32)
    row = cache.latent_pages.shape[-1]
    # garbage wherever nothing is written, the rows' zero tail kept
    cache = cache._replace(latent_pages=normal(
        *cache.latent_pages.shape).at[..., rank + dr:].set(0))
    layer = 1
    zero = jnp.zeros((B,), jnp.int32)
    cached = normal(B, int(max(lengths)) or 1, rank + dr)
    cache = la.write_latent(cache, layer, tables, zero, cached, None)
    c_kv, k_rope = normal(B, T, rank), normal(B, T, dr)
    cache = la.write_latent(cache, layer, tables, lengths,
                            jnp.concatenate([c_kv, k_rope], -1), None)
    q_nope, q_rope = normal(B, T, heads, dn), normal(B, T, heads, dr)
    w = normal(rank, heads * (dn + dv)) / np.sqrt(rank)
    scale = 1 / np.sqrt(dn + dr)
    rows = jnp.full((B,), T, jnp.int32) if real is None \
        else jnp.asarray(real, jnp.int32)
    mask = jnp.arange(T)[None] < rows[:, None]

    # the oracle never reads the chunk's rows of the pool: it is given a
    # pool that holds the cached entries alone
    before = la.write_latent(
        la.init_latent_pools(2, 1 + B * PAGES_A_SEQ, PAGE, rank + dr, 0,
                             jnp.float32), layer, tables, zero, cached, None)
    q = jnp.concatenate([q_nope, q_rope], -1)
    kv = (c_kv @ w).reshape(B, T, heads, dn + dv)
    keys = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_rope[:, :, None], (B, T, heads, dr))], -1)
    state = la.context_attention(
        q, before, layer, tables, lengths, w.reshape(rank, heads, dn + dv),
        dr, scale, real=mask, block_q=16, block_k=32)
    want, _, _ = la.prefill_attention(
        q, keys, kv[..., dn:], None, None, None,
        lengths[:, None] + jnp.arange(T)[None], 0, scale, real=mask,
        block_q=16, block_k=32, state=state)
    assert row % 128 == 0
    operands = (q_nope, q_rope, cache.latent_pages, layer, tables, lengths,
                w, scale)
    return operands, rows, np.asarray(mask), np.asarray(want)


def _tiles(q_tile, rows, heads, pages):
    return lp.LatentTiles(q_tile, rows, heads, pages, 0)


# id: (B, T, cached lengths, real rows or None, widths, tiles or None)
CASES = {
    "nothing-cached": (1, 16, [0], None, KIMI, None),
    "one-partial-page": (1, 16, [5], None, KIMI, None),
    "not-a-multiple-of-the-step": (1, 16, [37], None, KIMI,
                                   _tiles(16, 16, 1, 2)),
    "several-steps": (1, 16, [150], None, KIMI, _tiles(16, 16, 2, 2)),
    "other-widths": (1, 16, [37], None, OTHER, None),
    "other-widths-several-steps": (1, 32, [101], None, OTHER,
                                   _tiles(16, 32, 4, 1)),
    "three-unequal": (3, 16, [37, 0, 16], None, KIMI, None),
    "three-unequal-padded-rows": (3, 40, [37, 0, 16], [33, 40, 7], OTHER,
                                  _tiles(16, 48, 2, 2)),
    "a-sequence-of-padding-alone": (3, 40, [37, 0, 16], [33, 0, 7], OTHER,
                                    _tiles(16, 16, 4, 1)),
    "T-not-a-multiple-of-the-tile": (1, 40, [21], None, KIMI,
                                     _tiles(16, 48, 1, 3)),
    "T-not-a-multiple-padded": (1, 40, [21], [29], OTHER, None),
    "blocks-of-rows": (2, 64, [40, 3], [64, 50], OTHER,
                       _tiles(16, 32, 2, 2)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_is_the_walk_over_the_pool_and_the_causal_prefill(name):
    B, T, lengths, real, widths, tiles = CASES[name]
    operands, rows, mask, want = _case(B, T, lengths, real, widths)
    got = np.asarray(lp.latent_prefill_attention(
        *operands, real_lengths=rows, tiles=tiles, interpret=True))
    assert got.shape == want.shape == (B, T, 4, widths[2])
    np.testing.assert_allclose(got[mask], want[mask], **TOL)
    assert np.isfinite(got).all()
    if real is None:        # ... and without the count every row is one
        again = np.asarray(lp.latent_prefill_attention(
            *operands, tiles=tiles, interpret=True))
        np.testing.assert_array_equal(again, got)


def test_a_tile_of_padding_comes_out_as_zeros_and_costs_no_step():
    """Rows past the real ones are never a tile's reason to run: a whole
    tile of them is zeros, whatever the pool holds."""
    operands, rows, mask, want = _case(2, 64, [40, 3], [17, 0], OTHER)
    got = np.asarray(lp.latent_prefill_attention(
        *operands, real_lengths=rows, tiles=_tiles(16, 32, 2, 2),
        interpret=True))
    np.testing.assert_allclose(got[mask], want[mask], **TOL)
    assert not got[0, 32:].any() and not got[1].any()
    assert got[0, 17:32].any()      # the tile that holds real rows ran


def test_the_kernel_runs_inside_jit_with_traced_lengths_and_layer():
    """One program whatever the contexts hold: the key steps' bound, the
    lengths, the real rows and the layer are all traced."""
    operands, rows, mask, want = _case(3, 16, [37, 0, 16], [16, 9, 16], KIMI)
    q_nope, q_rope, pool, layer, tables, lengths, w, scale = operands

    @jax.jit
    def run(layer, lengths, rows, pool):
        return lp.latent_prefill_attention(
            q_nope, q_rope, pool, layer, tables, lengths, w, scale,
            real_lengths=rows, interpret=True)

    got = np.asarray(run(jnp.int32(layer), lengths, rows, pool))
    np.testing.assert_allclose(got[mask], want[mask], **TOL)
    # the other layer holds other rows: the index map reads ``layer``
    other = np.asarray(run(jnp.int32(0), lengths, rows, pool))
    assert np.abs(other[mask] - want[mask]).max() > 1e-2
    assert run._cache_size() == 1


@pytest.mark.parametrize("T,want", [
    # the cell's chunk: all 2,048 rows meet a block of 1,024 keys (eight
    # pages of 128) decompressed once, one head a step
    (2048, (512, 2048, 1, 8)),
    (512, (512, 512, 1, 8)), (8192, (512, 2048, 1, 8)),
    (1000, (512, 1024, 1, 8)), (40, (48, 48, 1, 8))])
def test_tiles_at_kimis_widths_come_from_the_shape(T, want):
    tiles = lp.pick_latent_tiles(T, 64, 128, 128, 512, 640, 128, 137, 2)
    assert tiles[:4] == want
    assert tiles.vmem_bytes <= lp.VMEM_BUDGET


def test_tiles_of_a_toy_take_every_head_and_no_more_pages_than_the_table():
    tiles = lp.pick_latent_tiles(16, 4, 24, 16, 32, 128, 8, 21, 4)
    assert tiles[:4] == (16, 16, 4, 8)
    assert lp.pick_latent_tiles(16, 4, 24, 16, 32, 128, 8, 3, 4).pages == 3
    # a step never holds more than the budget: pages go first
    wide = lp.pick_latent_tiles(2048, 64, 128, 128, 512, 640, 1024, 137, 2)
    assert wide.pages == 1 and wide.vmem_bytes <= lp.VMEM_BUDGET


# ----------------------------------------------------------------------
# through the engine
# ----------------------------------------------------------------------
BACKENDS = ("jnp", "pallas-interpret")


@pytest.fixture(scope="module")
def kimi():
    _, model, _ = test_dense_latent_serving.toy("kimi_k2")
    return model, model.init(jax.random.key(11), jnp.float32)


def _prompts(lengths, seed=2):
    rng = np.random.default_rng(seed)
    return {i: rng.integers(0, 512, n).astype(np.int32)
            for i, n in enumerate(lengths)}


def _serve(model, params, backend, prompts, chunk=None, max_seq=160):
    scheduler = {"policy": "chunked", "prefill_chunk_tokens": chunk,
                 "max_prefill_chunks_per_step": 1} if chunk else \
        {"policy": "monolithic"}
    engine = ServingEngine(
        model, params, max_batch=4, page_size=8, max_seq=max_seq,
        dtype=jnp.float32, serving={"attention_backend": backend,
                                    "scheduler": scheduler})
    rows, done = test_dense_latent_serving._served_rows(engine, prompts,
                                                        new=3)
    assert engine.leak_report() == {}
    dispatches = [d for r in engine.step_reports() for d in r["dispatches"]]
    return engine, rows, done, dispatches


@pytest.fixture(scope="module")
def served(kimi):
    """``served(policy, backend)``: the toy Kimi engine's run of the
    policy's prompts, once a module."""
    model, params = kimi
    runs = {}

    def get(policy, backend):
        if (policy, backend) not in runs:
            runs[policy, backend] = _serve(model, params, backend, **{
                # one to eight chunks of 16, most of them padded
                "chunked": dict(prompts=_prompts(
                    test_dense_latent_serving.PROMPTS), chunk=16),
                # 1,100 tokens pad to 1,536 and go as pieces of 1,024 and
                # 512; 700 are one bucket of 1,024, 90 one of 128
                "pieces": dict(prompts=_prompts((700, 90, 1100)),
                               max_seq=1600)}[policy])
        return runs[policy, backend]
    return get


@pytest.mark.parametrize("policy", ["chunked", "pieces"])
def test_the_kernel_serves_the_tokens_and_logits_of_the_xla_path(served,
                                                                 policy):
    _, want_rows, want, _ = served(policy, "jnp")
    engine, rows, done, dispatches = served(policy, "pallas-interpret")
    assert engine.latent_impl == "pallas" and engine.attention_impl == "jnp"
    assert done == want
    for rid in want_rows:
        np.testing.assert_allclose(
            rows[rid], want_rows[rid], rtol=0,
            atol=TOL["atol"] * np.abs(want_rows[rid]).max())
    prefills = [d for d in dispatches if d["phase"] == "prefill"]
    if policy == "pieces":
        assert sorted(d["tokens"] for d in prefills) == [128, 512, 1024,
                                                         1024]
        # a piece onto what the pieces before it wrote walks it
        assert max(d["ctx_entries"] for d in prefills) == 1024


@pytest.mark.parametrize("policy", ["chunked", "pieces"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_every_prefill_dispatch_says_what_read_the_pool(served, policy,
                                                        backend):
    engine, _, _, dispatches = served(policy, backend)
    want = "pallas" if backend == "pallas-interpret" else "jnp"
    assert engine.latent_impl == want
    for d in dispatches:
        if d["phase"] == "prefill":
            assert d["latent"] == want and d["experts"] == want
            # whole steps of the walk that read it, over what was cached
            cached = d["context"] - d["real"]
            step = engine._latent_walk_keys(d["tokens"])
            assert d["ctx_entries"] == -(-cached // step) * step
            assert d["context_keys"] == 5 * sum(range(cached + 1,
                                                      d["context"] + 1))
        else:
            assert "latent" not in d


def test_the_walk_the_host_reckons_is_the_kernels_own(kimi):
    model, params = kimi
    step = {}
    for backend in BACKENDS:
        engine = ServingEngine(
            model, params, max_batch=4, page_size=8, max_seq=160,
            dtype=jnp.float32, serving={"attention_backend": backend})
        step[backend] = engine._latent_walk_keys(16)
    pool = engine.caches.latent_pages
    assert step["jnp"] == la.PREFILL_BLOCK_K
    assert step["pallas-interpret"] == 8 * lp.pick_latent_tiles(
        16, 4, 24, 16, 32, pool.shape[-1], 8, engine.tables.shape[1],
        4).pages == 64


def test_a_selection_keeps_its_prefill_and_says_so():
    """A model with an indexer runs the XLA prefill whatever the backend:
    the same tokens, ``latent: "jnp"`` on every prefill dispatch."""
    model = CausalTransformerLM(test_latent_serving.config())
    params = model.init(jax.random.key(7), jnp.float32)
    prompts = _prompts((40, 9, 23))
    seen = {}
    for backend in BACKENDS:
        engine = ServingEngine(
            model, params, max_batch=4, page_size=8, max_seq=64,
            dtype=jnp.float32, serving={"attention_backend": backend})
        _, done = test_dense_latent_serving._served_rows(engine, prompts,
                                                         new=3)
        assert engine.latent_impl == "jnp"
        prefills = [d for r in engine.step_reports()
                    for d in r["dispatches"] if d["phase"] == "prefill"]
        assert prefills and all(d["latent"] == "jnp" for d in prefills)
        seen[backend] = done
    assert seen["jnp"] == seen["pallas-interpret"]


def test_a_model_without_latent_attention_says_nothing():
    model = CausalTransformerLM(TransformerConfig.tiny(
        hidden_size=64, n_heads=4, n_kv_heads=2, qk_norm="rms_flat",
        post_norm_only=True, activation="silu"))        # an OLMo-2 block
    engine = ServingEngine(
        model, model.init(jax.random.key(0), jnp.float32), max_batch=2,
        page_size=8, max_seq=64, dtype=jnp.float32,
        serving={"attention_backend": "pallas-interpret"})
    engine.generate([list(range(1, 20))], max_new_tokens=2)
    dispatches = [d for r in engine.step_reports() for d in r["dispatches"]]
    assert engine.latent_impl is None and engine.state_impl is None
    assert dispatches
    assert all("latent" not in d and "experts" not in d and "state" not in d
               and d["kv_write"] == "pallas" for d in dispatches)


def test_the_impl_fields_match_the_checker():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "scripts", "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("checker", path)
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    assert tuple(checker.DISPATCH_IMPLS) == tuple(serving.DISPATCH_IMPLS)
