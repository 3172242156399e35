"""Fused ragged paged-attention kernel vs the jnp gather oracle.

The Pallas kernel (``ops/pallas/ragged_paged_attention.py``) must be
bit-class equivalent (per-dtype tolerance) to ``paged_decode_attention``'s
jnp path on every ragged mix — decode-only, prefill-only, mixed — through
real ``PagedAllocator`` block tables including prefix-cache shared pages
and partial last pages.  On top of the kernel-level equivalence, the
serving engine's token streams must be BIT-IDENTICAL across
``attention_backend="jnp"`` and ``"pallas-interpret"`` — the backend is a
performance knob, never a quality knob.  All kernel runs use
``interpret=True`` (this suite is CPU tier-1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.paged_attention import (PagedAllocator, PagedKVCache,
                                               paged_decode_attention,
                                               resolve_attention_backend)
from deepspeed_tpu.ops.pallas import ragged_paged_attention as rpa
from deepspeed_tpu.ops.pallas.ragged_paged_attention import (
    VMEM_BUDGET, build_item_map, pick_tiles, ragged_paged_attention,
    ragged_paged_attention_rect, rect_grid_steps, rect_metadata)

H, HKV, D, PAGE = 4, 2, 8, 4
NPAGES = 64
TOL = dict(rtol=2e-5, atol=2e-5)


def _build_state(ctx_lens, shared_pages=0, seed=0):
    """A page pool + allocator-produced block tables for one ragged batch.

    ``shared_pages`` > 0 attaches that many leading pages of a holder
    sequence to EVERY request (``allocate(shared=...)`` — the prefix-cache
    admission path), so the kernel must read refcounted shared pages in
    place."""
    rng = np.random.default_rng(seed)
    alloc = PagedAllocator(NPAGES, PAGE, max_pages_per_seq=8,
                           reserve_scratch=True)
    shared = []
    if shared_pages:
        shared = alloc.allocate("__prefix__",
                                shared_pages * PAGE)[:shared_pages]
    for s, c in enumerate(ctx_lens):
        # a request can share at most its own FULL pages
        n_shared = min(shared_pages, max(0, (c - 1) // PAGE))
        alloc.allocate(s, c, shared=shared[:n_shared])
    tables = jnp.asarray(alloc.block_table(list(range(len(ctx_lens)))))
    kp = jnp.asarray(rng.standard_normal((NPAGES, HKV, PAGE, D)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NPAGES, HKV, PAGE, D)),
                     jnp.float32)
    return alloc, tables, kp, vp


def _ref(q_packed, q_lens, ctx_lens, kp, vp, tables):
    """Oracle: one rectangular jnp gather call per sequence."""
    cache = PagedKVCache(kp, vp)
    outs, off = [], 0
    for s, (ql, c) in enumerate(zip(q_lens, ctx_lens)):
        o = paged_decode_attention(
            q_packed[off:off + ql][None], cache, tables[s:s + 1],
            jnp.asarray([c], jnp.int32), impl="jnp")
        outs.append(o[0])
        off += ql
    return jnp.concatenate(outs, axis=0)


CASES = [
    ("decode_only", [1, 1, 1], [9, 4, 16]),
    ("prefill_only", [9, 5], [9, 5]),
    ("mixed", [6, 1, 3, 1], [6, 13, 7, 16]),
    ("length_one", [1], [1]),
    ("page_boundary", [4, 1], [8, 8]),       # ctx exactly fills pages
    ("partial_last_page", [5, 1], [5, 10]),  # ctx ends mid-page
]


@pytest.mark.parametrize("name,q_lens,ctx_lens",
                         CASES, ids=[c[0] for c in CASES])
def test_matches_jnp_oracle(name, q_lens, ctx_lens):
    _, tables, kp, vp = _build_state(ctx_lens)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((sum(q_lens), H, D)), jnp.float32)
    got = ragged_paged_attention(q, kp, vp, tables,
                                 jnp.asarray(ctx_lens, jnp.int32), q_lens,
                                 interpret=True)
    want = _ref(q, q_lens, ctx_lens, kp, vp, tables)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_prefix_cache_shared_pages_read_in_place():
    """Both requests' tables lead with the SAME physical pages (refcounted
    prefix attach); the kernel must produce the oracle's answer reading
    them in place — and the mix has a decode rider over the same pool."""
    q_lens, ctx_lens = [5, 1, 1], [13, 11, 9]
    alloc, tables, kp, vp = _build_state(ctx_lens, shared_pages=2)
    t = np.asarray(tables)
    assert t[0, 0] == t[1, 0] and t[0, 1] == t[1, 1]   # genuinely shared
    assert alloc.audit() == {}
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((sum(q_lens), H, D)), jnp.float32)
    got = ragged_paged_attention(q, kp, vp, tables,
                                 jnp.asarray(ctx_lens, jnp.int32), q_lens,
                                 interpret=True)
    want = _ref(q, q_lens, ctx_lens, kp, vp, tables)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("T", [1, 5, 8, 12])
def test_rect_front_end(T):
    """The rectangular wrapper (the jitted serving path's shape) must
    match the oracle for decode (T=1) and for prefills of a few rows,
    with the tile the picker chooses (all T rows of a sequence, both kv
    heads and every page of the table in one grid step at these sizes)."""
    B = 3
    ctx = [T + 3, T, T + 9]
    _, tables, kp, vp = _build_state(ctx)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    lengths = jnp.asarray(ctx, jnp.int32)
    got = ragged_paged_attention_rect(q, kp, vp, tables, lengths,
                                     interpret=True)
    want = paged_decode_attention(q, PagedKVCache(kp, vp), tables, lengths,
                                  impl="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("T", [1, 12], ids=["decode", "prefill"])
def test_stacked_pools_read_by_layer_index(T, layer):
    """What a serving dispatch calls: the stacked pools [L, P, Hkv, page,
    D] and a traced layer index give, to the bit, what the 4-D call gives
    on ``pool[layer]`` (the index maps pick the layer; the kernel body is
    the same), and the jnp gather out of the stack agrees."""
    ctx = [T + 3, T, T + 9]
    _, tables, kp, vp = _build_state(ctx)
    rng = np.random.default_rng(7)
    k_all, v_all = (jnp.asarray(rng.standard_normal((3,) + kp.shape),
                                jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((3, T, H, D)), jnp.float32)
    lengths = jnp.asarray(ctx, jnp.int32)
    want = ragged_paged_attention_rect(q, k_all[layer], v_all[layer], tables,
                                       lengths, interpret=True)
    stacked = jax.jit(lambda lay, impl: paged_decode_attention(
        q, PagedKVCache(k_all, v_all), tables, lengths, impl=impl,
        interpret=True, layer=lay), static_argnums=1)
    np.testing.assert_array_equal(
        np.asarray(stacked(jnp.int32(layer), "pallas")), np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(stacked(jnp.int32(layer), "jnp")), np.asarray(want),
        **TOL)


@pytest.mark.parametrize("q_tile", [3, 8])
def test_rect_front_end_explicit_q_tile(q_tile):
    """``q_tile=`` overrides the picker: T=12 splits into several tiles
    of one sequence, the last one padded (12 = 4 x 3 = 8 + 4)."""
    T, ctx = 12, [15, 12, 21]
    _, tables, kp, vp = _build_state(ctx)
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((3, T, H, D)), jnp.float32)
    lengths = jnp.asarray(ctx, jnp.int32)
    got = ragged_paged_attention_rect(q, kp, vp, tables, lengths,
                                     q_tile=q_tile, interpret=True)
    want = paged_decode_attention(q, PagedKVCache(kp, vp), tables, lengths,
                                  impl="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


# the shapes the serving path really makes: T=1 decode, the speculative
# verify window (a few rows), chunked prefill (T = chunk over a cached
# context); MHA and GQA; the tests' page of 8 and the default of 128; both
# head sizes; bf16 (the served models) and float32 (the tier-1 tiny ones)
SERVING_SHAPES = [
    # T, group, page, D, cache dtype
    (1, 1, 128, 128, jnp.bfloat16),
    (1, 4, 8, 64, jnp.float32),
    (1, 1, 8, 128, jnp.float32),
    (1, 4, 128, 64, jnp.bfloat16),
    (4, 1, 8, 64, jnp.bfloat16),
    (4, 4, 128, 128, jnp.float32),
    (4, 1, 128, 64, jnp.float32),
    (4, 4, 8, 128, jnp.bfloat16),
    (12, 1, 128, 128, jnp.float32),
    (12, 4, 8, 64, jnp.bfloat16),
    (12, 1, 8, 128, jnp.bfloat16),
    (12, 4, 128, 64, jnp.float32),
    (64, 1, 8, 64, jnp.float32),
    (64, 4, 128, 128, jnp.bfloat16),
    (64, 1, 128, 64, jnp.bfloat16),
    (64, 4, 8, 128, jnp.float32),
]


@pytest.mark.parametrize(
    "T,group,page,d,dtype", SERVING_SHAPES,
    ids=[f"T{t}-g{g}-p{p}-d{d}-{jnp.dtype(dt).name}"
         for t, g, p, d, dt in SERVING_SHAPES])
def test_serving_shapes_match_oracle(T, group, page, d, dtype):
    """One batch of four slots per shape, through the entry point the
    jitted step calls: slot 0's context ends mid-page over a cached
    prefix, slot 1 fills its last page exactly, slot 2 shares slot 0's
    first pages (a prefix-cache attach), slot 3 is inactive (length 0,
    its table row all scratch page 0).  Held to the float32 oracle: the
    float32 cache to rounding, the bf16 cache to ``chip_smoke.py``'s
    kernel tolerance."""
    hkv = 2
    ctx = [T + page + 3, -(-(T + 5) // page) * page, T + 2 * page + 1, T]
    width = max(-(-c // page) for c in ctx) + 1    # + the overrun column
    tables = np.zeros((4, width), np.int32)
    next_page = 1
    for s in range(3):
        n = -(-ctx[s] // page)
        tables[s, :n] = np.arange(next_page, next_page + n)
        next_page += n
    tables[2, :1] = tables[0, :1]                  # shared, not copied
    rng = np.random.default_rng(7)
    pool = PagedKVCache(*(jnp.asarray(
        rng.standard_normal((next_page, hkv, page, d)), dtype)
        for _ in range(2)))
    q = jnp.asarray(rng.standard_normal((4, T, hkv * group, d)), dtype)
    tables, lengths = jnp.asarray(tables), jnp.asarray(ctx, jnp.int32)
    got = paged_decode_attention(q, pool, tables, lengths, impl="pallas",
                                 interpret=True)
    assert got.dtype == q.dtype
    f32 = lambda t: jax.tree_util.tree_map(      # noqa: E731
        lambda x: x.astype(jnp.float32), t)
    want = np.asarray(paged_decode_attention(f32(q), f32(pool), tables,
                                             lengths, impl="jnp"))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    err = np.max(np.abs(np.asarray(got, np.float32) - want)) \
        / max(1.0, np.max(np.abs(want)))
    assert err <= tol, err


# -- the tile picker ---------------------------------------------------------

# B, T, group, Hkv, page, D, table width, itemsize
PICKER_SHAPES = [
    ("chat_decode", 32, 1, 1, 16, 128, 128, 33, 2),
    ("docbatch_decode", 16, 1, 1, 16, 128, 128, 33, 2),
    ("docbatch_prefill", 1, 4096, 1, 16, 128, 128, 33, 2),
    ("chat_prefill", 1, 256, 1, 16, 128, 128, 33, 2),
    ("spec_verify", 32, 5, 1, 16, 128, 128, 33, 2),
    ("gqa_decode_page16", 8, 1, 4, 4, 16, 128, 129, 2),
    ("gqa_prefill_page16", 1, 128, 4, 4, 16, 128, 129, 2),
    ("tiny_float32_page8", 4, 1, 2, 2, 8, 16, 9, 4),
    ("bf16_page8", 8, 1, 4, 4, 8, 128, 65, 2),
    ("d64_prefill", 1, 512, 1, 12, 128, 64, 17, 2),
]


@pytest.mark.parametrize("name,B,T,group,hkv,page,d,width,itemsize",
                         PICKER_SHAPES, ids=[c[0] for c in PICKER_SHAPES])
def test_pick_tiles(name, B, T, group, hkv, page, d, width, itemsize):
    """The choice is a pure function of the call's shapes: it fits the
    VMEM budget it states, whole kv heads divide evenly, the grid covers
    every query row and every table column, a page that does not fill a
    sublane tile of its dtype rides alone — and for the two serving
    cells' shapes the grid is at least 16 times smaller than the fixed
    8-row x 1-head x 1-page step's."""
    tiles = pick_tiles([T] * B, group, hkv, page, d, width, itemsize)
    assert tiles == pick_tiles([T] * B, group, hkv, page, d, width, itemsize)
    assert tiles.vmem_bytes <= VMEM_BUDGET
    assert 1 <= tiles.q_tile <= T and hkv % tiles.heads == 0
    n_qt = -(-T // tiles.q_tile)
    assert tiles.grid == (B * n_qt, hkv // tiles.heads,
                          -(-width // tiles.pages))
    assert tiles.grid_steps == tiles.grid[0] * tiles.grid[1] * tiles.grid[2]
    assert tiles.grid[2] * tiles.pages >= width
    if page % (8 * 4 // itemsize):
        assert tiles.pages == 1
    if T == 1:
        assert tiles.heads == hkv                  # a whole page a step
    fixed_step_grid = B * -(-T // min(8, T)) * hkv * width
    if name in ("chat_decode", "docbatch_prefill"):
        assert tiles.grid_steps * 16 <= fixed_step_grid
    else:
        assert tiles.grid_steps <= fixed_step_grid


def test_pick_tiles_explicit_q_tile_and_mixed_lengths():
    tiles = pick_tiles([256, 1], 1, 2, 128, 64, 17, 2, q_tile=8)
    assert tiles.q_tile == 8 and tiles.grid[0] == 32 + 1
    # the longest sequence picks the tile; every sequence pads to it
    tiles = pick_tiles([256, 1], 1, 2, 128, 64, 17, 2)
    assert tiles.q_tile == 256 and tiles.grid[0] == 2


# -- the item map: the grid follows the contexts ------------------------------

# name, T, group, Hkv, page, table width, contexts (the T new tokens
# included; a slot whose context is T is idle: its table row is all scratch
# page), q_tile override.  Pages of 8 and 32 float32 rows ride 8 a step.
ITEM_CASES = [
    ("every_slot_idle", 1, 1, 2, 8, 33, [1] * 32, None),
    ("one_live_slot_of_32", 1, 1, 2, 8, 33, [1] * 5 + [150] + [1] * 26, None),
    # exactly one step of 64 keys, one step plus one token, the whole
    # table, an idle slot between them
    ("step_edges", 1, 1, 2, 8, 33, [64, 1, 65, 264], None),
    # a 192-token chunk over a 100-token cached prefix: its three q tiles'
    # frontiers are 164, 228 and 292 keys
    ("prefill_cached_prefix", 192, 1, 2, 8, 40, [292], 64),
    ("gqa_decode", 1, 4, 2, 8, 33, [1, 70, 130, 64], None),
    ("gqa_verify_window", 5, 2, 2, 8, 33, [5, 69, 200], None),
    # 600 rows of one head a tile: two kv-head blocks sweep the items
    ("two_head_blocks", 600, 1, 2, 32, 33, [700], None),
]
ITEM_IDS = [c[0] for c in ITEM_CASES]


def _item_case(T, group, hkv, page, width, ctx, q_tile, d=16):
    B = len(ctx)
    tiles = pick_tiles([T] * B, group, hkv, page, d, width, 4, q_tile)
    sot, qot = rect_metadata(B, T, tiles.q_tile)
    return B, tiles, sot, qot


def _want_items(ctx, T, sot, qot, tiles, page):
    """The (tile, step) pairs with ``step * keys < kv_hi``, and step 0 of
    a tile that has none, in tile order with steps ascending."""
    keys = tiles.pages * page
    want = []
    for t, (s, qt) in enumerate(zip(sot, qot)):
        kv_hi = ctx[s] - T + min(T, (qt + 1) * tiles.q_tile)
        live = [(t, i) for i in range(tiles.grid[2]) if i * keys < kv_hi]
        want += live or [(t, 0)]
    return want


@pytest.mark.parametrize("name,T,group,hkv,page,width,ctx,q_tile",
                         ITEM_CASES, ids=ITEM_IDS)
def test_item_map(name, T, group, hkv, page, width, ctx, q_tile):
    """The map is a pure function of the contexts: exactly the pairs that
    hold keys, one item for a tile that holds none, tiles in order and
    steps ascending; the host's count and the search of the running sum
    (what an engine too large for the table runs) agree with it."""
    B, tiles, sot, qot = _item_case(T, group, hkv, page, width, ctx, q_tile)
    want = _want_items(ctx, T, sot, qot, tiles, page)
    items = build_item_map(jnp.asarray(ctx, jnp.int32),
                           jnp.full((B,), T, jnp.int32), sot, qot, tiles,
                           page)
    first, toi = np.asarray(items.first), np.asarray(items.tile_of_item)
    n = int(first[-1])
    assert first[0] == 0 and n == len(want)
    assert toi.shape == (tiles.grid[0] * tiles.grid[2],) and tiles.item_table
    assert [(int(t), int(i - first[t])) for i, t in enumerate(toi[:n])] \
        == want
    assert np.all(toi[n:] == tiles.grid[0] - 1)      # in range past the end
    assert rect_grid_steps(tiles, B, T, np.asarray(ctx), page) \
        == n * tiles.grid[1]
    searched = items._replace(tile_of_item=jnp.zeros(1, jnp.int32))
    assert [tuple(map(int, rpa._locate(jnp.int32(i), *searched,
                                       tiles.grid[0], False)))
            for i in range(n)] == want


@pytest.mark.parametrize("name,T,group,hkv,page,width,ctx,q_tile",
                         ITEM_CASES, ids=ITEM_IDS)
def test_item_map_kernel_matches_oracle(name, T, group, hkv, page, width, ctx,
                                        q_tile):
    """The kernel over each map against the jnp gather, idle slots beside
    live ones: every row equal (an idle slot attends its own scratch-page
    row in both) and finite — no output block is left unwritten."""
    B, d = len(ctx), 16
    tables = np.zeros((B, width), np.int32)
    next_page = 1
    for s, c in enumerate(ctx):
        if c > T:
            n = -(-c // page)
            tables[s, :n] = np.arange(next_page, next_page + n)
            next_page += n
    rng = np.random.default_rng(11)
    kp, vp = (jnp.asarray(rng.standard_normal((next_page, hkv, page, d)),
                          jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, T, hkv * group, d)), jnp.float32)
    tables, lengths = jnp.asarray(tables), jnp.asarray(ctx, jnp.int32)
    got = np.asarray(jax.jit(lambda *a: ragged_paged_attention_rect(
        *a, q_tile=q_tile, interpret=True))(q, kp, vp, tables, lengths))
    want = paged_decode_attention(q, PagedKVCache(kp, vp), tables, lengths,
                                  impl="jnp")
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, np.asarray(want), **TOL)


def test_item_search_kernel_matches_table(monkeypatch):
    """An engine whose (tile, step) rectangle would not fit scalar memory
    keeps the running sum alone and searches it in the index maps: the
    same items, so the same output to the bit."""
    _, T, group, hkv, page, width, ctx, _ = ITEM_CASES[ITEM_IDS.index(
        "step_edges")]
    rng = np.random.default_rng(12)
    tables = jnp.asarray(rng.integers(1, 40, (len(ctx), width)), jnp.int32)
    kp, vp = (jnp.asarray(rng.standard_normal((40, hkv, page, 16)),
                          jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((len(ctx), T, hkv * group, 16)),
                    jnp.float32)
    lengths = jnp.asarray(ctx, jnp.int32)

    def run():
        return np.asarray(ragged_paged_attention_rect(
            q, kp, vp, tables, lengths, interpret=True))

    table = run()
    monkeypatch.setattr(rpa, "ITEM_TABLE_MAX", 0)
    assert not pick_tiles([T] * len(ctx), group, hkv, page, 16, width,
                          4).item_table
    np.testing.assert_array_equal(run(), table)


def test_backend_selected_entry_point():
    """What ``resolve_attention_backend`` makes of "pallas-interpret"
    routes ``paged_decode_attention`` through the ragged kernel, and
    agrees with what it makes of "jnp"."""
    ctx = [7, 12]
    _, tables, kp, vp = _build_state(ctx)
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((2, 1, H, D)), jnp.float32)
    cache = PagedKVCache(kp, vp)
    lengths = jnp.asarray(ctx, jnp.int32)
    a, b = (paged_decode_attention(q, cache, tables, lengths, impl=impl,
                                   interpret=interpret)
            for impl, interpret in map(resolve_attention_backend,
                                       ("jnp", "pallas-interpret")))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)


def test_resolve_attention_backend():
    assert resolve_attention_backend(None) == (None, False)
    assert resolve_attention_backend("auto") == (None, False)
    assert resolve_attention_backend("jnp") == ("jnp", False)
    assert resolve_attention_backend("pallas") == ("pallas", False)
    assert resolve_attention_backend("pallas-interpret") == ("pallas", True)
    with pytest.raises(ValueError):
        resolve_attention_backend("cuda")


# -- serving end-to-end ----------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def test_serving_bit_identical_across_backends(tiny):
    """The whole engine — bucketed prefill, batched decode, sampling —
    must emit bit-identical token streams under the jnp gather path and
    the interpret-mode ragged kernel, with a clean leak report."""
    from deepspeed_tpu.inference.serving import ServingEngine
    cfg, model, params = tiny
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).tolist()
               for n in (5, 9, 3)]

    def run(backend):
        eng = ServingEngine(model, params, max_batch=4, page_size=8,
                            max_seq=64, dtype=jnp.float32,
                            serving={"attention_backend": backend})
        assert eng.attention_backend == backend
        out = eng.generate(prompts, max_new_tokens=4)
        assert eng.leak_report() == {}
        return out

    assert run("jnp") == run("pallas-interpret")


def test_kernel_grid_in_step_report(tiny):
    """Every dispatch of ``engine.last_step`` carries ``kernel_grid``, the
    grid steps its ragged kernel RUNS over all layers (the steps that hold
    a key of a decoding slot, and one for each idle slot), beside
    ``kernel_grid_full``, the rectangle the tile picker chose for the
    compiled shape; both 0 where the jnp path serves."""
    from deepspeed_tpu.inference.serving import ServingEngine
    cfg, model, params = tiny
    page = 8

    def dispatches(backend):
        eng = ServingEngine(model, params, max_batch=8, page_size=page,
                            max_seq=256, dtype=jnp.float32,
                            serving={"attention_backend": backend})
        eng.add_request("r0", list(range(1, 100)), max_new_tokens=4)
        eng.add_request("r1", list(range(1, 10)), max_new_tokens=4)
        while eng.queue or eng.n_active:
            eng.step()
        return eng, [d for rep in eng.step_reports()
                     for d in rep["dispatches"]]

    eng, got = dispatches("pallas-interpret")
    assert {d["phase"] for d in got} == {"prefill", "decode"}
    width = eng.tables.shape[1]
    for d in got:
        tiles = pick_tiles([d["tokens"]] * d["batch"],
                           cfg.n_heads // cfg.kv_heads, cfg.kv_heads, page,
                           cfg.head_dim, width, 4)
        assert d["kernel_grid_full"] == tiles.grid_steps * cfg.n_layers > 0
        keys = tiles.pages * page
        if d["phase"] == "decode":
            # 2 of 8 slots decode: their contexts' steps, one an idle slot
            assert len(d["contexts"]) == 2
            run = sum(-(-c // keys) for c in d["contexts"]) \
                + d["batch"] - len(d["contexts"])
            assert d["kernel_grid"] == run * tiles.grid[1] * cfg.n_layers
            assert 0 < d["kernel_grid"] < d["kernel_grid_full"]
        else:
            assert 0 < d["kernel_grid"] <= d["kernel_grid_full"]
    assert any(max(d["contexts"]) > keys for d in got
               if d["phase"] == "decode")       # a slot of several steps
    _, got = dispatches("jnp")
    assert got and all(d["kernel_grid"] == d["kernel_grid_full"] == 0
                       for d in got)


def test_bad_backend_rejected_at_construction(tiny):
    from deepspeed_tpu.inference.serving import ServingEngine
    cfg, model, params = tiny
    with pytest.raises(ValueError, match="attention_backend"):
        ServingEngine(model, params, max_batch=1, page_size=8, max_seq=64,
                      dtype=jnp.float32,
                      serving={"attention_backend": "cuda"})


def test_reservation_trimmed_and_audited(tiny):
    """Admission must trim the bucketed-prefill over-allocation to the
    request's true page need (``_trim_reservation``), and
    ``leak_report()`` must flag any active slot whose reservation drifts
    from it."""
    from deepspeed_tpu.inference.serving import ServingEngine
    cfg, model, params = tiny
    eng = ServingEngine(model, params, max_batch=1, page_size=4,
                        max_seq=32, dtype=jnp.float32)
    # prompt 9 + budget 2 = 11 tokens -> 3 pages; the prefill bucket pads
    # to 16 tokens -> 4 pages reserved, so admission MUST return one
    prompt = list(range(1, 10))
    eng.add_request("r0", prompt, max_new_tokens=2)
    eng.step()
    assert eng.slots[0] is not None and eng.slots[0].req_id == "r0"
    assert len(eng.alloc.seq_pages["r0"]) == 3
    assert eng.leak_report() == {}
    # force a drifted reservation: the audit must name the slot
    eng.alloc.extend("r0", 16)
    leaks = eng.leak_report()
    assert "over_reserved_slots" in leaks
    assert leaks["over_reserved_slots"]["r0"]["held"] == 4
    eng.alloc.shrink("r0", 11)
    assert eng.leak_report() == {}


# ---------------------------------------------------------------------------
# a sliding window over the keys, and a block table that is a ring
# ---------------------------------------------------------------------------
def _windowed_case(window, ring_on, T, ctx, group, page=8, hkv=2, d=16):
    """A pool (layer 1 of a stack of 2) that holds each sequence's keys
    where its table says, a row of a ring holding the newest position
    congruent to it; the T newest rows of each sequence as queries:
    (q, keys, values, pool, tables, ring)."""
    from deepspeed_tpu.ops.paged_attention import ring_pages
    rng = np.random.default_rng(0)
    ctx = np.asarray(ctx)
    B, longest = len(ctx), int(ctx.max())
    ring = ring_pages(window, page) if ring_on else None
    width = ring or -(-longest // page) + 1
    tables = 1 + np.arange(B * width, dtype=np.int32).reshape(B, width)
    keys = rng.standard_normal((B, longest, hkv, d)).astype(np.float32)
    values = rng.standard_normal((B, longest, hkv, d)).astype(np.float32)
    # what nobody wrote is noise, not zeros: a key read from a row that is
    # not inside the window must show
    k_pool = rng.standard_normal((2, 1 + B * width, hkv, page, d))
    v_pool = rng.standard_normal((2, 1 + B * width, hkv, page, d))
    for b in range(B):
        for t in range(ctx[b]):
            col = t // page % ring if ring else t // page
            k_pool[1, tables[b, col], :, t % page] = keys[b, t]
            v_pool[1, tables[b, col], :, t % page] = values[b, t]
    pool = PagedKVCache(jnp.asarray(k_pool, jnp.float32),
                        jnp.asarray(v_pool, jnp.float32))
    q = rng.standard_normal((B, T, hkv * group, d)).astype(np.float32)
    return q, keys, values, pool, jnp.asarray(tables), ring


def _brute_force(q, keys, values, ctx, window, group):
    """Dense attention of each query over the keys its window holds."""
    want = np.zeros_like(q)
    B, T = q.shape[:2]
    for b in range(B):
        for t in range(T):
            i = ctx[b] - T + t
            lo = max(0, i - window + 1) if window else 0
            kk = np.repeat(keys[b, lo:i + 1], group, axis=1)
            vv = np.repeat(values[b, lo:i + 1], group, axis=1)
            s = np.einsum("hd,khd->hk", q[b, t], kk) / np.sqrt(q.shape[-1])
            p = np.exp(s - s.max(-1, keepdims=True))
            want[b, t] = np.einsum("hk,khd->hd",
                                   p / p.sum(-1, keepdims=True), vv)
    return want


WINDOW_CASES = [
    # name, window, ring, T, contexts, group
    ("no_window_group8", None, False, 1, [5, 17, 40], 8),
    ("window_beyond_context", 64, False, 1, [5, 24, 40], 1),
    ("window_inside_decode", 16, False, 1, [5, 17, 40], 8),
    ("window_inside_prefill", 16, False, 12, [12, 24, 70], 2),
    ("window_inside_prefill_group8", 16, False, 40, [40, 64], 8),
    ("ring_decode_group8", 16, True, 1, [5, 17, 70], 8),
    ("ring_decode_wrapped_twice", 16, True, 1, [49, 24, 73], 1),
    ("ring_beyond_context", 64, True, 1, [5, 24, 70], 1),
    ("ring_chunk_of_four", 16, True, 4, [5, 24, 70], 2),
    ("ring_chunk_at_its_limit", 16, True, 9, [9, 33, 70], 1),
]


@pytest.mark.parametrize("name,window,ring_on,T,ctx,group", WINDOW_CASES,
                         ids=[c[0] for c in WINDOW_CASES])
def test_window_and_ring_match_oracle_and_brute_force(name, window, ring_on,
                                                      T, ctx, group):
    q, keys, values, pool, tables, ring = _windowed_case(
        window, ring_on, T, ctx, group)
    want = _brute_force(q, keys, values, ctx, window, group)
    for impl in ("jnp", "pallas"):
        got = paged_decode_attention(
            jnp.asarray(q), pool, tables, jnp.asarray(ctx),
            impl=impl, interpret=True, layer=1, window=window, ring=ring)
        np.testing.assert_allclose(got, want, **TOL, err_msg=impl)


def test_window_runs_only_the_steps_its_rows_can_see():
    """The item map of a windowed call starts at the first page that
    holds a key inside the window: a decode step over a context of 40
    pages of 8 under a window of 16 runs the one step of 8 pages that
    holds its 16 keys, not five."""
    ctx = np.array([320, 9, 100])
    tiles = pick_tiles([1] * 3, 8, 1, 8, 16, 41, 4, window=16)
    whole = pick_tiles([1] * 3, 8, 1, 8, 16, 41, 4)
    assert tiles.pages == whole.pages == 8 and whole.grid[2] == 6
    assert tiles.grid[2] == 2           # (16 + 1 - 2) // 64 + 2
    assert rect_grid_steps(whole, 3, 1, ctx, 8) == 5 + 1 + 2
    assert rect_grid_steps(tiles, 3, 1, ctx, 8, window=16) == 1 + 1 + 1
    # a window that straddles two steps runs both
    assert rect_grid_steps(tiles, 3, 1, np.array([70, 64, 65]), 8,
                           window=16) == 2 + 1 + 2
    ring = pick_tiles([1] * 3, 8, 1, 8, 16, 3, 4, window=16, ring=3)
    assert ring.pages == 3 and ring.grid[2] == 2
    # the widths the benchmark's window model runs (PERF.md section 4):
    # group 8 on 4 kv heads of 128, pages of 128, bf16
    decode = pick_tiles([1] * 16, 8, 4, 128, 128, 17, 2, window=2048, ring=17)
    assert (decode.q_tile, decode.heads, decode.pages) == (1, 4, 8)
    assert decode.grid == (16, 1, 3)
    prefill = pick_tiles([16384], 8, 4, 128, 128, 128, 2, window=2048)
    assert (prefill.q_tile, prefill.heads, prefill.pages) == (128, 1, 8)
    assert prefill.grid == (128, 4, 4)


@pytest.mark.parametrize("T,start", [(1, 23), (5, 21), (9, 20), (24, 0)])
def test_paged_kv_write_wraps_around_the_ring(T, start):
    """Rows written through a ring of 3 pages of 8 land at their position
    modulo 24 and touch no other byte; the jnp scatter leaves the same."""
    from deepspeed_tpu.ops.paged_attention import write_paged
    rng = np.random.default_rng(T)
    pool = jnp.asarray(rng.standard_normal((2, 7, 2, 8, 16)), jnp.float32)
    tables = jnp.asarray([[4, 2, 6], [1, 5, 3]], jnp.int32)
    new_k = jnp.asarray(rng.standard_normal((2, T, 2, 16)), jnp.float32)
    new_v = jnp.asarray(rng.standard_normal((2, T, 2, 16)), jnp.float32)
    starts = jnp.asarray([start, start + 48], jnp.int32)
    k, v = rpa.paged_kv_write(pool, pool + 1, 1, tables, starts, new_k,
                              new_v, interpret=True, ring=3)
    want_k, want_v = np.array(pool), np.array(pool + 1)
    for b in range(2):
        for t in range(T):
            at = (int(starts[b]) + t) % 24
            want_k[1, tables[b, at // 8], :, at % 8] = new_k[b, t]
            want_v[1, tables[b, at // 8], :, at % 8] = new_v[b, t]
    np.testing.assert_array_equal(k, want_k)
    np.testing.assert_array_equal(v, want_v)
    scattered = write_paged(PagedKVCache(pool, pool + 1), 1, tables, starts,
                            new_k, new_v, impl="jnp", ring=3)
    np.testing.assert_array_equal(scattered.k_pages, want_k)
    np.testing.assert_array_equal(scattered.v_pages, want_v)
