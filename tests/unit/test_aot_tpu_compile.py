"""The main path's kernels through the TPU's own compiler, without a chip.

The compiler is installed here and compiles for a chip that is described and
not attached (``/opt/skills/guides/on-chip-measurement`` §2, rehearsal 3):
Mosaic refuses here what it would refuse there — a slice off the tiling, too
much VMEM, a kernel XLA is asked to partition — which interpret mode never
sees.  Nothing runs, so these say nothing about results or times; the chip
run is ``chip_smoke.py``.  Each case asserts the kernel is in the compiled
text as a ``tpu_custom_call``.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or it logs under /tmp
# compile-only: no chip to contend for, so two test processes may load libtpu
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from deepspeed_tpu.benchmarks.training import MODELS
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.ops.attention import attention
from deepspeed_tpu.ops.paged_attention import (PagedKVCache,
                                               paged_decode_attention)
from deepspeed_tpu.parallel.topology import MESH_AXES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here: nothing to compile against
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(scope="module", autouse=True)
def _no_compilation_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _on(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("name,shape,kv_heads,window", [
    ("mha", (2, 1024, 16, 128), 16, None),
    ("gqa_16_4", (2, 1024, 16, 128), 4, None),
    ("window", (2, 1024, 16, 128), 16, 256),
    ("d64", (2, 1024, 12, 64), 12, None),
    # PR 43: 8,192 positions, where the dk/dv kernel holds a head's whole
    # q and dO (its blocks and temporaries are 15.5 of Mosaic's default
    # 16 MiB of scoped VMEM by ``_vmem_bytes``; past it the call asks for
    # its own limit, ``_vmem_params``)
    ("gqa_32_4_s8192_window", (1, 8192, 32, 128), 4, 1024),
    # PR 49: the three training cells' own calls, under the tiles the
    # picker gives them
    ("train-pythia-1.4b-s2048", (2, 2048, 16, 128), 16, None),
    ("train-pythia-6.9b-fsdp4", (1, 2048, 32, 128), 32, None),
    ("train-mellum2-12b-ep4-s8192_full", (1, 8192, 32, 128), 4, None),
    # twice that: the first length whose calls ask for a VMEM limit of
    # their own (22.75 MB by ``_vmem_bytes``)
    ("gqa_4_1_s16384", (1, 16384, 4, 128), 1, None),
])
def test_flash_forward_backward(topo, name, shape, kv_heads, window):
    """Forward and backward are exactly three kernels, each once, by name:
    what the benchmark's cost files count a layer and micro-batch
    (``chipbench/costs/flash_attention_train.py`` divides EVERY Pallas call
    of a step by layers and micro-batches)."""
    chip = SingleDeviceSharding(topo.devices[0])
    B, S, _, D = shape

    def loss(q, k, v):
        out = attention(q, k, v, causal=True, impl="pallas", window=window)
        return out.astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                          _on(chip, shape), _on(chip, (B, S, kv_heads, D)),
                          _on(chip, (B, S, kv_heads, D)))
    assert text.count("tpu_custom_call") == 3
    for kernel in ("fwd", "dq", "dkv"):
        assert len(re.findall(
            rf"%flash_attention_{kernel}[.\d]* = ", text)) == 1, kernel


def test_flash_on_a_four_chip_mesh(topo):
    """XLA cannot partition a Mosaic kernel; on a mesh ``attention`` runs
    it per shard (the fsdp=4 GPT-1B step died here before that)."""
    shape = tuple(4 if a == "fsdp" else 1 for a in MESH_AXES)
    mesh = Mesh(np.asarray(topo.devices).reshape(shape), MESH_AXES)
    x = _on(NamedSharding(mesh, P("fsdp")), (8, 1024, 16, 128))
    with mesh:
        _compiled_text(
            lambda q, k, v: attention(q, k, v, causal=True, impl="pallas"),
            x, x, x)


# name, batch, q_len, heads, kv_heads, page_size, table width: the smoke
# server's GQA widths at both page sizes, then the benchmark's serving
# cells as the engine dispatches them (OLMo-2 1B: MHA 16 x 128, page 128,
# ``max_seq`` 4096 -> 32 pages + the overrun column; chat decodes 32 slots,
# docbatch 16; prefill buckets from 64 to 4096 tokens), the speculative
# verify window, and a GQA prefill long enough for several q tiles
RAGGED_SHAPES = [
    ("decode_p16", 8, 1, 16, 4, 16, 128),
    ("prefill_p16", 1, 128, 16, 4, 16, 128),
    ("decode_p128", 8, 1, 16, 4, 128, 16),
    ("prefill_p128", 1, 128, 16, 4, 128, 16),
    ("chat_decode_b32", 32, 1, 16, 16, 128, 33),
    ("docbatch_decode_b16", 16, 1, 16, 16, 128, 33),
    ("prefill_64", 1, 64, 16, 16, 128, 33),
    ("prefill_1024", 1, 1024, 16, 16, 128, 33),
    ("prefill_4096", 1, 4096, 16, 16, 128, 33),
    ("spec_verify_b32", 32, 5, 16, 16, 128, 33),
    ("gqa_prefill_2048", 1, 2048, 32, 8, 128, 33),
]


@pytest.mark.parametrize("name,batch,q_len,heads,kv_heads,page_size,width",
                         RAGGED_SHAPES, ids=[c[0] for c in RAGGED_SHAPES])
def test_ragged_paged_attention(topo, name, batch, q_len, heads, kv_heads,
                                page_size, width):
    """The tile the picker chooses for each shape goes through Mosaic: a
    lowering failure of a picked tile fails here, not on the chip."""
    chip = SingleDeviceSharding(topo.devices[0])
    head_dim = 128
    pool = _on(chip, (batch * (width - 1) + 1, kv_heads, page_size,
                      head_dim))
    text = _compiled_text(
        lambda q, k, v, tables, lengths: paged_decode_attention(
            q, PagedKVCache(k, v), tables, lengths, impl="pallas"),
        _on(chip, (batch, q_len, heads, head_dim)), pool, pool,
        _on(chip, (batch, width), jnp.int32),
        _on(chip, (batch,), jnp.int32))
    assert ("ragged_paged_attention_decode" if q_len == 1
            else "ragged_paged_attention_prefill") in text


# name, batch, q_len, table width, window, ring: Trinity-Mini's shapes as
# the engine dispatches them (32 query heads on 4 kv heads of 128: group 8;
# page 128; ``max_seq`` 16,384 -> 128 pages + the overrun column; a window
# of 2,048 in a ring of 17 pages).  A window layer's decode step reads
# through the ring; its prefill reads the bucket's own rows as pages under
# the window; a full layer reads the growing table
WINDOW_SHAPES = [
    ("ring_decode_b16", 16, 1, 17, 2048, 17),
    ("full_decode_b16", 16, 1, 129, None, None),
    ("window_prefill_512", 1, 512, 4, 2048, None),
    ("window_prefill_16384", 1, 16384, 128, 2048, None),
    ("full_prefill_16384", 1, 16384, 129, None, None),
]


@pytest.mark.parametrize("name,batch,q_len,width,window,ring", WINDOW_SHAPES,
                         ids=[c[0] for c in WINDOW_SHAPES])
def test_ragged_window_and_ring(topo, name, batch, q_len, width, window,
                                ring):
    """Group 8 under a window and through a ring goes through Mosaic at
    the tiles the picker chooses, and so does the ring's wrapping write."""
    from deepspeed_tpu.ops.paged_attention import write_paged
    chip = SingleDeviceSharding(topo.devices[0])
    heads, kv_heads, page_size, head_dim = 32, 4, 128, 128
    pool = _on(chip, (3, batch * width + 1, kv_heads, page_size, head_dim))
    rows = _on(chip, (batch, q_len, kv_heads, head_dim))

    def step(q, k_new, v_new, k, v, tables, lengths):
        cache = PagedKVCache(k, v)
        if ring:
            cache = write_paged(cache, 1, tables, lengths, k_new, v_new,
                                impl="pallas", ring=ring)
        return paged_decode_attention(
            q, cache, tables, lengths + q_len, impl="pallas", layer=1,
            window=window, ring=ring), cache

    text = _compiled_text(
        step, _on(chip, (batch, q_len, heads, head_dim)), rows, rows, pool,
        pool, _on(chip, (batch, width), jnp.int32),
        _on(chip, (batch,), jnp.int32))
    assert ("ragged_paged_attention_decode" if q_len == 1
            else "ragged_paged_attention_prefill") in text
    assert ("paged_kv_write" in text) == bool(ring)


def test_ragged_item_search_compiles(topo, monkeypatch):
    """An engine whose (q tile, kv step) rectangle is past
    ``ITEM_TABLE_MAX`` searches the running sum of the tiles' steps inside
    the index maps: that loop goes through Mosaic too (the chat cell's
    decode shape, the threshold lowered to reach the path)."""
    from deepspeed_tpu.ops.pallas import ragged_paged_attention as rpa
    monkeypatch.setattr(rpa, "ITEM_TABLE_MAX", 0)
    test_ragged_paged_attention(topo, *next(
        c for c in RAGGED_SHAPES if c[0] == "chat_decode_b32"))


def test_paged_decode_step_llama_1b(topo):
    """One whole ``apply_with_paged_cache`` decode step at the widths
    ``chip_smoke.py`` serves."""
    chip = SingleDeviceSharding(topo.devices[0])
    model = CausalTransformerLM(TransformerConfig(
        vocab_size=32000, max_seq_len=2048, **MODELS["llama_1b"]))
    batch, page_size, max_pages = 8, 16, 128

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _on(chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.bfloat16)))
    caches = on_chip(jax.eval_shape(
        lambda: model.init_paged_caches(batch * max_pages + 1, page_size)))
    _compiled_text(
        lambda *a: model.apply_with_paged_cache(*a, attn_backend="pallas"),
        params, _on(chip, (batch, 1), jnp.int32), caches,
        _on(chip, (batch, max_pages + 1), jnp.int32),
        _on(chip, (batch,), jnp.int32))


# -- a serving dispatch holds the page pools as ONE buffer -------------------
# OLMo-2 1B as the benchmark's serving cells run it (chipbench/configs/
# olmo2-1b.json: bf16, pages of 128, 288 pages, table width 33)
OLMO2_1B = dict(vocab_size=100352, hidden_size=2048, n_layers=16, n_heads=16,
                ffn_hidden_size=8192, max_seq_len=4096, rope_theta=5e5,
                norm_eps=1e-6, activation="silu", use_rmsnorm=True,
                use_rope=True, qk_norm="rms_flat", post_norm_only=True,
                tie_embeddings=False)
POOL = (288, 16, 128, 128)
ONE_LAYER_POOL_BYTES = 2 * int(np.prod(POOL))          # 151 MB

# name, jit name, batch, tokens, model settings over OLMO2_1B
DISPATCHES = [
    ("chat_decode_b32", "serve_decode", 32, 1, {}),
    ("docbatch_decode_b16", "serve_decode", 16, 1, {}),
    ("prefill_128", "serve_prefill", 1, 128, {}),
    ("prefill_4096", "serve_prefill", 1, 4096, {}),
    ("spec_verify_b32_t5", "serve_decode", 32, 5, {}),
    ("decode_chunk_4", "chunk", 32, 4, {}),
    # a list-of-layers stack (the static loop MoE models take)
    ("list_stack_decode", "serve_decode", 32, 1,
     dict(n_layers=4, moe_num_experts=4, moe_top_k=1)),
]


# what may hold a pool or a weight stack without copying it: plumbing, the
# layer loop, and the kernels that read and write in place
IN_PLACE_OPS = {"parameter", "get-tuple-element", "tuple", "while",
                "bitcast", "tpu_custom_call"}


def _pool_shaped(text, layers, shapes=None):
    """(name, opcode) of every instruction of the compiled text whose
    result holds a pool-shaped or stack-shaped array (or one of
    ``shapes``)."""
    import re
    dims = ",".join(map(str, POOL))
    shapes = shapes or (f"bf16[{dims}]", f"bf16[{layers},{dims}]")
    found = []
    for line in text.splitlines():
        name, eq, rest = line.partition(" = ")
        op = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest)
        if not eq or not op:
            continue
        result = rest[:op.start()]
        if any(s in result for s in shapes):
            opcode = op.group(1)
            if opcode == "custom-call" and "tpu_custom_call" in rest:
                opcode = "tpu_custom_call"
            found.append((name.split()[-1], opcode))
    return found


def _olmo2(topo, settings=()):
    """OLMo-2 1B abstract on the described chip: (the paged call the
    engine jits, with a prefill's ``head_rows`` as an optional sixth
    argument; params; caches; ``ints(*shape)``)."""
    chip = SingleDeviceSharding(topo.devices[0])
    model = CausalTransformerLM(TransformerConfig(**{**OLMO2_1B,
                                                     **dict(settings)}))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _on(chip, x.shape, x.dtype), tree)

    def ints(*shape):
        return _on(chip, shape, jnp.int32)

    def paged_call(params, ids, caches, tables, lengths, *rows):
        return model.apply_with_paged_cache(
            params, ids, caches, tables, lengths, attn_backend="pallas",
            **dict(zip(("head_rows",), rows)))

    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.bfloat16)))
    caches = on_chip(jax.eval_shape(
        lambda: model.init_paged_caches(POOL[0], POOL[2])))
    return paged_call, params, caches, ints


def _lower_dispatch(olmo2, jit_name, batch, tokens, *head):
    """One dispatch as the engine jits it (the caches donated); ``head``
    the shape of a prefill's ``head_rows``, nothing for every row."""
    paged_call, params, caches, ints = olmo2
    paged_call.__name__ = jit_name
    return jax.jit(paged_call, donate_argnums=(2,)).lower(
        params, ints(batch, tokens), caches, ints(batch, 33), ints(batch),
        *[ints(*shape) for shape in head])


@pytest.mark.parametrize("name,jit_name,batch,tokens,settings", DISPATCHES,
                         ids=[c[0] for c in DISPATCHES])
def test_serving_dispatch_never_copies_the_page_pools(
        topo, name, jit_name, batch, tokens, settings):
    """The guard that keeps the pool copies from coming back unseen on a
    CPU-only check (PERF.md §6, PR 29: ten copies of a layer's pool a
    layer a dispatch were four fifths of a decode step).  In the compiled
    program of each dispatch the engine makes, with the caches donated as
    the engine donates them, nothing but parameters, tuple plumbing, the
    layer loop and the two kernels has a result of the pool's or the
    stack's shape: no slice out of the stack, no re-layout, no copy back."""
    import types

    from deepspeed_tpu.inference.scheduler import SchedulerBase
    olmo2 = paged_call, params, caches, ints = _olmo2(topo, settings)
    layers = caches.k_pages.shape[0]
    if jit_name == "chunk":
        # the scheduler's own K-token scan, the pools in ITS carry
        sched = types.SimpleNamespace(engine=types.SimpleNamespace(
            decode_chunk=tokens, _paged_call=paged_call))
        floats = _on(ints(batch).sharding, (batch,), jnp.float32)
        lowered = SchedulerBase._build_chunk_fn(sched, False).lower(
            params, caches, ints(batch, 33), ints(batch), ints(batch),
            floats, ints(batch), ints(batch), ints(batch), floats)
    elif jit_name == "serve_prefill":
        # as the engine dispatches it: the head on the one row it samples
        lowered = _lower_dispatch(olmo2, jit_name, batch, tokens, (batch, 1))
    else:
        lowered = _lower_dispatch(olmo2, jit_name, batch, tokens)
    compiled = lowered.compile()
    text = compiled.as_text()
    found = _pool_shaped(text, layers)
    assert found, "the pools are not in the compiled text"
    assert not [f for f in found if f[1] not in IN_PLACE_OPS]
    assert "paged_kv_write" in text
    assert ("ragged_paged_attention_decode"
            if tokens == 1 or jit_name == "chunk"
            else "ragged_paged_attention_prefill") in text
    if tokens == 1 or jit_name == "chunk":
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < ONE_LAYER_POOL_BYTES, temp


WINDOW_DISPATCHES = {"decode_b16": (16, 1), "prefill_4096": (1, 4096)}
WINDOW_SIZES = dict(pages=257, slots=16, ring=17, held=16)


@pytest.fixture(scope="module")
def window_dispatch(topo):
    """Each of ``WINDOW_DISPATCHES`` of a model with window layers and a
    share of dropless experts (Trinity-Mini's widths, 8 of its layers: 4
    leading and one scanned period, 16 of 128 experts held), compiled once
    for the tests that read its text."""
    import functools
    import json
    import os

    from chipbench.families import afmoe
    with open(os.path.join(os.path.dirname(afmoe.__file__), "..",
                           "configs", "trinity-mini-ep8.json")) as f:
        cfg = json.load(f)
    cfg.update(num_hidden_layers=8, layer_types=cfg["layer_types"][:8],
               num_experts=WINDOW_SIZES["held"], vocab_size=1024)
    chip = SingleDeviceSharding(topo.devices[0])
    model = CausalTransformerLM(TransformerConfig(
        **afmoe.transformer_kwargs(cfg)))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _on(chip, x.shape, x.dtype), tree)

    def ints(*shape):
        return _on(chip, shape, jnp.int32)

    pages, slots, ring = (WINDOW_SIZES[k] for k in ("pages", "slots",
                                                    "ring"))
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.bfloat16)))
    caches = on_chip(jax.eval_shape(
        lambda: model.init_paged_caches(pages, 128, ring_slots=slots)))

    def serve(params, ids, caches, tables, lengths, real, *rows):
        return model.apply_with_paged_cache(
            params, ids, caches, tables, lengths, attn_backend="pallas",
            expert_backend="pallas", real_lengths=real,
            **dict(zip(("head_rows",), rows)))

    @functools.lru_cache(maxsize=None)
    def compiled(name):
        batch, tokens = WINDOW_DISPATCHES[name]
        return jax.jit(serve, donate_argnums=(2,)).lower(
            params, ints(batch, tokens), caches, ints(batch, 129 + ring),
            ints(batch), ints(batch),
            *([ints(batch, 1)] if tokens > 1 else [])).compile()

    return compiled


@pytest.mark.parametrize("name", WINDOW_DISPATCHES)
def test_window_model_dispatch_never_copies_either_stack(window_dispatch,
                                                         name):
    """The same guard for a model with window layers: neither the
    full-attention stack nor the ring stack is sliced, re-laid or copied
    by a dispatch; a prefill longer than the ring (4,096 rows into 17
    pages of 128) gathers its tail out of the new rows, not out of a
    pool."""
    compiled = window_dispatch(name)
    tokens = WINDOW_DISPATCHES[name][1]
    pages, slots, ring = (WINDOW_SIZES[k] for k in ("pages", "slots",
                                                    "ring"))
    text = compiled.as_text()
    found = {op for _, op in _pool_shaped(text, None, [
        f"bf16[{layers},{n},4,128,128]"
        for layers, n in ((2, pages), (6, slots * ring + 1))])}
    assert found and found <= IN_PLACE_OPS, found
    assert "paged_kv_write" in text and " while(" in text
    assert ("ragged_paged_attention_decode" if tokens == 1
            else "ragged_paged_attention_prefill") in text
    smaller = 2 * 2 * slots * ring * 4 * 128 * 128      # one ring layer
    assert compiled.memory_analysis().temp_size_in_bytes < (
        smaller if tokens == 1 else 1 << 30)


@pytest.mark.parametrize("name", WINDOW_DISPATCHES)
def test_expert_layer_never_copies_an_expert_or_loops_over_them(
        window_dispatch, name):
    """The guard that keeps the copies of the held experts from coming
    back unseen on a CPU-only check (PERF.md §6, PR 38: ``w_gate[layer,
    e]`` and ``w_up[layer, e]`` of every held expert, cut out ahead of a
    ``while`` an expert and copied whether it ran or not, were a sixth of
    a cell's busy time).  The expert layer is ONE kernel call that finds
    its weights in the stacked leaves: nothing but parameters, tuple
    plumbing, the loops, bitcasts and the kernel has a result of a leaf's
    shape; nothing under the ``experts`` scope has one expert's (the
    shared expert, of the same widths, is not under it); and the loops
    are the scan over the periods and one of chunks an expert layer, not
    one an expert."""
    import re
    text = window_dispatch(name).as_text()
    held = WINDOW_SIZES["held"]
    leaves = [f"bf16[{lead}{held},{shape}]" for lead in ("", "1,")
              for shape in ("2048,1024", "1024,2048")]
    found = {op for _, op in _pool_shaped(text, None, leaves)}
    assert found and found <= IN_PLACE_OPS, found
    one = re.compile(r" = \(?bf16\[(1,)*(2048,1024|1024,2048)\]")
    assert not [line[:120] for line in text.splitlines()
                if one.search(line) and "/experts/" in line]
    assert "grouped_expert_glu" in text
    # 2 listed expert layers and the period's 4: a loop of chunks each,
    # and the scan, where a single period keeps one (the parent: 16 more
    # a layer, one an expert)
    assert 6 <= text.count(" while(") <= 1 + 2 + 4


# name: (sequences, tokens, rows the head runs on, what the prefill reads
# the pool with: the XLA walk, or the kernel of PR 45)
DENSE_LATENT_DISPATCHES = {"decode_b16": (16, 1, None, "jnp"),
                           "chunk_2048": (1, 2048, 0, "jnp"),
                           "chunk_2048_sampled": (1, 2048, 1, "jnp"),
                           "chunk_2048_kernel": (1, 2048, 0, "pallas"),
                           "chunk_2048_sampled_kernel": (1, 2048, 1,
                                                         "pallas"),
                           "pieces_b3_512_kernel": (3, 512, 1, "pallas")}
DENSE_LATENT_PAGES = 2 * 136 + 1        # two slots of max_seq 17,408


@pytest.fixture(scope="module")
def dense_latent_dispatch(topo):
    """Each of ``DENSE_LATENT_DISPATCHES`` of a latent-attention model
    WITHOUT a selection (Kimi-K2's widths, 2 of its layers: the dense one
    and an expert layer of 12 held of 384), compiled once."""
    import functools
    import json
    import os

    from chipbench.families import kimi_k2
    with open(os.path.join(os.path.dirname(kimi_k2.__file__), "..",
                           "configs", "kimi-k2-ep32.json")) as f:
        cfg = json.load(f)
    cfg.update(num_hidden_layers=2, vocab_size=1024)
    chip = SingleDeviceSharding(topo.devices[0])
    model = CausalTransformerLM(TransformerConfig(
        **kimi_k2.transformer_kwargs(cfg)))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _on(chip, x.shape, x.dtype), tree)

    def ints(*shape):
        return _on(chip, shape, jnp.int32)

    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.bfloat16)))
    caches = on_chip(jax.eval_shape(
        lambda: model.init_paged_caches(DENSE_LATENT_PAGES, 128)))

    def serve(impl, params, ids, caches, tables, lengths, real, *rows):
        return model.apply_with_paged_cache(
            params, ids, caches, tables, lengths, expert_backend="pallas",
            latent_backend=impl, real_lengths=real,
            **dict(zip(("head_rows",), rows)))

    @functools.lru_cache(maxsize=None)
    def compiled(name):
        batch, tokens, rows, impl = DENSE_LATENT_DISPATCHES[name]
        return jax.jit(functools.partial(serve, impl),
                       donate_argnums=(2,)).lower(
            params, ints(batch, tokens), caches, ints(batch, 137),
            ints(batch), ints(batch),
            *([] if rows is None else [ints(batch, rows)])).compile()

    return compiled


@pytest.mark.parametrize("name", DENSE_LATENT_DISPATCHES)
def test_dense_latent_dispatch_walks_the_pool_in_place(dense_latent_dispatch,
                                                       name):
    """A decode step and a prefill chunk of a latent model without a
    selection (PR 41): the stacked pool of 640-value rows is written by a
    scatter in place and read a block of pages at a time inside a loop,
    or (PR 45) by the ``latent_attention_prefill`` kernel through its
    index maps, which Mosaic compiles at these widths for one chunk of
    2,048 rows and for three pieces of 512; nothing else has a result of
    its shape (no copy, no re-layout), the index pool has no bytes, and
    the 12 held experts of 384 go through the one grouped kernel."""
    compiled = dense_latent_dispatch(name)
    text = compiled.as_text()
    pool = f"bf16[2,{DENSE_LATENT_PAGES},128,640]"
    found = {op for _, op in _pool_shaped(text, None, [pool])}
    assert found and found <= IN_PLACE_OPS | {"fusion", "scatter"}, found
    batch, tokens, rows, impl = DENSE_LATENT_DISPATCHES[name]
    assert ("latent_attention_prefill" in text) == (impl == "pallas")
    assert " while(" in text or impl == "pallas"   # the XLA walk's loop
    # a chunk that is not sampled from needs nothing of its LAST layer
    # but the entries it writes: the compiler drops that layer's expert
    # product (here the only one) with the head
    assert ("grouped_expert_glu" in text) == (rows != 0)
    # no index keys anywhere: the second pool is an array of no elements
    assert f"bf16[2,{DENSE_LATENT_PAGES},128,0]" in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * DENSE_LATENT_PAGES * 128 * 1280
    # a decode step's largest temporary is a block of gathered entries; a
    # chunk's the dense layer's gate and up (2,048 x 18,432)
    assert memory.temp_size_in_bytes < (1 << 26 if tokens == 1 else 1 << 30)


BUCKET_LOGITS = "f32[1,4096,100352]"      # 1.64 GB: every row of a bucket


def _text_and_bytes(compiled):
    """The compiled text, and the bytes the dispatch allocates beside its
    arguments: temporaries and results, less the donated pools (the
    compiler lays temporaries into the logits' block where there is one,
    so neither number alone says what the head's rows cost)."""
    memory = compiled.memory_analysis()
    return compiled.as_text(), (memory.temp_size_in_bytes
                                + memory.output_size_in_bytes
                                - memory.alias_size_in_bytes)


@pytest.fixture(scope="module")
def all_rows_prefill(topo):
    """The 4,096 bucket asked for every row, as every prefill was until
    PR 33."""
    return _text_and_bytes(_lower_dispatch(_olmo2(topo), "serve_prefill", 1,
                                           4096).compile())


@pytest.mark.parametrize("rows", [1, 0], ids=["sampled_row", "no_head"])
def test_prefill_holds_no_logits_of_its_bucket(topo, all_rows_prefill, rows):
    """A prefill takes the head on the row it samples from (an
    intermediate chunk on none): the 4,096 bucket's program has no float32
    logits of the whole bucket anywhere, and allocates about that block
    less than the all-rows call (1.58 GB: the block, less the 67 MB of
    temporaries that lay in it)."""
    text, held = _text_and_bytes(_lower_dispatch(
        _olmo2(topo), "serve_prefill", 1, 4096, (1, rows)).compile())
    full_text, full_held = all_rows_prefill
    assert BUCKET_LOGITS in full_text
    assert BUCKET_LOGITS not in text
    assert f"f32[1,{rows},100352]" in text
    assert full_held - held > 1.5e9, (full_held, held)


def test_decode_still_returns_every_row(topo):
    """The decode program (B = 32, T = 1) is not asked for rows: its
    result is the logits of all 32 slots, as the sampler reads them."""
    text = _lower_dispatch(_olmo2(topo), "serve_decode", 32,
                           1).compile().as_text()
    result = next(line for line in text.splitlines()
                  if line.startswith("ENTRY"))
    assert "f32[32,1,100352]" in result, result


def test_one_program_a_bucket_whatever_row_is_sampled():
    """The row a prefill samples from is an argument of its program, not
    a shape: prompts of 9 to 16 tokens run ONE compiled
    ``jit_serve_prefill`` (a bucket of 16), and the decode program, which
    is asked for no rows, stays one beside them."""
    from deepspeed_tpu.inference.serving import ServingEngine
    model = CausalTransformerLM(TransformerConfig.tiny(
        hidden_size=64, n_heads=4, n_kv_heads=2))
    eng = ServingEngine(model, model.init(jax.random.key(0)), max_batch=4,
                        page_size=8, max_seq=128, dtype=jnp.float32)
    for i, n in enumerate((9, 12, 16)):
        eng.add_request(i, list(range(1, n + 1)), max_new_tokens=2)
    while eng.queue or eng.n_active:
        eng.step()
    assert eng._prefill_fn._cache_size() == 1
    assert eng._step_fn._cache_size() == 1


def test_decode_is_one_program_whatever_the_lengths():
    """The ragged kernel's item bound is a traced value, not a bucketed
    shape: a decode dispatch of idle slots and one of slots at their full
    context run the SAME compiled ``jit_serve_decode`` (interpret mode on
    the CPU; on the chip ``compiles.*`` counts any other)."""
    from deepspeed_tpu.inference.serving import ServingEngine
    model = CausalTransformerLM(TransformerConfig.tiny(
        hidden_size=64, n_heads=4, n_kv_heads=2))
    eng = ServingEngine(model, model.init(jax.random.key(0)), max_batch=4,
                        page_size=8, max_seq=128, dtype=jnp.float32,
                        serving={"attention_backend": "pallas-interpret"})
    ids = jnp.zeros((4, 1), jnp.int32)
    grids = []
    for lengths in (np.zeros(4, np.int32), np.full(4, 127, np.int32)):
        _, eng.caches, _ = eng._run_step(ids, jnp.asarray(eng.tables),
                                         lengths)
        grids.append(eng._report["dispatches"][-1]["kernel_grid"])
    assert eng._step_fn._cache_size() == 1
    assert 0 < grids[0] < grids[1] <= \
        eng._report["dispatches"][-1]["kernel_grid_full"]


def test_dropless_expert_layer_backward(topo):
    """PR 43: the held-expert layer's ``custom_vjp`` at Mellum2's widths
    (d 2304, f 896, 16 held of 64, top-8) through Mosaic: the backward's
    two kernels are in the compiled gradient, the dw kernel's traced grid
    bound in SECOND place and its aliased float32 accumulators included."""
    from deepspeed_tpu.moe.sharded_moe import dropless_held_experts
    chip = SingleDeviceSharding(topo.devices[0])
    N, d, f, held, k = 2048, 2304, 896, 16, 8

    def loss(h, weights, experts, chosen):
        out, _, _ = dropless_held_experts(h, chosen, weights, experts,
                                          jax.nn.silu, impl="pallas")
        return jnp.sum(out)

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        _on(chip, (N, d)), _on(chip, (N, k), jnp.float32),
        {"w_gate": _on(chip, (held, d, f)), "w_up": _on(chip, (held, d, f)),
         "w_down": _on(chip, (held, f, d))},
        _on(chip, (N, k), jnp.int32)).compile().as_text()
    assert "grouped_expert_glu_dx" in text and "grouped_expert_glu_dw" in text
    # the combine's and the row gather's transposes are gathers too
    assert "scatter(" not in text


HYBRID_DISPATCHES = {"decode_b64": (64, 1), "prefill_1024": (1, 1024)}
HYBRID_SIZES = dict(pages=1025, slots=64, layers=20)


@pytest.fixture(scope="module")
def hybrid_dispatch(topo):
    """Each of ``HYBRID_DISPATCHES`` of a model with state-space layers
    (granite-4.0-h-micro's widths, two of its four periods of ten layers,
    a vocabulary of 1,024), as the engine dispatches it: a prefill is told
    its real rows and its slot, a decode step neither."""
    import functools
    import json
    import os

    from chipbench.families import granitemoehybrid
    with open(os.path.join(os.path.dirname(granitemoehybrid.__file__), "..",
                           "configs", "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    layers = HYBRID_SIZES["layers"]
    cfg.update(num_hidden_layers=layers,
               layer_types=cfg["layer_types"][:layers], vocab_size=1024)
    chip = SingleDeviceSharding(topo.devices[0])
    model = CausalTransformerLM(TransformerConfig(
        **granitemoehybrid.transformer_kwargs(cfg)))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _on(chip, x.shape, x.dtype), tree)

    def ints(*shape):
        return _on(chip, shape, jnp.int32)

    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.bfloat16)))
    caches = on_chip(jax.eval_shape(lambda: model.init_paged_caches(
        HYBRID_SIZES["pages"], 128, state_slots=HYBRID_SIZES["slots"])))

    def serve(params, ids, caches, tables, lengths, *told):
        return model.apply_with_paged_cache(
            params, ids, caches, tables, lengths, attn_backend="pallas",
            **dict(zip(("head_rows", "real_lengths", "state_slots"), told)))

    @functools.lru_cache(maxsize=None)
    def compiled(name):
        batch, tokens = HYBRID_DISPATCHES[name]
        return jax.jit(serve, donate_argnums=(2,)).lower(
            params, ints(batch, tokens), caches, ints(batch, 17),
            ints(batch), *([ints(batch, 1), ints(batch), ints(batch)]
                           if tokens > 1 else [])).compile()

    return compiled


@pytest.mark.parametrize("name", HYBRID_DISPATCHES)
def test_hybrid_dispatch_copies_neither_the_pages_nor_the_state(
        hybrid_dispatch, name):
    """The guard of PR 29's rule for a model with state-space layers: no
    dispatch slices, re-lays or copies the attention layers' page stack
    (heads of 64 packed two a row of 128 lanes: at [.., 8, 128, 64] the
    compiler re-laid both stacks on the way into and out of every dispatch,
    four copies of a pool), the recurrent state (float32, a row a slot) or
    the convolution's last inputs (flat: as [layers, 3, slots, 4352] the
    prefill re-laid it nine times).  A decode step advances the state
    through the aliased ``ssm_decode_update`` kernel alone (PR 48: no
    fusion holds the pool, so none reads it a second time for ``y``); a
    prefill reads and writes it in place by the layer loop's fusions."""
    compiled = hybrid_dispatch(name)
    tokens = HYBRID_DISPATCHES[name][1]
    pages, slots = HYBRID_SIZES["pages"], HYBRID_SIZES["slots"]
    text = compiled.as_text()
    found = {op for _, op in _pool_shaped(text, None, [
        f"bf16[2,{pages},4,128,128]"])}
    assert found and found <= IN_PLACE_OPS, found
    assert f"bf16[2,{pages},8,128,64]" not in text
    # a decode step's state goes through the kernel's custom call and
    # nothing else; a prefill's is read and written in place by the loop's
    # fusions (a dynamic-update-slice inside each); the conv's tails, a
    # pool of 30 MB here, may be prefetched whole (slice-start, copy-start:
    # no re-layout)
    state = {op for _, op in _pool_shaped(text, None, [
        f"f32[18,{slots},64,64,128]"])}
    assert state and state <= IN_PLACE_OPS | (
        set() if tokens == 1 else {"fusion", "dynamic-update-slice"}), state
    assert ("tpu_custom_call" in state) == (tokens == 1)
    assert ("ssm_decode_update" in text) == (tokens == 1)
    tails = {op for _, op in _pool_shaped(text, None, [
        f"bf16[18,{slots},13056]"])}
    assert tails and "copy" not in tails, tails
    assert f"bf16[18,3,{slots},4352]" not in text
    assert "paged_kv_write" in text and " while(" in text
    assert ("ragged_paged_attention_decode" if tokens == 1
            else "ragged_paged_attention_prefill") in text
    one_layer_state = slots * 64 * 64 * 128 * 4         # 33.5 MB
    assert compiled.memory_analysis().temp_size_in_bytes < (
        one_layer_state if tokens == 1 else 1 << 28)


def test_a_short_prefill_of_128_state_heads_leaves_the_pool_where_it_lies(
        topo):
    """granite-4.0-h-small's widths (128 state heads of 64 x 128, a routed
    expert layer in every layer; the cell's one period of ten layers and
    its pools, a vocabulary of 1,024): a prefill of 64 rows, one chunk of
    the scan.  Its einsums leave the new state in an order of their own,
    and written back as [H, P, N] that order went on to the WHOLE pool:
    2.4 GB copied on the way in and again on the way out, 2.46 GB of
    temporaries (PR 51; the micro's 64 heads never showed it, nor did the
    longer buckets here).  ``mix_ssm_paged`` cuts a slot's state out of
    [L, slots, H x P, N]: no instruction but the loop's in-place ones holds
    the pool."""
    import json
    import os

    from chipbench.families import granitemoehybrid_routed as family
    with open(os.path.join(os.path.dirname(family.__file__), "..",
                           "configs", "granite-4.0-h-small-ep2.json")) as f:
        cfg = dict(json.load(f), vocab_size=1024)
    chip = SingleDeviceSharding(topo.devices[0])
    model = CausalTransformerLM(TransformerConfig(
        **family.transformer_kwargs(cfg)))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _on(chip, x.shape, x.dtype), tree)

    def ints(*shape):
        return _on(chip, shape, jnp.int32)

    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.bfloat16)))
    caches = on_chip(jax.eval_shape(lambda: model.init_paged_caches(
        1025, 128, state_slots=64)))

    def serve(params, ids, caches, tables, lengths, rows, real, slots):
        return model.apply_with_paged_cache(
            params, ids, caches, tables, lengths, attn_backend="pallas",
            expert_backend="pallas", head_rows=rows, real_lengths=real,
            state_slots=slots)

    compiled = jax.jit(serve, donate_argnums=(2,)).lower(
        params, ints(1, 64), caches, ints(1, 17), ints(1), ints(1, 1),
        ints(1), ints(1)).compile()
    state = {op for _, op in _pool_shaped(compiled.as_text(), None, [
        "f32[9,64,128,64,128]", "f32[9,64,8192,128]"])}
    assert state and state <= IN_PLACE_OPS | {
        "fusion", "dynamic-update-slice", "bitcast"}, state
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28
    assert "grouped_expert_glu" in compiled.as_text()


def test_bf16_moment_update_is_one_pass_at_the_memory_pace(topo):
    """PR 55: the update of the 1.4b cell's largest stacked leaf (bf16
    moments, bf16 gradients, float32 master; the norm, ``tx.update`` and
    ``apply_updates`` of the engine's ``optimizer`` scope) by the compiler's
    own account: 18 bytes a parameter (read 4 + 2 + 2 + 2, write 4 + 2 + 2)
    and, since the rounding's noise is a counter hash, 40 operations a
    parameter where two Threefry draws a leaf took 281 (50 ps a parameter on
    the chip: the vector unit's pace, not the memory's 22)."""
    import optax
    from deepspeed_tpu.runtime.engine import _global_norm_f32
    from deepspeed_tpu.runtime.optimizers import build_optimizer
    chip = SingleDeviceSharding(topo.devices[0])
    shape = (16, 2048, 8192)
    tx = build_optimizer("adamw", {"lr": 1e-4, "weight_decay": 0.0,
                                   "moment_dtype": "bfloat16"})

    def update(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, \
            _global_norm_f32(grads)

    params = {"w": _on(chip, shape, jnp.float32)}
    opt_state = jax.tree_util.tree_map(
        lambda x: _on(chip, x.shape, x.dtype), jax.eval_shape(tx.init, params))
    compiled = jax.jit(update, donate_argnums=(0, 1)).lower(
        params, opt_state, {"w": _on(chip, shape)}).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    n = np.prod(shape)
    assert cost["bytes accessed"] / n == pytest.approx(18.0, abs=0.05)
    assert cost["flops"] / n < 60
    assert "rng-bit-generator" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


SALA_DISPATCHES = {"decode_b16": (16, 1), "prefill_16384": (1, 16384)}
SALA_SIZES = dict(pages=2113, slots=16)


@pytest.fixture(scope="module")
def sala_dispatch(topo):
    """Each of ``SALA_DISPATCHES`` of a model with linear-attention layers
    and block-sparse attention (the ``minicpm-sala`` configuration's eight
    layers at their widths, the cell's pools, a vocabulary of 1,024), as
    the engine dispatches it: both are told their real rows, a prefill its
    slot too."""
    import functools
    import json
    import os

    from chipbench.families import minicpm_sala
    with open(os.path.join(os.path.dirname(minicpm_sala.__file__), "..",
                           "configs", "minicpm-sala.json")) as f:
        cfg = json.load(f)
    cfg.update(vocab_size=1024)
    chip = SingleDeviceSharding(topo.devices[0])
    model = CausalTransformerLM(TransformerConfig(
        **minicpm_sala.transformer_kwargs(cfg)))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _on(chip, x.shape, x.dtype), tree)

    def ints(*shape):
        return _on(chip, shape, jnp.int32)

    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.bfloat16)))
    caches = on_chip(jax.eval_shape(lambda: model.init_paged_caches(
        SALA_SIZES["pages"], 128, state_slots=SALA_SIZES["slots"])))

    def serve(params, ids, caches, tables, lengths, *told):
        names = ("head_rows", "real_lengths", "state_slots") \
            if len(told) == 3 else ("real_lengths",)
        return model.apply_with_paged_cache(
            params, ids, caches, tables, lengths, attn_backend="pallas",
            **dict(zip(names, told)))

    @functools.lru_cache(maxsize=None)
    def compiled(name):
        batch, tokens = SALA_DISPATCHES[name]
        return jax.jit(serve, donate_argnums=(2,)).lower(
            params, ints(batch, tokens), caches, ints(batch, 133),
            ints(batch), *([ints(batch, 1), ints(batch), ints(batch)]
                           if tokens > 1 else [ints(batch)])).compile()

    return compiled


@pytest.mark.parametrize("name", SALA_DISPATCHES)
def test_sala_dispatch_copies_neither_the_pools_nor_the_state(
        sala_dispatch, name):
    """PR 29's rule for PR 56's pools: no dispatch re-lays or copies the
    sparse layers' K/V stack (a decode step's gather of a compressed
    key's 32 keys by single rows did: 277 MB a layer and step, until they
    were read as two aligned runs of 16), the compressed keys (written and
    read by whole row index: as [.., Hkv, page / 16, D] the scatter and the
    gather each re-laid the pool their own way) or the linear layers'
    matrix state (float32, a row a slot: updated in place by the layer
    loop's fusions)."""
    compiled = sala_dispatch(name)
    tokens = SALA_DISPATCHES[name][1]
    pages, slots = SALA_SIZES["pages"], SALA_SIZES["slots"]
    text = compiled.as_text()
    kv = {op for _, op in _pool_shaped(text, None, [
        f"bf16[2,{pages},2,128,128]"])}
    assert kv and kv <= IN_PLACE_OPS, kv
    compressed = {op for _, op in _pool_shaped(text, None, [
        f"bf16[2,{pages},16,128]"])}
    assert compressed and "copy" not in compressed, compressed
    state = {op for _, op in _pool_shaped(text, None, [
        f"f32[6,{slots},32,128,128]"])}
    assert state and state <= IN_PLACE_OPS | {
        "fusion", "dynamic-update-slice"}, state
    assert "paged_kv_write" in text
    assert ("ragged_paged_attention_decode" if tokens == 1
            else "ragged_paged_attention_prefill") in text
    # a decode step holds no more than a few blocks' gather beside its
    # operands; the 16k bucket two SwiGLU halves and a step of the mask
    assert compiled.memory_analysis().temp_size_in_bytes < (
        64 << 20 if tokens == 1 else 3 << 30)
