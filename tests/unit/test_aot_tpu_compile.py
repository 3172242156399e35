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
])
def test_flash_forward_backward(topo, name, shape, kv_heads, window):
    chip = SingleDeviceSharding(topo.devices[0])
    B, S, _, D = shape

    def loss(q, k, v):
        out = attention(q, k, v, causal=True, impl="pallas", window=window)
        return out.astype(jnp.float32).sum()

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                          _on(chip, shape), _on(chip, (B, S, kv_heads, D)),
                          _on(chip, (B, S, kv_heads, D)))
    assert text.count("tpu_custom_call") >= 3      # fwd, dq, dkv


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
