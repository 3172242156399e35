"""Pallas inference/optimizer kernels vs their jnp oracles (interpreter on
CPU CI; on TPU the same kernels compile via the auto dispatch in
``ops/decode_attention.py`` / ``ops/paged_attention.py`` / ``ops/adam.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.decode_attention import KVCache, decode_attention
from deepspeed_tpu.ops.paged_attention import (PagedAllocator, PagedKVCache,
                                               paged_decode_attention,
                                               write_paged)
from deepspeed_tpu.ops.pallas.decode_attention import decode_attention_pallas


def _filled_pools(npages, page, tables, k, v):
    """A one-layer stack of pools holding rows ``k``/``v`` [B, T, Hkv, D]
    from position 0, written by the pools' one writer (jnp pair)."""
    B, _, Hkv, D = k.shape
    shape = (1, npages, Hkv, page, D)
    empty = PagedKVCache(jnp.zeros(shape, jnp.float32),
                         jnp.zeros(shape, jnp.float32))
    return write_paged(empty, 0, tables, jnp.zeros((B,), jnp.int32), k, v,
                       impl="jnp")


def _cache_inputs(B=3, S=64, H=4, Hkv=2, D=16, seed=0):
    """Cache-layout [B, Hkv, S, D] arrays (+ model-layout views for
    prefill inputs via swapaxes at the call sites)."""
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    lengths = jnp.asarray([5, 33, S], jnp.int32)[:B]
    return k, v, lengths, rng


@pytest.mark.parametrize("T", [1, 4])
@pytest.mark.parametrize("Hkv", [4, 2])
def test_decode_kernel_matches_oracle(T, Hkv):
    B, S, H, D = 3, 64, 4, 16
    k, v, lengths, rng = _cache_inputs(B, S, H, Hkv, D)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)

    per_batch = []
    for b in range(B):
        cache = KVCache(k=k[b:b + 1], v=v[b:b + 1], length=lengths[b])
        per_batch.append(decode_attention(q[b:b + 1], cache, impl="jnp"))
    oracle = jnp.concatenate(per_batch, 0)

    got = decode_attention_pallas(q, k, v, lengths, block_k=16,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)


def test_decode_dispatch_pallas_impl():
    """impl="pallas" through the public API (uniform length, interpret)."""
    B, S, H, Hkv, D = 2, 32, 4, 2, 16
    rng = np.random.default_rng(1)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    cache = KVCache(k=k, v=v, length=jnp.asarray(20, jnp.int32))
    ref = decode_attention(q, cache, impl="jnp")
    got = decode_attention(q, cache, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T", [1, 3])
def test_paged_kernel_matches_oracle(T):
    B, S, H, Hkv, D = 3, 64, 4, 2, 16
    page, npages, maxp = 16, 32, 4
    k, v, lengths, rng = _cache_inputs(B, S, H, Hkv, D)
    alloc = PagedAllocator(npages, page, maxp)
    for b in range(B):
        alloc.allocate(b, int(lengths[b]))
    tables = jnp.asarray(alloc.block_table(range(B)))
    cache = _filled_pools(npages, page, tables, jnp.swapaxes(k, 1, 2),
                          jnp.swapaxes(v, 1, 2))
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)

    oracle = paged_decode_attention(q, cache, tables, lengths, impl="jnp",
                                    layer=0)
    got = paged_decode_attention(q, cache, tables, lengths, impl="pallas",
                                 interpret=True, layer=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)


def test_paged_kernel_shuffled_page_table():
    """Pages deliberately non-contiguous in the pool: the kernel must
    follow the block table, not linear page order."""
    B, H, Hkv, D = 2, 2, 2, 16
    page, npages, maxp = 8, 16, 4
    rng = np.random.default_rng(2)
    # hand-build shuffled tables: seq0 -> pages [7, 3], seq1 -> [11, 0, 5]
    tables = jnp.asarray([[7, 3, 0, 0], [11, 0, 5, 0]], jnp.int32)
    lengths = jnp.asarray([13, 22], jnp.int32)
    k = jnp.asarray(rng.normal(size=(B, maxp * page, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, maxp * page, Hkv, D)), jnp.float32)
    cache = _filled_pools(npages, page, tables, k, v)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    oracle = paged_decode_attention(q, cache, tables, lengths, impl="jnp",
                                    layer=0)
    got = paged_decode_attention(q, cache, tables, lengths, impl="pallas",
                                 interpret=True, layer=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)


# ---- fused Adam ------------------------------------------------------

@pytest.mark.parametrize("n", [1000, 65536, 70001])
def test_fused_adam_pallas_matches_oracle(n):
    from deepspeed_tpu.ops.adam import init_state, reference_impl
    from deepspeed_tpu.ops.pallas.fused_adam import fused_adam_pallas
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    st = init_state(p)
    for _ in range(3):
        p_ref, st_ref = reference_impl(p, g, st, lr=1e-3, weight_decay=0.01)
        p_pal, st_pal = fused_adam_pallas(p, g, st, lr=1e-3,
                                          weight_decay=0.01, interpret=True)
        np.testing.assert_allclose(np.asarray(p_pal), np.asarray(p_ref),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(st_pal.m), np.asarray(st_ref.m),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(st_pal.v), np.asarray(st_ref.v),
                                   rtol=1e-6, atol=1e-6)
        assert int(st_pal.step) == int(st_ref.step)
        p, st, g = p_ref, st_ref, g * 0.9


@pytest.mark.parametrize("adamw_mode,bias_correction",
                         [(False, True), (True, False), (False, False)])
def test_fused_adam_pallas_modes(adamw_mode, bias_correction):
    from deepspeed_tpu.ops.adam import init_state, reference_impl
    from deepspeed_tpu.ops.pallas.fused_adam import fused_adam_pallas
    rng = np.random.default_rng(3)
    p = jnp.asarray(rng.normal(size=(4096,)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(4096,)), jnp.float32)
    st = init_state(p)
    pr, _ = reference_impl(p, g, st, adamw_mode=adamw_mode,
                           weight_decay=0.1, bias_correction=bias_correction)
    pp, _ = fused_adam_pallas(p, g, st, adamw_mode=adamw_mode,
                              weight_decay=0.1,
                              bias_correction=bias_correction,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(pp), np.asarray(pr),
                               rtol=1e-6, atol=1e-6)
