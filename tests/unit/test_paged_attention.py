"""Paged KV-cache attention tests: paged path must reproduce the dense
ring-buffer decode attention on ragged batches."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.decode_attention import (KVCache, decode_attention,
                                                init_cache, update_cache)
from deepspeed_tpu.ops.paged_attention import (PagedAllocator, PagedKVCache,
                                               paged_decode_attention,
                                               write_paged)

H, HKV, D, PAGE = 4, 2, 8, 4


def _pools(num_pages):
    """A one-layer stack of empty pools, as a dispatch carries them."""
    shape = (1, num_pages, HKV, PAGE, D)
    return PagedKVCache(jnp.zeros(shape, jnp.float32),
                        jnp.zeros(shape, jnp.float32))


def _write(pools, tables, lengths, k, v):
    """The pools' one writer (jnp pair) → (pools, new lengths)."""
    return (write_paged(pools, 0, tables, lengths, k, v, impl="jnp"),
            lengths + k.shape[1])


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def test_allocator_reuse_and_tables():
    al = PagedAllocator(num_pages=10, page_size=PAGE, max_pages_per_seq=4)
    p0 = al.allocate("a", 9)     # 3 pages
    p1 = al.allocate("b", 4)     # 1 page
    assert len(p0) == 3 and len(p1) == 1 and not set(p0) & set(p1)
    table = al.block_table(["a", "b"])
    assert table.shape == (2, 4)
    np.testing.assert_array_equal(table[0, :3], p0)
    al.free_sequence("a")
    assert al.can_allocate(3)
    p2 = al.allocate("c", 12)
    assert set(p2) <= set(p0) | set(al.free) | set(p2)  # reused pool
    al.extend("b", 6)            # crosses into a second page
    assert len(al.seq_pages["b"]) == 2


def test_paged_matches_dense_single_seq():
    """Prefill + several decode steps, non-trivial page permutation."""
    B, T0 = 1, 6
    al = PagedAllocator(num_pages=8, page_size=PAGE, max_pages_per_seq=4)
    al.free = [5, 1, 7, 2, 0, 3, 6, 4]  # force scattered pages
    al.allocate(0, T0)

    dense = init_cache(B, 16, HKV, D, jnp.float32)
    paged = _pools(8)
    lengths = jnp.zeros((B,), jnp.int32)

    k0, v0 = _rand((B, T0, HKV, D), 1), _rand((B, T0, HKV, D), 2)
    dense = update_cache(dense, k0, v0)
    tables = jnp.asarray(al.block_table([0]))
    paged, lengths = _write(paged, tables, lengths, k0, v0)

    for step in range(5):
        al.extend(0, T0 + step + 1)
        tables = jnp.asarray(al.block_table([0]))
        q = _rand((B, 1, H, D), 10 + step)
        k1, v1 = _rand((B, 1, HKV, D), 20 + step), _rand((B, 1, HKV, D),
                                                         30 + step)
        dense = update_cache(dense, k1, v1)
        paged, lengths = _write(paged, tables, lengths, k1, v1)
        ref = decode_attention(q, dense)
        got = paged_decode_attention(q, paged, tables, lengths, layer=0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


def test_paged_ragged_batch():
    """Two sequences of different lengths batched together — the case the
    dense ring buffer cannot express without padding to max length."""
    al = PagedAllocator(num_pages=16, page_size=PAGE, max_pages_per_seq=4)
    al.allocate("s0", 3)
    al.allocate("s1", 11)
    paged = _pools(16)
    tables = jnp.asarray(al.block_table(["s0", "s1"]))
    lengths = jnp.zeros((2,), jnp.int32)

    # per-sequence prefill with different lengths: pad the short one and
    # overwrite lengths afterwards (host orchestration)
    k = _rand((2, 11, HKV, D), 1)
    v = _rand((2, 11, HKV, D), 2)
    al.extend("s0", 11)  # scratch pages so padded writes land somewhere
    tables = jnp.asarray(al.block_table(["s0", "s1"]))
    paged, _ = _write(paged, tables, lengths, k, v)
    lengths = jnp.asarray([3, 11], jnp.int32)

    q = _rand((2, 1, H, D), 3)
    got = paged_decode_attention(q, paged, tables, lengths, layer=0)

    # reference: each sequence independently with a dense cache
    for b, L in enumerate((3, 11)):
        dense = init_cache(1, 16, HKV, D, jnp.float32)
        dense = update_cache(dense, k[b:b + 1, :L], v[b:b + 1, :L])
        ref = decode_attention(q[b:b + 1], dense)
        np.testing.assert_allclose(np.asarray(got[b:b + 1]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


def test_gqa_paged():
    B, T0 = 2, 5
    al = PagedAllocator(num_pages=8, page_size=PAGE, max_pages_per_seq=2)
    al.allocate(0, T0)
    al.allocate(1, T0)
    paged = _pools(8)
    tables = jnp.asarray(al.block_table([0, 1]))
    lengths = jnp.zeros((B,), jnp.int32)
    k, v = _rand((B, T0, HKV, D), 1), _rand((B, T0, HKV, D), 2)
    paged, lengths = _write(paged, tables, lengths, k, v)
    q = _rand((B, 1, H, D), 3)   # H=4 query heads over HKV=2 (GQA)
    out = paged_decode_attention(q, paged, tables, lengths, layer=0)
    assert out.shape == (B, 1, H, D)
    dense = init_cache(B, 8, HKV, D, jnp.float32)
    dense = update_cache(dense, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(decode_attention(q, dense)),
                               rtol=1e-5, atol=1e-6)
