"""Block-sparse attention over compressed keys
(``ops/block_sparse_attention.py``) against the plain statements of
``chipbench/reference/minicpm_sala.py``, on random float32 operands at toy
sizes (block 4, kernel 2 / 1 and 4 / 2, top-6, window 8).

``MARGIN``: program and reference compute the same float32 block scores in
another order of sums, so a block set is compared only where the
reference's own margin between its last chosen and its first unchosen
UNFORCED block is over 1e-5 (scores are sums of 2 softmax weights, of the
order of 0.1 to 2; float32 rounding moves them by 1e-7).  An exact tie
is decided: neighbouring blocks share a compressed key, so both sides see
the same element twice, and both give the lower index.  Forced blocks are
always in, whatever the margin."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import minicpm_sala as reference
from deepspeed_tpu.ops import block_sparse_attention as bsa
from deepspeed_tpu.ops.topk import topk_mask

MARGIN = 1e-5
TOL = 2e-5
Hkv, R, D = 2, 2, 16
SHAPES = {"kernel 2 / 1": bsa.SparseSizes(4, 6, 2, 1, 1, 8, 32),
          "kernel 4 / 2": bsa.SparseSizes(4, 6, 4, 2, 1, 8, 32),
          "two leading blocks": bsa.SparseSizes(4, 6, 2, 1, 2, 8, 32)}


def _qkv(seed, T):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (1, T, Hkv * R, D), jnp.float32) * 2,
            jax.random.normal(keys[1], (1, T, Hkv, D), jnp.float32),
            jax.random.normal(keys[2], (1, T, Hkv, D), jnp.float32))


def _sz(sizes, T):
    return dict(block=sizes.block, topk=sizes.topk, kernel=sizes.kernel,
                stride=sizes.stride, init_blocks=sizes.init_blocks,
                window=sizes.window, n_blocks=-(-T // sizes.block))


def _reference_compressed(k, sizes):
    T = k.shape[0]
    J = max((T - sizes.kernel) // sizes.stride + 1, 0)
    return jnp.stack([jnp.mean(
        k[sizes.stride * j:sizes.stride * j + sizes.kernel], axis=0)
        for j in range(J)])


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_compressed_keys_are_the_windows_means(name):
    sizes = SHAPES[name]
    _, k, _ = _qkv(1, 37)
    got = bsa.compress_keys(k, sizes)[0]
    want = _reference_compressed(k[0], sizes)
    assert got.shape[0] == -(-37 // sizes.stride)
    # the complete ones; the others are visible to no query of the 37
    assert float(jnp.abs(got[:want.shape[0]] - want).max()) < 1e-6


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_block_sets_are_the_references(name):
    """(c) every (query, group) whose margin is over MARGIN has the
    reference's block set; forced blocks are always in; at most top-k."""
    sizes, T = SHAPES[name], 75
    q, k, _ = _qkv(2, T)
    t = jnp.arange(T)
    nb = -(-T // sizes.block)
    score, valid = bsa.select_blocks(
        q.reshape(1, T, Hkv, R, D), bsa.compress_keys(k, sizes), t[None],
        sizes, 1.0 / np.sqrt(D), nb)
    got = np.asarray(topk_mask(score, jnp.broadcast_to(valid, score.shape),
                               sizes.topk))[0]               # [T, Hkv, nb]
    sz = _sz(sizes, T)
    c = _reference_compressed(k[0], sizes)
    ref_score, _ = reference.block_scores(q[0].reshape(T, Hkv, R, D), c, t,
                                          sz)
    want = np.asarray(reference._block_sets(q[0].reshape(T, Hkv, R, D), c,
                                            t, sz))
    ref_score = np.asarray(ref_score)
    compared = 0
    for i in range(T):
        own = i // sizes.block
        forced = [b for b in range(own + 1) if b < sizes.init_blocks
                  or own - sizes.local_blocks < b]
        for g in range(Hkv):
            assert got[i, g, forced].all() and want[i, g, forced].all()
            assert got[i, g].sum() == want[i, g].sum() == \
                min(sizes.topk, own + 1)
            finite = ref_score[i, g][np.isfinite(ref_score[i, g])]
            chosen = finite[np.argsort(-finite)][
                :sizes.topk - len(forced)]
            rest = np.sort(finite)[::-1][sizes.topk - len(forced):]
            if len(rest) and len(chosen) and \
                    0 < chosen[-1] - rest[0] < MARGIN:
                continue        # undecided at float32
            compared += 1
            assert (got[i, g] == want[i, g]).all(), (i, g)
    assert compared > 0.9 * T * Hkv


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_a_context_of_at_most_topk_blocks_is_dense_attention(name):
    sizes = SHAPES[name]
    T = sizes.topk * sizes.block             # 24 keys: every block chosen
    q, k, v = _qkv(3, T)
    got = bsa.sparse_prefill_attention(q, k, v, sizes, q_chunk=5)
    s = jnp.einsum("bthd,bshd->bhts", q.reshape(1, T, Hkv, R, D).reshape(
        1, T, Hkv * R, D), jnp.repeat(k, R, axis=2)) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1),
                      jnp.repeat(v, R, axis=2))
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_a_decode_step_through_the_pools_is_the_prefills_last_row(name):
    """A prefill of T - 1 rows writes pages and compressed keys; the
    decode step of row T - 1 writes its key, completes a compressed key
    where one is due, selects and gathers: the row the masked prefill of
    all T gives."""
    from deepspeed_tpu.ops.paged_attention import PagedKVCache, write_paged
    sizes, page = SHAPES[name], 8
    for T in (41, 42, 58):
        q, k, v = _qkv(T, T)
        want = bsa.sparse_prefill_attention(q, k, v, sizes)[0, -1]
        pools = bsa.init_sparse_pools(2, 12, Hkv, page, D, sizes.stride,
                                      jnp.float32)
        tables = jnp.asarray([[3, 7, 1, 9, 4, 11, 2, 5]], jnp.int32)
        layer = jnp.int32(1)

        def write(pools, lengths, rows_k, rows_v):
            kv = write_paged(PagedKVCache(pools.k_pages, pools.v_pages),
                             layer, tables, lengths, rows_k, rows_v,
                             impl="jnp")
            return pools._replace(k_pages=kv.k_pages, v_pages=kv.v_pages)

        zero = jnp.zeros((1,), jnp.int32)
        pools = write(pools, zero, k[:, :T - 1], v[:, :T - 1])
        pools = pools._replace(c_pages=bsa.write_compressed_prefill(
            pools.c_pages, layer, tables,
            bsa.compress_keys(k[:, :T - 1], sizes)))
        at = jnp.full((1,), T - 1, jnp.int32)
        pools = write(pools, at, k[:, T - 1:], v[:, T - 1:])
        pools = bsa.write_compressed_decode(pools, layer, tables, at, sizes)
        got, attended = bsa.sparse_decode_attention(
            q[:, -1], pools, layer, tables, at + 1, sizes)
        assert float(jnp.abs(got[0] - want).max()) < TOL, T
        assert int(attended[0]) == (sizes.topk - 1) * sizes.block \
            + (T - 1) % sizes.block + 1
        # the other layer's pools were not touched
        assert not bool(jnp.any(pools.c_pages[0])) and \
            not bool(jnp.any(pools.k_pages[0]))


@pytest.mark.parametrize("q_chunk,spans", [(4, 4), (7, 3), (16, 2),
                                           (5, 1)])
def test_the_spans_of_a_prefill_change_nothing(q_chunk, spans):
    """However the queries are cut into steps and the steps into spans of
    keys, a query attends the same keys: a key after its chunk's span is
    after the query."""
    sizes = SHAPES["kernel 4 / 2"]
    q, k, v = _qkv(9, 75)
    want = bsa.sparse_prefill_attention(q, k, v, sizes, spans=1)
    got = bsa.sparse_prefill_attention(q, k, v, sizes, q_chunk=q_chunk,
                                       spans=spans)
    assert float(jnp.abs(got - want).max()) < 1e-6


def test_sizes_that_do_not_fit_are_refused():
    with pytest.raises(AssertionError):
        bsa.SparseSizes(4, 2, 2, 1, 1, 8, 32).check()   # 3 forced > top-2
    with pytest.raises(AssertionError):
        bsa.SparseSizes(4, 6, 3, 2, 1, 8, 32).check()   # kernel % stride
    with pytest.raises(AssertionError):
        bsa.SparseSizes(4, 6, 2, 1, 1, 8, 32).check(page_size=6)
