"""Block-sparse grouped-query attention over a compressed-key cache (the
``minicpm4`` mixer: InfLLM v2): a query attends the keys of at most
``topk`` blocks of ``block`` keys, picked by what its own heads make of a
coarse view of the context, and nothing else.

The coarse view: for key/value head ``g`` one COMPRESSED key every
``stride`` tokens, ``c_j = mean(k_g[stride j : stride j + kernel])`` over
the keys as the pages hold them, visible to the query at position ``t``
once its last key is, ``stride j + kernel - 1 <= t``.  Each of the group's
query heads takes a softmax over the visible compressed keys; the heads'
sum ``P_j`` scores compressed key ``j``, and block ``b`` scores the
largest ``P_j`` of the compressed keys that overlap it
(:func:`select_blocks`).  Forced in whatever they score: the first
``init_blocks`` blocks and the ``window / block`` blocks up to the
query's own.  The query's blocks are the forced ones and the
highest-scoring others up to ``topk`` in all, ties to the lower index
(``ops/topk.py``): one set for all heads of a group, another for each
group.  Attention is the causal softmax over those blocks' keys.  A
context of at most ``topk`` blocks selects every block, so the result is
dense attention's; the model's own switch to dense attention under
``dense_len`` is the caller's (``models/transformer.py
mix_sparse_paged``).

Precision: compressed keys in the pages' dtype (their mean summed in
float32); the selection's scores, softmax and group sum float32 from the
queries and compressed keys as they are; the attention's scores and
softmax float32, its probabilities times the values in the values' dtype.

On the serving path the compressed keys live in a pool of their own
beside the K/V pages (:class:`SparseKVCache`), under the SAME block
tables: compressed key ``j`` of key/value head ``g`` lies in the page of
its first token, row ``g x (page / stride) + j % (page / stride)``, so a
page that is freed or reused takes its
compressed keys with it.  A prefill writes every one its rows start
(:func:`write_compressed_prefill`); a decode step writes the one its token
completes, from the last ``kernel`` keys of the slot
(:func:`write_compressed_decode`), ahead of its own selection, so a
compressed key is never visible before its writer has run.  A decode
step reads the compressed keys of its table and ``topk`` blocks of K/V a
(sequence, key/value head), gathered out of the pool by block
(:func:`sparse_decode_attention`); a prefill from an empty context
applies the selection as a mask over the keys it brings, a chunk of
queries at a time (:func:`sparse_prefill_attention`).  Everything here is
plain XLA under the ``jax.named_scope``s its caller opens.
"""

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.topk import topk_indices, topk_mask

PREFILL_QUERIES = 256       # queries a step of the masked prefill
PREFILL_SPANS = 4           # scans of its steps, each over the keys so far


class SparseSizes(NamedTuple):
    """The sizes of the selection (static)."""
    block: int          # keys a block
    topk: int           # blocks a query attends, forced ones included
    kernel: int         # keys a compressed key averages
    stride: int         # tokens between two compressed keys
    init_blocks: int    # leading blocks forced in
    window: int         # keys up to the query's own whose blocks are forced
    dense_len: int      # a context under it attends densely (the caller's)

    @property
    def per_block(self):
        """Compressed keys that START in a block."""
        return self.block // self.stride

    @property
    def first_overlap(self):
        """Of the compressed keys that overlap block ``b``, the first's
        offset from ``b x per_block`` (negative: it starts before it)."""
        return 1 - -(-self.kernel // self.stride)

    @property
    def local_blocks(self):
        return self.window // self.block

    def check(self, page_size=None):
        assert self.block % self.stride == 0 and \
            self.kernel % self.stride == 0 and \
            self.window % self.block == 0 and self.topk > 0, self
        assert self.init_blocks + self.local_blocks <= self.topk, (
            f"{self.init_blocks} + {self.local_blocks} forced blocks do "
            f"not fit in top-{self.topk}")
        if page_size is not None:
            assert page_size % self.block == 0, (
                f"a page of {page_size} rows does not hold whole blocks "
                f"of {self.block}")


class SparseKVCache(NamedTuple):
    """The pools of block-sparse attention layers, stacked over them:
    keys and values [L, P, Hkv, page, D] and the compressed keys
    ``c_pages`` [L, P, Hkv x page / stride, D] (a head's compressed keys
    of a page one after another, the heads one after another: rows
    written and read by whole index, which a [.., Hkv, page / stride, D]
    pool's scatter and gather each re-laid their own way), all under one
    block table."""
    k_pages: jnp.ndarray
    v_pages: jnp.ndarray
    c_pages: jnp.ndarray


def init_sparse_pools(layers, num_pages, kv_heads, page_size, head_dim,
                      stride, dtype):
    shape = (layers, num_pages, kv_heads, page_size, head_dim)
    return SparseKVCache(
        jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
        jnp.zeros((layers, num_pages, kv_heads * (page_size // stride),
                   head_dim), dtype))


# ----------------------------------------------------------------------
# the compressed keys
# ----------------------------------------------------------------------
def compress_keys(k, sizes):
    """k: [B, T, Hkv, D] from position 0 -> the compressed keys [B, J,
    Hkv, D], J = ceil(T / stride), in ``k``'s dtype: ``c_j`` the mean of
    rows ``stride j .. stride j + kernel - 1`` (rows past T count as
    zeros: such a ``c_j`` is complete, and visible, for no query of the
    T)."""
    B, T, Hkv, D = k.shape
    st, parts = sizes.stride, sizes.kernel // sizes.stride
    J = -(-T // st)
    k = jnp.pad(k, ((0, 0), (0, (J + parts - 1) * st - T), (0, 0), (0, 0)))
    sums = jnp.sum(k.reshape(B, J + parts - 1, st, Hkv, D).astype(
        jnp.float32), axis=2)
    total = sum(sums[:, p:p + J] for p in range(parts))
    return (total / sizes.kernel).astype(k.dtype)


def write_compressed_prefill(c_pages, layer, block_tables, c):
    """The compressed keys ``c`` [B, J, Hkv, D] of a prefill from
    position 0 into layer ``layer`` of ``c_pages`` [L, P, Hkv x page /
    stride, D], whole pages through the table's first columns."""
    B, J, Hkv, D = c.shape
    per_page = c_pages.shape[2] // Hkv
    n = -(-J // per_page)
    c = jnp.pad(c, ((0, 0), (0, n * per_page - J), (0, 0), (0, 0)))
    rows = jnp.swapaxes(c.reshape(B, n, per_page, Hkv, D), 2, 3)
    return c_pages.at[layer, block_tables[:, :n]].set(
        rows.reshape(B, n, Hkv * per_page, D).astype(c_pages.dtype))


def write_compressed_decode(cache: SparseKVCache, layer, block_tables,
                            lengths, sizes):
    """After a decode step wrote its key at position ``lengths`` [B]: the
    compressed key that key completes (every ``stride``-th token from
    ``kernel - 1`` on), the mean of the slot's last ``kernel`` keys read
    back out of the pages, written to its rows; a row whose token
    completes none (an idle slot's among them) writes to the scratch page
    0.  A completed key's ``kernel`` keys are ``kernel / stride`` whole
    runs of ``stride`` rows, read as such (a gather by single rows made
    the compiler re-lay the whole K pool)."""
    L, P, Hkv, page, D = cache.k_pages.shape
    ks, st = sizes.kernel, sizes.stride
    per_page = page // st
    done = ((lengths + 1) % st == 0) & (lengths + 1 >= ks)
    first = jnp.maximum(lengths + 1 - ks, 0)    # its first token
    run = first[:, None] // st + jnp.arange(ks // st)[None, :]   # [B, m]
    pages = jnp.take_along_axis(block_tables, run // per_page, axis=1)
    head = jnp.arange(Hkv)[None, None, :]
    keys = cache.k_pages.reshape(L, P, Hkv, per_page, st, D)[
        layer, pages[..., None], head, (run % per_page)[..., None]]
    c_new = jnp.mean(keys.astype(jnp.float32), axis=(1, 3))  # [B, Hkv, D]
    target = jnp.where(done, pages[:, 0], 0)[:, None]
    row = jnp.where(done, run[:, 0] % per_page, 0)[:, None] \
        + per_page * head[0]
    return cache._replace(c_pages=cache.c_pages.at[layer, target, row].set(
        c_new.astype(cache.c_pages.dtype)))


# ----------------------------------------------------------------------
# the selection
# ----------------------------------------------------------------------
def select_blocks(q, c, t, sizes, scale, n_blocks):
    """Block scores of queries at positions ``t`` [B, T].  q: [B, T, Hkv,
    R, D], the ``R`` query heads of each key/value head; c: [B, J, Hkv,
    D], compressed key ``j`` of tokens ``stride j ..``.  Returns (scores
    [B, T, Hkv, n_blocks] float32, ``+inf`` on a forced block; valid [B,
    T, 1, n_blocks]: the blocks that hold a key at or before ``t``).  The
    ``topk`` largest scores among the valid, ties to the lower index, are
    the query's blocks."""
    J = c.shape[1]
    f32 = jnp.float32
    dots = jnp.einsum("bthrd,bjhd->bthrj", q, c,
                      preferred_element_type=f32) * scale
    visible = (jnp.arange(J) * sizes.stride + sizes.kernel - 1
               <= t[..., None])[:, :, None, None, :]         # [B,T,1,1,J]
    # softmax over the visible compressed keys by hand (jax.nn.softmax of
    # a masked tensor compiles to a row-wide reduce-window on the TPU); a
    # query that sees none scores zeros
    top = jnp.max(jnp.where(visible, dots, -1e30), axis=-1, keepdims=True)
    e = jnp.where(visible, jnp.exp(dots - top), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    P = jnp.where(visible[:, :, :, 0], jnp.sum(p, axis=3), -jnp.inf)
    # block b: the largest P_j of j = per_block b + first_overlap ..
    # per_block (b + 1) - 1
    r, low = sizes.per_block, sizes.first_overlap
    span = r * n_blocks
    P = jnp.pad(P[..., :span], ((0, 0),) * 3 + ((-low, max(span - J, 0)),),
                constant_values=-jnp.inf)
    score = functools.reduce(jnp.maximum, [
        P[..., s - low:s - low + r * (n_blocks - 1) + 1:r]
        for s in range(low, r)])
    b = jnp.arange(n_blocks)
    own = (t // sizes.block)[..., None]                          # [B, T, 1]
    forced = (b < sizes.init_blocks) | ((b > own - sizes.local_blocks)
                                        & (b <= own))
    score = jnp.where(forced[:, :, None], jnp.inf, score)
    return score, (b <= own)[:, :, None]


# ----------------------------------------------------------------------
# the attention paths
# ----------------------------------------------------------------------
def sparse_prefill_attention(q, k, v, sizes, scale=None,
                             q_chunk=PREFILL_QUERIES, c=None,
                             spans=PREFILL_SPANS):
    """Block-sparse attention of T tokens over themselves from position 0
    (a whole sequence, or a prefill from an empty context): each query's
    own selection applied as a mask over the keys up to its chunk's span,
    ``q_chunk`` queries a step.  q: [B, T, H, D]; k, v: [B, T, Hkv, D] ->
    [B, T, H, D]; ``c``: ``compress_keys(k, sizes)`` where the caller has
    it.  The chunks run as ``spans`` scans one after another, each over the
    keys up to its last query alone (a causal query sees none after it):
    four spans compute five eighths of the products one span over all T
    keys would.  Rows past a sequence's tokens (a bucket's padding) come
    after them, so no token attends one."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    R = H // Hkv
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    c = compress_keys(k, sizes) if c is None else c
    Cq = min(int(q_chunk), T)
    n_chunks = -(-T // Cq)
    S = -(-n_chunks * Cq // sizes.block) * sizes.block
    k, v = (jnp.pad(a, ((0, 0), (0, S - T), (0, 0), (0, 0)))
            for a in (k, v))
    qc = jnp.pad(q, ((0, 0), (0, n_chunks * Cq - T), (0, 0), (0, 0)))
    qc = jnp.moveaxis(qc.reshape(B, n_chunks, Cq, Hkv, R, D), 1, 0)

    def span(first, last):
        """Chunks ``first`` .. ``last`` - 1 over the keys up to theirs."""
        n_blocks = -(-last * Cq // sizes.block)
        keys_k, keys_v = (a[:, :n_blocks * sizes.block] for a in (k, v))
        seen_c = c[:, :n_blocks * sizes.per_block]
        kpos = jnp.arange(n_blocks * sizes.block)

        def chunk(_, inp):
            q_i, i = inp
            t = jnp.broadcast_to(i * Cq + jnp.arange(Cq)[None, :], (B, Cq))
            with jax.named_scope("block_select"):
                score, valid = select_blocks(q_i, seen_c, t, sizes, scale,
                                             n_blocks)
                blocks = topk_mask(
                    score, jnp.broadcast_to(valid, score.shape),
                    sizes.topk)                           # [B, Cq, Hkv, nb]
            with jax.named_scope("sparse_attn"):
                keys = jnp.repeat(blocks, sizes.block, axis=-1) \
                    & (kpos <= t[..., None])[:, :, None]  # [B, Cq, Hkv, S']
                s = jnp.einsum("bthrd,bshd->bhrts", q_i, keys_k,
                               preferred_element_type=jnp.float32) * scale
                s = jnp.where(jnp.moveaxis(keys, 1, 2)[:, :, None], s,
                              -1e30)
                p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
                o = jnp.einsum("bhrts,bshd->bthrd", p.astype(v.dtype),
                               keys_v, preferred_element_type=jnp.float32)
                o = o / jnp.moveaxis(jnp.sum(p, axis=-1), 3, 1)[..., None]
            return None, o.astype(q.dtype)

        return jax.lax.scan(chunk, None, (qc[first:last],
                                          jnp.arange(first, last)))[1]

    per = -(-n_chunks // max(int(spans), 1))
    out = jnp.concatenate([span(first, min(first + per, n_chunks))
                           for first in range(0, n_chunks, per)])
    return jnp.moveaxis(out, 0, 1).reshape(B, n_chunks * Cq, H, D)[:, :T]


def sparse_decode_attention(q, cache: SparseKVCache, layer, block_tables,
                            context, sizes, scale=None):
    """One query a sequence over its selected blocks, read out of the
    pool.  q: [B, H, D], the token at position ``context - 1`` (its key
    and, if it completes one, its compressed key already written);
    ``context`` [B].  Reads the compressed keys of the table and ``topk``
    blocks of K and V a (sequence, key/value head).  Returns (out [B, H,
    D], attended [B]: the keys a head of the sequence attended)."""
    B, H, D = q.shape
    L, P, Hkv, page, _ = cache.k_pages.shape
    R, bs = H // Hkv, sizes.block
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    n = block_tables.shape[1]
    t = context[:, None] - 1                                     # [B, 1]
    q5 = q.reshape(B, 1, Hkv, R, D)
    with jax.named_scope("block_select"):
        c = cache.c_pages[layer, block_tables]  # [B, n, Hkv x per_page, D]
        c = jnp.moveaxis(c.reshape(B, n, Hkv, -1, D), 2, 3).reshape(
            B, -1, Hkv, D)
        n_blocks = n * page // bs
        score, valid = select_blocks(q5, c, t, sizes, scale, n_blocks)
        idx, live = topk_indices(
            score[:, 0], jnp.broadcast_to(valid[:, 0], score[:, 0].shape),
            sizes.topk)                                     # [B, Hkv, K]
    with jax.named_scope("sparse_attn"):
        first = idx * bs
        pages = jnp.where(live, jnp.take_along_axis(
            jnp.broadcast_to(block_tables[:, None], (B, Hkv, n)),
            first // page, axis=2), 0)
        part = (first % page) // bs
        head = jnp.arange(Hkv)[None, :, None]

        def blocks(pool):       # [L, P, Hkv, page, D] -> [B, Hkv, K, bs, D]
            return pool.reshape(L, P, Hkv, page // bs, bs, D)[
                layer, pages, head, part]

        k_sel, v_sel = blocks(cache.k_pages), blocks(cache.v_pages)
        s = jnp.einsum("bhrd,bhkjd->bhrkj", q5[:, 0], k_sel,
                       preferred_element_type=jnp.float32) * scale
        seen = live[..., None] & (first[..., None] + jnp.arange(bs)
                                  <= t[:, :, None, None])   # [B,Hkv,K,bs]
        s = jnp.where(seen[:, :, None], s, -1e30)
        p = jnp.exp(s - jnp.max(s, axis=(-2, -1), keepdims=True))
        o = jnp.einsum("bhrkj,bhkjd->bhrd", p.astype(v_sel.dtype), v_sel,
                       preferred_element_type=jnp.float32)
        o = o / jnp.sum(p, axis=(-2, -1))[..., None]
    # both groups attend as many keys: min(topk, valid) blocks, the
    # query's own part-filled
    attended = jnp.sum(seen[:, 0], axis=(-2, -1), dtype=jnp.int32)
    return o.reshape(B, H, D).astype(q.dtype), attended
