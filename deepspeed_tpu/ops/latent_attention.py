"""Latent (MLA) attention over a paged pool, with a learned key selection.

The cache of a latent-attention model holds ONE entry a token and layer,
``[c_kv | k_rope]`` (the normed latent and the one rotary key all heads
share), and nothing per head; a second pool of the same pages holds the
selection's own key (the "indexer" of DeepSeek-V3.2 / GLM-5), narrower:

  latent_pages: [L, num_pages, page_size, kv_rank + rope_dim -> lanes]
  index_pages:  [L, num_pages, page_size, index_dim]

Both are addressed by the block tables and lengths of
``ops/paged_attention.py`` (one allocator, one page id, two widths).  An
entry's row is padded with zeros to whole lane tiles of 128 (576 values in
a row of 640): with a minor dimension that is no multiple of 128 the TPU
compiler keeps the pool in another layout than the scatter and the gathers
want, and copies the whole pool into and out of every dispatch (compiled
for a described v5e, PR 32: two 1.4 GB copies a decode step).

Two arithmetic paths over the same entries:

* a prefill (T > 1, from an empty context) decompresses the keys and
  values of the tokens it brings (``k = [c_kv W_uk | k_rope]``,
  ``v = c_kv W_uv``) and attends over them under a mask: causal AND
  selected (:func:`prefill_attention`, blocked over queries);
* a decode step (T = 1) gathers the selected entries of its context out of
  the pool and attends in the latent space with absorbed weights
  (``q_abs = q_nope W_uk^T``, ``o = (sum p c_kv) W_uv``:
  :func:`decode_attention`), so nothing is decompressed.

A model WITHOUT a selection (``index_topk`` unset: DeepSeek-V3, Kimi-K2)
is the dense case of both: its pools hold no index keys (an
``index_pages`` of zero width: no bytes, no write), nothing is scored or
sorted, and every query attends over its whole context.  Its prefill may
start from entries ALREADY IN THE POOL (a chunk of a longer prompt):
:func:`context_attention` walks the cached entries through the block table
a block of keys at a time, decompresses each block once and folds it into
the running softmax of every block of queries, and
:func:`prefill_attention` goes on from that state over the chunk's own
keys.  Its decode step (:func:`dense_decode_attention`) reads the
context's entries in page order, masked by the lengths, as far as the
longest context of the batch reaches.

Which function serves which model (``models/transformer.py``):

* a model WITH a selection: :func:`prefill_attention` (its prefill, from
  an empty context) and :func:`decode_attention`, whatever the backend;
* the whole-sequence path of any latent model (``mix_latent_whole``: the
  trainer's forward, the references' comparisons):
  :func:`prefill_attention`;
* a model WITHOUT a selection, served: :func:`dense_decode_attention` for
  a decode step whatever the backend; for a prefill, where the engine's
  backend resolves to "pallas" (a TPU), the kernel
  ``ops/pallas/latent_attention.py`` ``latent_prefill_attention``, which
  walks cached entries and chunk alike out of the pool with its scores in
  VMEM (PR 45), and elsewhere :func:`context_attention` +
  :func:`prefill_attention`, which are also that kernel's oracle
  (``tests/unit/test_latent_prefill_kernel.py``).

The selection (:func:`index_scores`, :func:`topk_mask`): query ``t`` keeps
the ``min(k, t + 1)`` causal keys of largest ``I[t, s] = sum_h w[t, h] *
relu(q_i[t, h] . k_i[s])``, by value, ties to the lower index
(``jax.lax.top_k``'s rule, which the decode step uses directly).

Everything in this file is plain XLA under ``jax.named_scope``s the
caller opens (``latent_attn``, ``select``, ``latent_ctx``).
"""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.paged_attention import _row_targets
# the order-exact top-k, shared with the block selection of
# ops/block_sparse_attention.py
from deepspeed_tpu.ops.topk import (_sortable, topk_indices,  # noqa: F401
                                    topk_mask)

PREFILL_BLOCK_Q = 256      # queries a block of the prefill's attention
PREFILL_BLOCK_K = 512      # keys a step of its running softmax
# pages a step of the dense decode's running softmax: 2,048 entries at
# the page of 128, a prefill chunk's worth; the one value run, not swept
DECODE_BLOCK_PAGES = 16
LANES = 128                # an entry's row is whole tiles of this many


class LatentKVCache(NamedTuple):
    latent_pages: jnp.ndarray   # [L, P, page, row of kv_rank + rope_dim]
    index_pages: jnp.ndarray    # [L, P, page, index_dim]; width 0
    #                             for a model without a selection


def init_latent_pools(n_layers, num_pages, page_size, entry_dim, index_dim,
                      dtype=jnp.bfloat16) -> LatentKVCache:
    row = -(-entry_dim // LANES) * LANES
    return LatentKVCache(
        latent_pages=jnp.zeros((n_layers, num_pages, page_size, row), dtype),
        index_pages=jnp.zeros((n_layers, num_pages, page_size, index_dim),
                              dtype))


def write_latent(cache: LatentKVCache, layer, block_tables, lengths, entry,
                 index_key) -> LatentKVCache:
    """Write rows ``entry`` [B, T, E] and ``index_key`` [B, T, Di] from
    ``lengths`` on into layer ``layer`` of both stacked pools, in place
    (an XLA scatter on the donated stack; no kernel reads these pools, so
    nothing re-lays them).  ``index_key`` None: a model without a
    selection, whose index pool has no width and is left alone."""
    page_idx, offset = _row_targets(block_tables, lengths, entry.shape[1],
                                    cache.latent_pages.shape[2])
    entry = jnp.pad(entry, ((0, 0), (0, 0), (
        0, cache.latent_pages.shape[-1] - entry.shape[-1])))
    return LatentKVCache(
        latent_pages=cache.latent_pages.at[layer, page_idx, offset].set(
            entry.astype(cache.latent_pages.dtype)),
        index_pages=cache.index_pages if index_key is None else
        cache.index_pages.at[layer, page_idx, offset].set(
            index_key.astype(cache.index_pages.dtype)))


class RopeYarn(NamedTuple):
    """``rope_scaling`` of type ``yarn`` as published (DeepSeek-V3 /
    Kimi-K2 ``config.json``): the context is stretched ``factor`` times
    over ``original_positions`` by dividing the slow rotary frequencies,
    and the softmax is sharpened to make up for the longer rows."""
    factor: float
    original_positions: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float

    def magnitude(self, mscale):
        """``0.1 mscale ln(factor) + 1`` (1 without a stretch)."""
        if self.factor <= 1:
            return 1.0
        return 0.1 * mscale * math.log(self.factor) + 1.0

    @property
    def rotary_magnitude(self):
        """What cos and sin are multiplied by."""
        return self.magnitude(self.mscale) / self.magnitude(
            self.mscale_all_dim)

    @property
    def softmax_factor(self):
        """What the softmax scale is multiplied by."""
        return self.magnitude(self.mscale_all_dim) ** 2 \
            if self.mscale_all_dim else 1.0

    def inv_freq(self, dim, theta):
        """The ``dim // 2`` rotary frequencies: ``theta**(-2i / dim)``
        kept where a frequency turns more than ``beta_fast`` times over
        the original positions, divided by ``factor`` where it turns
        fewer than ``beta_slow`` times, a linear ramp over the indices
        between (float64 on the host: data of the program)."""
        half = dim // 2
        freq = theta ** (-np.arange(half, dtype=np.float64) / half)

        def turns_at(turns):    # the index whose frequency turns so often
            return dim * math.log(self.original_positions
                                  / (turns * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = min(max(math.floor(turns_at(self.beta_fast)), 0), half - 1)
        high = min(max(math.ceil(turns_at(self.beta_slow)), 0), half - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
        return tuple(float(v) for v in
                     freq * (1.0 - ramp) + freq / self.factor * ramp)


def rope_interleaved(x, positions, theta, inv_freq=None, magnitude=1.0):
    """Rotary embedding on pairs ``(2i, 2i+1)`` of the last axis
    (``rope_interleave: true``), angle ``position * theta**(-2i / D)``, or
    ``position * inv_freq[i]`` where the model scales its frequencies
    (:class:`RopeYarn`), cos and sin times ``magnitude``;
    x: [B, T, ..., D], positions: [B, T]."""
    D = x.shape[-1]
    half = D // 2
    if inv_freq is None:
        freqs = jnp.exp(-np.log(theta) * jnp.arange(half, dtype=jnp.float32)
                        / half)
    else:
        assert len(inv_freq) == half, (len(inv_freq), half)
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ----------------------------------------------------------------------
# the selection
# ----------------------------------------------------------------------
def index_scores(q_i, w_i, k_i):
    """``I[b, t, s] = sum_h w_i[b, t, h] * relu(q_i[b, t, h] . k_i[b, s])``
    in float32; q_i: [B, T, Hi, Di], w_i: [B, T, Hi], k_i: [B, S, Di]."""
    dots = jnp.einsum("bthd,bsd->bths", q_i, k_i,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bths,bth->bts", jax.nn.relu(dots),
                      w_i.astype(jnp.float32))


# ----------------------------------------------------------------------
# the attention paths
# ----------------------------------------------------------------------
def _fold(carry, s, values):
    """One step of a running softmax: fold the masked scores ``s`` [B, H,
    q, k] (float32) and their ``values`` [B, k, H, dv] into ``carry`` =
    (largest score, sum of weights, weighted values) a query and head."""
    top, total, acc = carry
    new_top = jnp.maximum(top, jnp.max(s, axis=-1))
    p = jnp.exp(s - new_top[..., None])
    keep = jnp.exp(top - new_top)
    return (new_top, total * keep + jnp.sum(p, axis=-1),
            acc * keep[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(values.dtype), values,
                preferred_element_type=jnp.float32))


def _empty_state(shape, dv):
    """The running softmax before any key; ``shape``: [..., B, H, q]."""
    return (jnp.full(shape, -1e30, jnp.float32),
            jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape + (dv,), jnp.float32))


def _query_blocks(x, bq):
    """[B, T, ...] -> [T / bq (rounded up), B, bq, ...], zero-padded."""
    B, T = x.shape[:2]
    x = jnp.pad(x, ((0, 0), (0, (-T) % bq)) + ((0, 0),) * (x.ndim - 2))
    return jnp.moveaxis(
        x.reshape((B, x.shape[1] // bq, bq) + x.shape[2:]), 1, 0)


def _context_walk(cached, page_size, block_k):
    """How :func:`context_attention` walks ``cached`` entries of the pool
    (the longest cached context of its batch; traced there, a number on
    the host): (pages a block, keys a block, blocks)."""
    pages = max(1, block_k // page_size)
    return pages, pages * page_size, -(-cached // (pages * page_size))


def context_entries(cached, page_size, block_k=PREFILL_BLOCK_K):
    """Pool entries :func:`context_attention` walks for a chunk that
    finds ``cached`` entries of its sequence in the pool: whole blocks of
    keys (the host's reckoning, for the engine's report, by the walk's
    own arithmetic)."""
    _, bk, blocks = _context_walk(int(cached), page_size, block_k)
    return blocks * bk


def context_attention(q, cache: LatentKVCache, layer, block_tables, lengths,
                      w_kvb, rope_dim, scale, real=None,
                      block_q=PREFILL_BLOCK_Q, block_k=PREFILL_BLOCK_K):
    """The part of a chunk's attention that reads what was in the pool
    BEFORE the chunk: T queries q [B, T, H, dn + dr] over the ``lengths``
    [B] cached entries of each sequence, every one of them (no selection).
    The entries are walked through the block table a block of ``block_k``
    keys (whole pages) at a time, as far as the longest cached context
    reaches; a block is decompressed ONCE (``[k_nope | v] = c_kv w_kvb``,
    w_kvb: [R, H, dn + dv]; the one rotary key of ``rope_dim`` values
    broadcast to the heads) and folded into the running softmax of each
    block of ``block_q`` queries, so that neither the decompressed context
    nor a [T, S] tensor of all heads is ever live.  Blocks of queries
    that hold no ``real`` [B, T] token are skipped.  Returns the running
    softmax's state a block of queries, (top, total, acc) of shapes [nb,
    B, H, bq], [nb, B, H, bq], [nb, B, H, bq, dv] in float32: what
    :func:`prefill_attention` starts from (``state``; the same
    ``block_q``).  With nothing cached it is the empty state, and the
    chunk is a fresh prefill."""
    B, T, H, dk = q.shape
    R, dn = w_kvb.shape[0], dk - rope_dim
    dv = w_kvb.shape[-1] - dn
    pages, bk, n_blocks = _context_walk(
        jnp.max(lengths), cache.latent_pages.shape[2], block_k)
    bq = min(block_q, T)
    tables = jnp.pad(block_tables,
                     ((0, 0), (0, (-block_tables.shape[1]) % pages)))
    if real is None:
        real = jnp.ones((B, T), bool)
    blocks = (_query_blocks(q, bq), _query_blocks(real, bq))

    def over_block(j, state):
        entries = cache.latent_pages[
            layer, jax.lax.dynamic_slice_in_dim(tables, j * pages, pages, 1)
        ].reshape(B, bk, -1)
        kv = jnp.einsum("bkr,rhd->bkhd", entries[..., :R], w_kvb)
        keys = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            entries[:, :, None, R:R + rope_dim], (B, bk, H, rope_dim))], -1)
        values = kv[..., dn:]
        cached = (j * bk + jnp.arange(bk))[None, :] < lengths[:, None]

        def fold(block):
            qb, carry = block
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, keys,
                           preferred_element_type=jnp.float32) * scale
            return _fold(carry, jnp.where(cached[:, None, None], s, -1e30),
                         values)

        def one(block):
            qb, realb, carry = block
            return jax.lax.cond(jnp.any(realb), fold, lambda b: b[1],
                                (qb, carry))

        return jax.lax.map(one, blocks + (state,))

    return jax.lax.fori_loop(
        0, n_blocks, over_block,
        _empty_state((blocks[0].shape[0], B, H, bq), dv))


def prefill_attention(q, k, v, q_i, w_i, k_i, positions, topk, scale,
                      real=None, block_q=PREFILL_BLOCK_Q,
                      block_k=PREFILL_BLOCK_K, state=None):
    """Causal attention of T tokens over themselves, each query over its
    selected keys.  q, k: [B, T, H, dk]; v: [B, T, H, dv]; q_i: [B, T, Hi,
    Di]; w_i: [B, T, Hi]; k_i: [B, T, Di]; positions: [B, T], rising along
    T.  Blocked over queries, and for each block of queries over the
    blocks of keys that lie at or before it (a running softmax; the loop's
    trip count is the causal prefix, so the upper triangle is never
    computed), so that no [T, T] tensor of all heads is live.  ``real``
    [B, T] (all, without it) marks the queries that are tokens: a bucket's
    padding selects nothing, is not counted, and a block of it is skipped.
    ``q_i`` None: no selection, every causal key (``w_i``, ``k_i`` and
    ``topk`` are not read).  ``state``: the running softmax each block of
    queries starts from (:func:`context_attention`'s, over what the pool
    held before these tokens); the empty one without it.
    Returns (out [B, T, H, dv], keys attended, causal keys) — the counts
    of THESE tokens' keys, summed over the real queries, int32."""
    B, T = positions.shape
    H, dv = v.shape[2], v.shape[3]
    bq, bk = min(block_q, T), min(block_k, T)
    pad_q, pad_k = (-T) % bq, (-T) % bk
    dense = q_i is None

    keys = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    values = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    # the first position of each block of keys: a block of queries needs
    # the blocks that begin at or before its last query
    begins = jnp.min(positions[:, ::bk], axis=0)

    if real is None:
        real = jnp.ones((B, T), bool)
    if dense:       # nothing of an indexer's to cut into blocks
        q_i = w_i = jnp.zeros((B, T, 0), q.dtype)

    def causal_keys(posb, realb):
        return (positions[:, None, :] <= posb[:, :, None]) \
            & realb[:, :, None]                                  # [B,bq,T]

    def attend(block):
        qb, qib, wib, posb, realb = block[:5]
        if dense:
            mask = causal = causal_keys(posb, realb)
            padded = jnp.pad(mask, ((0, 0), (0, 0), (0, pad_k)))
        else:
            with jax.named_scope("select"):
                causal = causal_keys(posb, realb)
                mask = topk_mask(index_scores(qib, wib, k_i), causal, topk)
                padded = jnp.pad(mask, ((0, 0), (0, 0), (0, pad_k)))

        def over_keys(j, carry):
            kj = jax.lax.dynamic_slice_in_dim(keys, j * bk, bk, 1)
            vj = jax.lax.dynamic_slice_in_dim(values, j * bk, bk, 1)
            mj = jax.lax.dynamic_slice_in_dim(padded, j * bk, bk, 2)
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kj,
                           preferred_element_type=jnp.float32) * scale
            return _fold(carry, jnp.where(mj[:, None], s, -1e30), vj)

        with jax.named_scope("latent_attn"):
            # a row's masked scores in a block before its first real key
            # count as exp(0) until a real one arrives and scales them to
            # nothing; every row has one (itself; a padded row, which
            # selects nothing, comes out as an average and is cut off)
            start = _empty_state((B, H, bq), dv) if state is None \
                else block[5]
            _, total, acc = jax.lax.fori_loop(
                0, jnp.sum(begins <= jnp.max(posb)), over_keys, start)
            out = jnp.moveaxis(acc / total[..., None], 1, 2).astype(v.dtype)
        return out, jnp.sum(mask, axis=(1, 2)), jnp.sum(causal, axis=(1, 2))

    def skip(block):
        return (jnp.zeros((B, bq, H, dv), v.dtype),
                jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32))

    def one(block):
        return jax.lax.cond(jnp.any(block[4]), attend, skip, block)

    out, attended, context = jax.lax.map(
        one, tuple(_query_blocks(x, bq) for x in (q, q_i, w_i, positions,
                                                  real))
        + (() if state is None else (state,)))
    out = jnp.moveaxis(out, 0, 1).reshape((B, T + pad_q) + out.shape[3:])
    return (out[:, :T], jnp.sum(attended).astype(jnp.int32),
            jnp.sum(context).astype(jnp.int32))


def dense_decode_attention(q_abs, q_rope, cache: LatentKVCache, layer,
                           block_tables, lengths, scale, real=None,
                           block_pages=DECODE_BLOCK_PAGES):
    """One new token a sequence (already written) over EVERY entry of its
    context, in the latent space: no index pool, no scores, no top-k.
    q_abs: [B, H, R] (``q_nope W_uk^T``); q_rope: [B, H, dr]; ``lengths``
    [B] counts the new token.  The entries are read through the block
    table in page order, ``block_pages`` pages a step of a running
    softmax, as far as the longest context of the batch reaches, each
    masked by its sequence's length.  A row of the pool is ``[c_kv |
    k_rope | zeros]``: the query ``[q_abs | q_rope | zeros]`` meets whole
    rows and the weighted sum is taken over whole rows and cut to the
    latent after, so nothing of a gathered block is sliced.  ``real`` [B]
    (all, without it): the slots that hold a sequence, for the count.
    Returns (o_lat [B, H, R], keys in context)."""
    B, H, R = q_abs.shape
    page, row = cache.latent_pages.shape[2:]
    pages = min(block_pages, block_tables.shape[1])
    bk = pages * page
    tables = jnp.pad(block_tables,
                     ((0, 0), (0, (-block_tables.shape[1]) % pages)))
    query = jnp.concatenate([q_abs, q_rope.astype(q_abs.dtype)], axis=-1)
    query = jnp.pad(query, ((0, 0), (0, 0), (0, row - query.shape[-1])))

    def over_block(j, carry):
        entries = cache.latent_pages[
            layer, jax.lax.dynamic_slice_in_dim(tables, j * pages, pages, 1)
        ].reshape(B, bk, row)
        s = jnp.einsum("bhe,bke->bhk", query, entries,
                       preferred_element_type=jnp.float32) * scale
        live = (j * bk + jnp.arange(bk))[None, :] < lengths[:, None]
        # the heads ride the running softmax's query axis, under one
        # "head" that every row of the block belongs to
        return _fold(carry, jnp.where(live[:, None], s, -1e30)[:, None],
                     entries[:, :, None])

    _, total, acc = jax.lax.fori_loop(
        0, -(-jnp.max(lengths) // bk), over_block,
        _empty_state((B, 1, H), row))
    total, acc = total[:, 0], acc[:, 0]
    o_lat = (acc[..., :R] / total[..., None]).astype(q_abs.dtype)
    context = lengths if real is None else jnp.where(real, lengths, 0)
    return o_lat, jnp.sum(context).astype(jnp.int32)


def decode_attention(q_abs, q_rope, q_i, w_i, cache: LatentKVCache, layer,
                     block_tables, lengths, topk, scale, real=None):
    """One new token a sequence (already written) over the selected
    entries of its context, in the latent space.  q_abs: [B, H, R]
    (``q_nope W_uk^T``); q_rope: [B, H, dr]; q_i: [B, Hi, Di]; w_i: [B,
    Hi]; ``lengths`` [B] counts the new token; ``real`` [B] (all, without
    it) marks the slots that hold a sequence: the counts leave the others
    out.  Returns (o_lat [B, H, R], keys attended, keys in context)."""
    B, H, R = q_abs.shape
    page = cache.latent_pages.shape[2]
    S = block_tables.shape[1] * page
    with jax.named_scope("select"):
        # the whole context's index keys, a page at a time: [B, S, Di]
        k_i = cache.index_pages[layer, block_tables].reshape(B, S, -1)
        valid = jnp.arange(S)[None, :] < lengths[:, None]
        scores = index_scores(q_i[:, None], w_i[:, None], k_i)[:, 0]
        idx, live = topk_indices(scores, valid, topk)            # [B, K]
    with jax.named_scope("latent_attn"):
        pages = jnp.take_along_axis(block_tables, idx // page, axis=1)
        entries = cache.latent_pages[layer, pages, idx % page]   # [B,K,E]
        c_kv = entries[..., :R]
        k_rope = entries[..., R:R + q_rope.shape[-1]]
        s = (jnp.einsum("bhr,bkr->bhk", q_abs, c_kv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhd,bkd->bhk", q_rope, k_rope,
                          preferred_element_type=jnp.float32)) * scale
        s = jnp.where(live[:, None], s, -1e30)
        # normalised after the product: ``jax.nn.softmax`` here compiles,
        # on the TPU, to a reduce-window as wide as the row for every key
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        o_lat = jnp.einsum("bhk,bkr->bhr", p.astype(c_kv.dtype), c_kv,
                           preferred_element_type=jnp.float32)
        o_lat = (o_lat / jnp.sum(p, axis=-1, keepdims=True)
                 ).astype(c_kv.dtype)
    if real is not None:
        live, valid = live & real[:, None], valid & real[:, None]
    return (o_lat, jnp.sum(live).astype(jnp.int32),
            jnp.sum(valid).astype(jnp.int32))
