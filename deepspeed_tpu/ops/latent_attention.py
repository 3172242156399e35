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

The selection (:func:`index_scores`, :func:`topk_mask`): query ``t`` keeps
the ``min(k, t + 1)`` causal keys of largest ``I[t, s] = sum_h w[t, h] *
relu(q_i[t, h] . k_i[s])``, by value, ties to the lower index
(``jax.lax.top_k``'s rule, which the decode step uses directly).

Everything here is plain XLA under ``jax.named_scope``s the caller opens
(``latent_attn``, ``select``): no Pallas kernel yet (ROADMAP B1).
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.paged_attention import _row_targets

PREFILL_BLOCK_Q = 256      # queries a block of the prefill's attention
PREFILL_BLOCK_K = 512      # keys a step of its running softmax
LANES = 128                # an entry's row is whole tiles of this many


class LatentKVCache(NamedTuple):
    latent_pages: jnp.ndarray   # [L, P, page, row of kv_rank + rope_dim]
    index_pages: jnp.ndarray    # [L, P, page, index_dim]


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
    nothing re-lays them)."""
    page_idx, offset = _row_targets(block_tables, lengths, entry.shape[1],
                                    cache.latent_pages.shape[2])
    entry = jnp.pad(entry, ((0, 0), (0, 0), (
        0, cache.latent_pages.shape[-1] - entry.shape[-1])))
    return LatentKVCache(
        latent_pages=cache.latent_pages.at[layer, page_idx, offset].set(
            entry.astype(cache.latent_pages.dtype)),
        index_pages=cache.index_pages.at[layer, page_idx, offset].set(
            index_key.astype(cache.index_pages.dtype)))


def rope_interleaved(x, positions, theta):
    """Rotary embedding on pairs ``(2i, 2i+1)`` of the last axis
    (``rope_interleave: true``), angle ``position * theta**(-2i / D)``;
    x: [B, T, ..., D], positions: [B, T]."""
    D = x.shape[-1]
    half = D // 2
    freqs = jnp.exp(-np.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
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


def _sortable(x):
    """float32 -> uint32 in the floats' own order (-inf lowest; the two
    zeros are one value)."""
    x = x.astype(jnp.float32)
    i = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    i = i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(i, jnp.uint32) ^ jnp.uint32(1 << 31)


def topk_mask(scores, valid, k):
    """Boolean mask [..., S] of the ``min(k, valid entries)`` largest
    ``scores`` among ``valid``, by value, ties to the lower index: what
    ``jax.lax.top_k`` would pick, without sorting.  The k-th largest value
    is found by bisection over the bits of the floats' order (32 passes
    of compare and count), the ties at it are cut by a running count."""
    if k >= scores.shape[-1]:
        return valid
    u = jnp.where(valid, jnp.maximum(_sortable(scores), 1), 0)

    def refine(i, kth):
        trial = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(u >= trial, axis=-1, keepdims=True) >= k
        return jnp.where(enough, trial, kth)

    kth = jax.lax.fori_loop(
        0, 32, refine, jnp.zeros(u.shape[:-1] + (1,), jnp.uint32))
    above = u > kth
    ties = u == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return valid & (above | (ties & (jnp.cumsum(ties, axis=-1) <= room)))


def topk_indices(scores, valid, k):
    """(indices [..., k'], live [..., k']) of the same selection as
    :func:`topk_mask`, ``k' = min(k, S)``; ``live`` is false where fewer
    than k' entries are valid."""
    k = min(k, scores.shape[-1])
    scores = jnp.where(scores == 0, 0.0, scores)    # -0.0 is 0.0
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
    return idx, jnp.take_along_axis(valid, idx, axis=-1)


# ----------------------------------------------------------------------
# the two attention paths
# ----------------------------------------------------------------------
def prefill_attention(q, k, v, q_i, w_i, k_i, positions, topk, scale,
                      real=None, block_q=PREFILL_BLOCK_Q,
                      block_k=PREFILL_BLOCK_K):
    """Causal attention of T tokens over themselves, each query over its
    selected keys.  q, k: [B, T, H, dk]; v: [B, T, H, dv]; q_i: [B, T, Hi,
    Di]; w_i: [B, T, Hi]; k_i: [B, T, Di]; positions: [B, T], rising along
    T.  Blocked over queries, and for each block of queries over the
    blocks of keys that lie at or before it (a running softmax; the loop's
    trip count is the causal prefix, so the upper triangle is never
    computed), so that no [T, T] tensor of all heads is live.  ``real``
    [B, T] (all, without it) marks the queries that are tokens: a bucket's
    padding selects nothing, is not counted, and a block of it is skipped.
    Returns (out [B, T, H, dv], keys attended, causal keys) — the counts
    summed over the real queries, int32."""
    B, T = positions.shape
    H, dv = v.shape[2], v.shape[3]
    bq, bk = min(block_q, T), min(block_k, T)
    pad_q, pad_k = (-T) % bq, (-T) % bk
    nb = (T + pad_q) // bq

    def blocks(x):      # [B, T, ...] -> [nb, B, bq, ...]
        x = jnp.pad(x, ((0, 0), (0, pad_q)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((B, nb, bq) + x.shape[2:]), 1, 0)

    keys = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    values = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    # the first position of each block of keys: a block of queries needs
    # the blocks that begin at or before its last query
    begins = jnp.min(positions[:, ::bk], axis=0)

    if real is None:
        real = jnp.ones((B, T), bool)

    def attend(block):
        qb, qib, wib, posb, realb = block
        with jax.named_scope("select"):
            causal = (positions[:, None, :] <= posb[:, :, None]) \
                & realb[:, :, None]                              # [B,bq,T]
            mask = topk_mask(index_scores(qib, wib, k_i), causal, topk)
            padded = jnp.pad(mask, ((0, 0), (0, 0), (0, pad_k)))

        def over_keys(j, carry):
            top, total, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(keys, j * bk, bk, 1)
            vj = jax.lax.dynamic_slice_in_dim(values, j * bk, bk, 1)
            mj = jax.lax.dynamic_slice_in_dim(padded, j * bk, bk, 2)
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kj,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(mj[:, None], s, -1e30)
            new_top = jnp.maximum(top, jnp.max(s, axis=-1))
            p = jnp.exp(s - new_top[..., None])
            keep = jnp.exp(top - new_top)
            return (new_top, total * keep + jnp.sum(p, axis=-1),
                    acc * keep[..., None] + jnp.einsum(
                        "bhqk,bkhd->bhqd", p.astype(v.dtype), vj,
                        preferred_element_type=jnp.float32))

        with jax.named_scope("latent_attn"):
            # a row's masked scores in a block before its first real key
            # count as exp(0) until a real one arrives and scales them to
            # nothing; every row has one (itself; a padded row, which
            # selects nothing, comes out as an average and is cut off)
            start = (jnp.full((B, H, bq), -1e30, jnp.float32),
                     jnp.zeros((B, H, bq), jnp.float32),
                     jnp.zeros((B, H, bq, dv), jnp.float32))
            _, total, acc = jax.lax.fori_loop(
                0, jnp.sum(begins <= jnp.max(posb)), over_keys, start)
            out = jnp.moveaxis(acc / total[..., None], 1, 2).astype(v.dtype)
        return out, jnp.sum(mask, axis=(1, 2)), jnp.sum(causal, axis=(1, 2))

    def skip(block):
        return (jnp.zeros((B, bq, H, dv), v.dtype),
                jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32))

    def one(block):
        return jax.lax.cond(jnp.any(block[-1]), attend, skip, block)

    out, attended, context = jax.lax.map(
        one, (blocks(q), blocks(q_i), blocks(w_i), blocks(positions),
              blocks(real)))
    out = jnp.moveaxis(out, 0, 1).reshape((B, T + pad_q) + out.shape[3:])
    return (out[:, :T], jnp.sum(attended).astype(jnp.int32),
            jnp.sum(context).astype(jnp.int32))


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
