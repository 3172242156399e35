"""The order-exact top-k of a selection inside attention: the ``k`` largest
scores among the valid ones BY VALUE, ties to the lower index
(``jax.lax.top_k``'s rule), as a mask without sorting (:func:`topk_mask`)
or as indices (:func:`topk_indices`).  Both selections of the serving
path take it from here: the learned one over single latent entries
(``ops/latent_attention.py``) and the block selection over compressed
keys (``ops/block_sparse_attention.py``)."""

import jax
import jax.numpy as jnp


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
