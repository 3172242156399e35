"""Linear attention with a constant decay a head (Lightning Attention), and
its cache.

A head ``h`` of width ``D`` keeps a matrix state ``S`` [D, D] that every
token decays by the head's constant and adds an outer product to:

    S_t = a_h * S_{t-1} + k_t^T v_t
    o_t = scale * q_t S_t                    (S_t holds token t itself)

with ``a_h = exp(-slope_h)`` and ``slope_h = 2^(-8 (h + 1) / H)``, the
ALiBi slopes (:func:`decay_slopes`): time constants from a token (head 0)
to hundreds of tokens (the last head).

``linear_step`` is that, one row a sequence (a decode step: a read and a
write of the state).  ``linear_scan`` is the same function of T rows
computed in chunks: inside a chunk the products ``q_i . k_j`` masked by
the decays ``a^(i - j)`` and times ``v_j``; between chunks the states,
``T / chunk`` steps of the recurrence over whole chunks.  Nothing of size
``T x T`` is built: the largest temporary is [H, T / chunk, chunk, chunk].

Precision: the state, the decays and every sum are float32.  The operands
of the chunked form's matrix products are of ``q``'s dtype (bfloat16 on
the serving path; float32 in a float32 model) and accumulate in float32.

A row that is no token (a bucket's padding, the pad of the last chunk
here) decays nothing and adds nothing: ``real`` says how many of a
sequence's rows are tokens, as ``ssd_scan``'s ``dt`` 0 does.

On the serving path the state lives beside the page pools
(``ops/ssm.py HybridKVCache``, its ``ssm`` member a
:class:`LinearStateCache`), a row a SLOT: no allocator, nothing to leak.
A decode dispatch advances a layer's rows of the STACKED pool through
:func:`state_decode_update`, in XLA.
"""

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

# rows a chunk of ``linear_scan``: the largest temporary is
# [H, T / CHUNK, CHUNK, CHUNK] float32 (134 MB for 32 heads of a 16,384-row
# prefill), and a chunk's products are matrices of CHUNK x 128
CHUNK = 256


class LinearStateCache(NamedTuple):
    """The linear-attention layers' per-slot state, stacked over those
    layers: ``state`` [L_lin, slots, H, D, D] float32 (summed over tens of
    thousands of steps).  There is no convolution and so no tail."""
    state: Any


def init_linear_state_cache(layers, slots, heads, head_dim):
    return LinearStateCache(state=jnp.zeros(
        (layers, slots, heads, head_dim, head_dim), jnp.float32))


def decay_slopes(heads: int):
    """``slope_h = 2^(-8 (h + 1) / heads)``, h = 0 .. heads - 1, float32:
    a head decays its state by ``exp(-slope_h)`` a token."""
    return jnp.exp2(-8.0 * (jnp.arange(heads, dtype=jnp.float32) + 1.0)
                    / heads)


def linear_step(q, k, v, slopes, state, scale=1.0):
    """One row a sequence.  q, k, v: [b, H, D]; slopes: [H] float32;
    state: [b, H, D, D] float32.  Returns (o [b, H, D] in ``q``'s dtype,
    the state after the row)."""
    f32 = jnp.float32
    decay = jnp.exp(-slopes.astype(f32))[None, :, None, None]
    S = state * decay + k.astype(f32)[..., :, None] * v.astype(f32)[
        ..., None, :]
    o = jnp.sum(q.astype(f32)[..., :, None] * S, axis=-2) * scale
    return o.astype(q.dtype), S


def state_decode_update(pool, layer, q, k, v, slopes, live, scale=1.0):
    """One row a SLOT on the stacked pool: ``linear_step`` on layer
    ``layer`` (may be traced) of ``pool`` [L, slots, H, D, D] float32, the
    other operands as ``linear_step``'s with a row a slot.  A slot that is
    not ``live`` [slots] keeps its state bit for bit (its ``o`` is
    nobody's to read).  Returns (o [slots, H, D], the pool)."""
    state = jax.lax.dynamic_index_in_dim(pool, layer, 0, False)
    o, new = linear_step(q, k, v, slopes, state, scale)
    return o, jax.lax.dynamic_update_index_in_dim(
        pool, jnp.where(live[:, None, None, None], new, state), layer, 0)


def linear_scan(q, k, v, slopes, state, real=None, scale=1.0, chunk=None):
    """T rows a sequence, in chunks of ``chunk`` (None: ``CHUNK``).  q, k, v: [b, T, H, D];
    slopes: [H]; state: [b, H, D, D] float32, the state ahead of row 0;
    ``real`` [b]: how many of the T rows are tokens (None: all), the
    others advance nothing.  Returns (o [b, T, H, D] in ``q``'s dtype, the
    state after the last real row).  The same function as T calls of
    ``linear_step``."""
    b, T, H, D = q.shape
    f32, mm = jnp.float32, q.dtype
    Q = min(int(chunk or CHUNK), T)
    pad = (-T) % Q
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
    token = jnp.broadcast_to(
        jnp.arange(T + pad)[None, :]
        < (T if real is None else real[:, None]), (b, T + pad))
    k = jnp.where(token[..., None, None], k, 0)     # a pad row adds nothing
    nc = (T + pad) // Q
    qc, kc, vc = (a.reshape(b, nc, Q, H, D) for a in (q, k, v))
    # the log of each row's decay (0 on a row that is no token), summed
    # from the chunk's first row: [b, nc, H, Q], the chunk's rows last
    rate = jnp.where(token, 1.0, 0.0).reshape(b, nc, 1, Q) \
        * -slopes.astype(f32)[None, None, :, None]
    cum = jnp.cumsum(rate, axis=-1)
    # inside a chunk: row i takes v_j of every row j <= i, through
    # q_i . k_j and the decay from j to i
    scores = jnp.einsum("bcihd,bcjhd->bchij", qc, kc,
                        preferred_element_type=f32)
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    o = jnp.einsum("bchij,bcjhd->bcihd", (scores * decay).astype(mm), vc,
                   preferred_element_type=f32)
    # what each chunk adds to the state at its end, and the recurrence
    # over whole chunks
    to_end = jnp.exp(cum[..., -1:] - cum)                    # [b, nc, H, Q]
    scaled = kc.astype(f32) * jnp.moveaxis(to_end, -1, 2)[..., None]
    added = jnp.einsum("bcjhd,bcjhe->bchde", scaled.astype(mm), vc,
                       preferred_element_type=f32)
    whole = jnp.exp(cum[..., -1])                            # [b, nc, H]

    def over_chunks(S, inp):
        keep, add = inp
        return S * keep[..., None, None] + add, S

    final, before = jax.lax.scan(
        over_chunks, state.astype(f32),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
    # ... and what the state ahead of the chunk gives each of its rows
    carried = jnp.einsum("bcihd,cbhde->bcihe", qc, before.astype(mm),
                         preferred_element_type=f32)
    o = (o + carried * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]) * scale
    return o.reshape(b, T + pad, H, D)[:, :T].astype(mm), final
