"""The state-space recurrence of a Mamba-2 layer (SSD), and its cache.

A head ``h`` of width ``P`` keeps a state ``S`` [P, N] that every token
decays by a scalar and adds an outer product to:

    a_t = exp(dt_t * A_h)                       (A_h < 0, dt_t >= 0)
    S_t = a_t * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D_h * x_t

with ``B_t`` and ``C_t`` [N] shared by the ``H / G`` heads of a group.
``ssm_step`` is that, one row a sequence (a decode step: a read and a
write of the state).  ``ssd_scan`` is the same function of T rows computed
in chunks: inside a chunk the products ``C_i . B_j`` masked by the decays
``exp(sum_{j<k<=i} dt_k A)`` and times ``dt_j x_j``; between chunks the
states, ``T / chunk`` steps of the recurrence over whole chunks.  Nothing
of size ``T x T`` is built: the largest temporary is [H, T / chunk, chunk,
chunk].

Precision: the state, the decays and every sum are float32.  The operands
of the chunked form's matrix products are of ``x``'s dtype (bfloat16 on
the serving path, as the published kernels multiply them; float32 in a
float32 model) and accumulate in float32.

A row with ``dt`` 0 changes nothing (``a`` = 1 and nothing added): that is
how a caller masks rows that are no tokens (a bucket's padding, the pad
of the last chunk here).

``causal_conv`` is the depthwise convolution ahead of the recurrence,
with its own small state: the last ``K - 1`` inputs.

On the serving path both states live beside the page pools
(``HybridKVCache``), a row a SLOT: no allocator, nothing to leak.  A
decode dispatch advances a layer's rows of the STACKED state pool through
``state_decode_update``: on a TPU one Pallas kernel
(``ops/pallas/ssm_update.py``, device line ``ssm_decode_update``) that
reads each slot's state once and writes it once, in place; elsewhere the
slice, ``ssm_step`` and the masked write, which stays the oracle.
"""

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.paged_attention import PagedKVCache


class StateCache(NamedTuple):
    """The state-space layers' per-slot state, stacked over those layers:
    ``state`` [L_ssm, slots, H, P, N] float32 (summed over thousands of
    steps) and ``conv`` [L_ssm, slots, (K - 1) x C], the slot's last
    K - 1 inputs of the convolution (before it and the silu), oldest
    first, flat: a [K - 1, C] tile a slot would be padded to sixteen rows
    on the chip."""
    state: Any
    conv: Any


class HybridKVCache(NamedTuple):
    """The pools of a model whose layers are attention or state-space:
    ``full`` the attention layers' pages [L_attn, P, ...] under the block
    tables, ``ssm`` the other layers' state, a row a slot."""
    full: PagedKVCache
    ssm: StateCache


def init_state_cache(layers, slots, heads, head_dim, state, conv_rows,
                     conv_channels, dtype):
    return StateCache(
        state=jnp.zeros((layers, slots, heads, head_dim, state),
                        jnp.float32),
        conv=jnp.zeros((layers, slots, conv_rows * conv_channels), dtype))


def causal_conv(x, tail, weight, bias, real=None):
    """Depthwise causal convolution and silu.  x: [B, T, C]; ``tail``
    [B, K - 1, C]: the inputs ahead of row 0 (zeros ahead of position 0);
    ``weight`` [K, C], row K - 1 on the current input; ``bias`` [C];
    ``real`` [B]: how many of the T rows are tokens (None: all).  Returns
    (silu(conv) [B, T, C], the new tail: the last K - 1 REAL inputs, the
    old tail's where fewer than K - 1 rows are real)."""
    T, K = x.shape[1], weight.shape[0]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = weight.astype(jnp.float32)
    out = bias.astype(jnp.float32)
    for k in range(K):
        out = out + full[:, k:k + T].astype(jnp.float32) * w[k]
    if real is None:
        new_tail = full[:, T:]
    else:
        # row j of ``full`` is input j - (K - 1): the last K - 1 real
        # inputs are rows real .. real + K - 2
        at = real[:, None] + jnp.arange(K - 1)[None, :]
        new_tail = jnp.take_along_axis(full, at[:, :, None], axis=1)
    return jax.nn.silu(out).astype(x.dtype), new_tail


def ssm_step(x, dt, A, B, C, D, state):
    """One row a sequence.  x: [b, H, P]; dt: [b, H] float32; A, D: [H];
    B, C: [b, G, N]; state: [b, H, P, N] float32.  Returns (y [b, H, P]
    in ``x``'s dtype, the state after the row)."""
    b, H, P = x.shape
    G, N = B.shape[1:]
    f32 = jnp.float32
    xf = x.astype(f32).reshape(b, G, H // G, P)
    dt = dt.astype(f32).reshape(b, G, H // G)
    decay = jnp.exp(dt * A.astype(f32).reshape(G, H // G))
    S = state.reshape(b, G, H // G, P, N)
    S = S * decay[..., None, None] + (dt[..., None] * xf)[..., None] \
        * B.astype(f32)[:, :, None, None, :]
    y = jnp.sum(S * C.astype(f32)[:, :, None, None, :], axis=-1) \
        + D.astype(f32).reshape(G, H // G, 1) * xf
    return y.reshape(b, H, P).astype(x.dtype), S.reshape(b, H, P, N)


def state_decode_update(pool, layer, x, dt, A, B, C, D, live, impl="jnp",
                        interpret=False):
    """One row a SLOT on the stacked pool: ``ssm_step`` on layer
    ``layer`` (may be traced) of ``pool`` [L, slots, H, P, N] float32, the
    other operands as ``ssm_step``'s with a row a slot.  A slot that is
    not ``live`` [slots] keeps its state bit for bit (its ``y`` is nobody's
    to read).  ``impl`` "pallas": the aliased kernel
    (``ops/pallas/ssm_update.py``; its interpreter with ``interpret``),
    which takes the decay and ``dt x`` as ``ssm_step`` forms them and
    leaves ``D x`` and the cast here; "jnp": the slice, ``ssm_step`` and
    the masked write, the oracle.  Returns (y [slots, H, P] in ``x``'s
    dtype, the pool)."""
    if impl == "pallas":
        from deepspeed_tpu.ops.pallas.ssm_update import ssm_decode_update
        f32 = jnp.float32
        xf, dt = x.astype(f32), dt.astype(f32)
        y, pool = ssm_decode_update(
            pool, layer, live, jnp.exp(dt * A.astype(f32)),
            dt[..., None] * xf, B.astype(f32), C.astype(f32),
            interpret=interpret)
        y = y + D.astype(f32)[:, None] * xf
        return y.astype(x.dtype), pool
    state = jax.lax.dynamic_index_in_dim(pool, layer, 0, False)
    y, new = ssm_step(x, dt, A, B, C, D, state)
    return y, jax.lax.dynamic_update_index_in_dim(
        pool, jnp.where(live[:, None, None, None], new, state), layer, 0)


def _by_rows(pool):
    """The stacked state pool [L, slots, H, P, N] with a slot's heads and
    their rows as ONE axis, [L, slots, H x P, N] (no data moves)."""
    return pool.reshape(pool.shape[:2] + (-1, pool.shape[-1]))


def read_slot_state(pool, layer, slot):
    """Slot ``slot``'s state of layer ``layer`` (both may be traced) out
    of the stacked pool [L, slots, H, P, N] -> [H, P, N]: what a prefill
    starts a sequence from."""
    flat = _by_rows(pool)
    return jax.lax.dynamic_slice(
        flat, (layer, slot, 0, 0), (1, 1) + flat.shape[2:]
    ).reshape(pool.shape[2:])


def write_slot_state(pool, layer, slot, state):
    """The pool with ``state`` [H, P, N] as slot ``slot``'s of layer
    ``layer``, written in place as [H x P, N].  The chunked scan's einsums
    leave the new state in an order of their own choosing, and a write of
    [H, P, N] handed that order on to the WHOLE pool: at 128 heads of 64
    the 64-row prefill (one chunk) copied 2.4 GB on its way in and again
    on its way out (docs/serving.md).  With heads and rows one axis there
    is one order to have."""
    flat = _by_rows(pool)
    return jax.lax.dynamic_update_slice(
        flat, state.reshape((1, 1) + flat.shape[2:]).astype(pool.dtype),
        (layer, slot, 0, 0)).reshape(pool.shape)


def ssd_scan(x, dt, A, B, C, D, state, chunk):
    """T rows a sequence, in chunks of ``chunk``.  x: [b, T, H, P]; dt:
    [b, T, H] float32 (0 on a row that is no token); A, D: [H]; B, C:
    [b, T, G, N]; state: [b, H, P, N] float32, the state ahead of row 0.
    Returns (y [b, T, H, P] in ``x``'s dtype, the state after row T - 1).
    The same function as T calls of ``ssm_step``."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    R = H // G
    f32, mm = jnp.float32, x.dtype
    Q = min(int(chunk), T)
    pad = (-T) % Q
    if pad:     # dt 0: the pad rows change nothing
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                               (a.ndim - 2)) for a in (x, dt, B, C))
    nc = (T + pad) // Q
    xc = x.reshape(b, nc, Q, G, R, P)
    Bc, Cc = B.reshape(b, nc, Q, G, N), C.reshape(b, nc, Q, G, N)
    # [b, nc, G, R, Q]: the chunk's rows last, where the masks are built
    dtc = jnp.moveaxis(dt.astype(f32).reshape(b, nc, Q, G, R), 2, -1)
    cum = jnp.cumsum(dtc * A.astype(f32).reshape(G, R, 1), axis=-1)
    # inside a chunk: row i takes dt_j x_j of every row j <= i, through
    # C_i . B_j and the decay from j to i
    scores = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                        preferred_element_type=f32)
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    weights = scores[:, :, :, None] * decay * dtc[..., None, :]
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", weights.astype(mm), xc,
                   preferred_element_type=f32)
    # what each chunk adds to the state at its end, and the recurrence
    # over whole chunks
    to_end = jnp.exp(cum[..., -1:] - cum) * dtc          # [b, nc, G, R, Q]
    scaled = (xc.astype(f32) * jnp.moveaxis(to_end, -1, 2)[..., None])
    added = jnp.einsum("bcjgrp,bcjgn->bcgrpn", scaled.astype(mm), Bc,
                       preferred_element_type=f32)
    whole = jnp.exp(cum[..., -1])                        # [b, nc, G, R]

    def over_chunks(S, inp):
        keep, add = inp
        return S * keep[..., None, None] + add, S

    final, before = jax.lax.scan(
        over_chunks, state.astype(f32).reshape(b, G, R, P, N),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
    # ... and what the state ahead of the chunk gives each of its rows
    carried = jnp.einsum("bcign,cbgrpn->bcigrp", Cc, before.astype(mm),
                         preferred_element_type=f32)
    y = y + carried * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None] \
        + D.astype(f32).reshape(G, R, 1) * xc.astype(f32)
    y = y.reshape(b, T + pad, H, P)[:, :T]
    return y.astype(mm), final.reshape(b, H, P, N)
