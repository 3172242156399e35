"""The linear-attention recurrence with a constant decay a head
(``ops/linear_attention.py``): the chunked form against the token-by-token
one, on random float32 operands.

Tolerance: float32 sums in another order; the chunked form's decays are
products of at most ``chunk`` factors each at most 1: 2e-5 of the largest
output (the state: 2e-5 of its largest entry)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.linear_attention import (decay_slopes, linear_scan,
                                                linear_step,
                                                state_decode_update)

TOL = 2e-5
H, D = 4, 16


def _operands(seed, b, T):
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k, v = (jax.random.normal(key, (b, T, H, D), jnp.float32)
               for key in keys[:3])
    state = jax.random.normal(keys[3], (b, H, D, D), jnp.float32)
    return q, k, v, state


def _token_by_token(q, k, v, slopes, state, scale, real=None):
    """T calls of ``linear_step``; rows past ``real`` are skipped."""
    b, T = q.shape[:2]
    out = []
    for t in range(T):
        o, new = linear_step(q[:, t], k[:, t], v[:, t], slopes, state, scale)
        live = jnp.ones((b,), bool) if real is None else t < real
        state = jnp.where(live[:, None, None, None], new, state)
        out.append(o)
    return jnp.stack(out, axis=1), state


def _close(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def test_the_slopes_are_the_published_ones():
    slopes = np.asarray(decay_slopes(32))
    assert np.allclose(slopes, 2.0 ** (-8.0 * (np.arange(32) + 1) / 32))
    decay = np.exp(-slopes)
    # time constants from about a token to 256
    assert abs(decay[0] - 0.43) < 0.01 and abs(decay[-1] - 0.9961) < 1e-4


@pytest.mark.parametrize("T,chunk", [(37, 8), (8, 8), (5, 16), (64, 16),
                                     (23, 7)])
def test_the_chunked_form_is_the_token_by_token_one(T, chunk):
    """Lengths the chunk does not divide, a non-zero state coming in."""
    q, k, v, state = _operands(T, 2, T)
    slopes, scale = decay_slopes(H), 1.0 / np.sqrt(D)
    want, want_state = _token_by_token(q, k, v, slopes, state, scale)
    got, got_state = linear_scan(q, k, v, slopes, state, chunk=chunk,
                                 scale=scale)
    assert _close(got, want) < TOL
    assert _close(got_state, want_state) < TOL


@pytest.mark.parametrize("real", [[29, 3], [1, 40], [40, 17]])
def test_a_padded_prefill_advances_nothing(real):
    """Rows past ``real`` neither decay the state nor add to it: the state
    is the one after the last real row, and the real rows' outputs are
    those of the unpadded sequence."""
    T = 40
    q, k, v, state = _operands(5, 2, T)
    slopes, real = decay_slopes(H), jnp.asarray(real)
    want, want_state = _token_by_token(q, k, v, slopes, state, 1.0, real)
    got, got_state = linear_scan(q, k, v, slopes, state, real=real, chunk=8)
    assert _close(got_state, want_state) < TOL
    for b, n in enumerate(np.asarray(real)):
        assert _close(got[b, :n], want[b, :n]) < TOL


def test_a_prefill_in_two_pieces_carries_the_state():
    q, k, v, state = _operands(6, 1, 48)
    slopes = decay_slopes(H)
    want, want_state = linear_scan(q, k, v, slopes, state, chunk=8)
    first, mid = linear_scan(q[:, :20], k[:, :20], v[:, :20], slopes, state,
                             chunk=8)
    second, got_state = linear_scan(q[:, 20:], k[:, 20:], v[:, 20:], slopes,
                                    mid, chunk=8)
    assert _close(jnp.concatenate([first, second], 1), want) < TOL
    assert _close(got_state, want_state) < TOL


def test_the_decode_update_on_the_stacked_pool_keeps_dead_rows():
    """One row a slot on layer 1 of a pool of three layers: the live
    slots' rows are ``linear_step``'s, the dead slot's and the other
    layers' keep their bits."""
    q, k, v, _ = _operands(7, 3, 1)
    pool = jax.random.normal(jax.random.key(8), (3, 3, H, D, D), jnp.float32)
    slopes, live = decay_slopes(H), jnp.asarray([True, False, True])
    o, new = jax.jit(state_decode_update)(pool, jnp.int32(1), q[:, 0],
                                          k[:, 0], v[:, 0], slopes, live)
    want_o, want = linear_step(q[:, 0], k[:, 0], v[:, 0], slopes, pool[1])
    assert bool(jnp.all(new[0] == pool[0]) and jnp.all(new[2] == pool[2]))
    assert bool(jnp.all(new[1, 1] == pool[1, 1]))
    assert _close(new[1, ::2], want[::2]) < TOL
    assert _close(o[::2], want_o[::2]) < TOL


def test_bf16_operands_keep_a_float32_state():
    q, k, v, state = _operands(9, 1, 24)
    o, new = linear_scan(*(x.astype(jnp.bfloat16) for x in (q, k, v)),
                         decay_slopes(H), state, chunk=8)
    assert o.dtype == jnp.bfloat16 and new.dtype == jnp.float32
    want, _ = linear_scan(q, k, v, decay_slopes(H), state, chunk=8)
    # bf16 carries 8 bits: a few roundings of 0.4 % over sums of 24 terms
    assert _close(o.astype(jnp.float32), want) < 0.03
