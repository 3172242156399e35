"""The state-space recurrence (``ops/ssm.py``): the chunked (SSD) scan
against the one-row update run token by token, the convolution's tail, and
the page pools' lane packing (``ops/paged_attention.py kv_lane_pack``)
against unpacked pools.  float32 on the CPU: the two forms of the
recurrence differ by the order of sums (1e-5 of values of size 10)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import paged_attention as paged
from deepspeed_tpu.ops import ssm


def _inputs(T, G=1, b=2, H=4, P=8, N=16, seed=0):
    k = jax.random.split(jax.random.key(seed + T), 7)
    return dict(
        x=jax.random.normal(k[0], (b, T, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (b, T, H)) - 2),
        A=-jnp.exp(jax.random.uniform(k[2], (H,), minval=0, maxval=2.7)),
        B=jax.random.normal(k[3], (b, T, G, N)),
        C=jax.random.normal(k[4], (b, T, G, N)),
        D=jax.random.normal(k[5], (H,)),
        state=jax.random.normal(k[6], (b, H, P, N)))


def _token_by_token(x, dt, A, B, C, D, state):
    ys = []
    for t in range(x.shape[1]):
        y, state = ssm.ssm_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], D,
                                state)
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("T,chunk,groups", [
    (37, 16, 1),        # T no multiple of the chunk
    (5, 16, 1),         # T under a chunk
    (1, 16, 1),         # one row
    (64, 16, 2),        # whole chunks, two groups of heads
    (50, 256, 1),       # the published chunk, one chunk of 50
])
def test_the_chunked_scan_is_the_recurrence(T, chunk, groups):
    """With a non-zero initial state: y and the final state."""
    args = _inputs(T, groups)
    y, state = ssm.ssd_scan(chunk=chunk, **args)
    want_y, want_state = _token_by_token(**args)
    assert float(jnp.max(jnp.abs(y - want_y))) < 2e-5
    assert float(jnp.max(jnp.abs(state - want_state))) < 2e-5


def test_a_row_with_dt_zero_changes_nothing():
    """How padding is masked: the state after 20 real rows and 12 rows of
    dt 0 is the state after the 20, bit for bit."""
    args = _inputs(32)
    real = dict(args, dt=args["dt"].at[:, 20:].set(0.0))
    _, padded = ssm.ssd_scan(chunk=16, **real)
    _, short = ssm.ssd_scan(chunk=16, **{
        k: (v[:, :20] if k in ("x", "dt", "B", "C") else v)
        for k, v in args.items()})
    assert float(jnp.max(jnp.abs(padded - short))) < 1e-6
    _, kept = ssm.ssm_step(args["x"][:, 0], jnp.zeros_like(args["dt"][:, 0]),
                           args["A"], args["B"][:, 0], args["C"][:, 0],
                           args["D"], args["state"])
    assert bool(jnp.all(kept == args["state"]))


@pytest.mark.parametrize("real", [[8, 8], [5, 2], [1, 0]])
def test_the_convs_tail_is_the_last_three_real_inputs(real):
    """Kernel 4: the tail after T rows of which ``real`` are tokens is the
    last three REAL inputs, the old tail's rows where fewer are real."""
    k = jax.random.split(jax.random.key(1), 4)
    x = jax.random.normal(k[0], (2, 8, 12))
    tail = jax.random.normal(k[1], (2, 3, 12))
    w, b = jax.random.normal(k[2], (4, 12)), jax.random.normal(k[3], (12,))
    out, new = ssm.causal_conv(x, tail, w, b, jnp.asarray(real))
    full = np.concatenate([np.asarray(tail), np.asarray(x)], axis=1)
    for seq, n in enumerate(real):
        assert np.array_equal(np.asarray(new[seq]), full[seq, n:n + 3])
    # row t sees rows t-3 .. t, weight row 3 on the current input
    pre = sum(full[:, j:j + 8] * np.asarray(w)[j] for j in range(4)) \
        + np.asarray(b)
    assert np.allclose(np.asarray(out), np.asarray(jax.nn.silu(pre)),
                       atol=1e-6)
    # every row a token: the same as no ``real`` at all
    if real == [8, 8]:
        _, plain = ssm.causal_conv(x, tail, w, b)
        assert np.array_equal(np.asarray(plain), np.asarray(new))


@pytest.mark.parametrize("kv_heads,head_dim,pack", [
    (8, 64, 2), (4, 32, 4), (8, 128, 1), (16, 256, 1), (3, 64, 1),
    (2, 16, 1), (8, 16, 8), (4, 96, 1)])
def test_heads_share_a_pool_row_where_they_fill_the_lanes(kv_heads,
                                                          head_dim, pack):
    assert paged.kv_lane_pack(kv_heads, head_dim) == pack
    assert paged.paged_pool_shape(3, 9, kv_heads, 8, head_dim) == \
        (3, 9, kv_heads // pack, 8, head_dim * pack)


@pytest.mark.parametrize("impl,interpret", [("jnp", False),
                                            ("pallas", True)])
def test_packed_pools_read_as_unpacked_ones(impl, interpret):
    """8 key/value heads of 64 under 16 query heads (group 2), packed two a
    row: write and read through the packed stack against the same through
    a stack of [.., 8, page, 64], whose jnp pair is the oracle; with a
    softmax scale that is not 1 / sqrt(64), and with none."""
    B, T, H, Hkv, D, page, pages = 2, 5, 16, 8, 64, 8, 9
    k = jax.random.split(jax.random.key(2), 6)
    tables = jnp.asarray(np.arange(1, 9).reshape(2, 4), jnp.int32)
    lengths = jnp.asarray([9, 0], jnp.int32)

    def stack(shape):
        return paged.PagedKVCache(jnp.zeros(shape), jnp.zeros(shape))

    packed = stack(paged.paged_pool_shape(2, pages, Hkv, page, D))
    plain = stack((2, pages, Hkv, page, D))
    assert packed.k_pages.shape == (2, pages, 4, page, 128)
    # a context of 9 rows for sequence 0, then T new rows for both
    for at, rows in ((jnp.zeros(2, jnp.int32), 9), (lengths, T)):
        kn = jax.random.normal(k[rows % 4], (B, rows, Hkv, D))
        vn = jax.random.normal(k[rows % 4 + 1], (B, rows, Hkv, D))
        packed = paged.write_paged(packed, 1, tables, at, kn, vn, impl=impl,
                                   interpret=interpret)
        plain = paged.write_paged(plain, 1, tables, at, kn, vn, impl="jnp")
    assert np.array_equal(
        np.asarray(packed.k_pages).reshape(2, pages, 4, page, 2, D),
        np.moveaxis(np.asarray(plain.k_pages).reshape(
            2, pages, 4, 2, page, D), 3, 4))
    q = jax.random.normal(k[5], (B, T, H, D))
    for scale in (0.015625, None):
        got = paged.paged_decode_attention(
            q, packed, tables, lengths + T, softmax_scale=scale, impl=impl,
            interpret=interpret, layer=1)
        want = paged.paged_decode_attention(
            q, plain, tables, lengths + T, softmax_scale=scale, impl="jnp",
            layer=1)
        assert got.shape == want.shape == (B, T, H, D)
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5
