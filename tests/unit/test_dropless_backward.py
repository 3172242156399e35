"""The dropless held-expert layer's backward pass
(``moe/sharded_moe.py:dropless_held_experts`` is a ``custom_vjp``; its two
kernels are ``ops/pallas/grouped_expert_glu.py``): gradients with respect
to the rows, the router (through the chosen probabilities) and the three
expert leaves against ``jax.grad`` of plain code that computes every held
expert on every row, in float32 (2e-5 of the largest entry: sums of a few
hundred float32 products in another order; bf16 operands would miss by
4e-3), in both forms (the jnp products, the kernels in interpret mode),
with listed and stacked leaves, one chunk and several; and the properties
ISSUE 43 lists: no token dropped, an unchosen expert's gradient zero, a
tile's padding rows and the tiles past the live ones add nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import mellum
from deepspeed_tpu.moe.sharded_moe import (balance_statistic,
                                           dropless_held_experts,
                                           dropless_route)
from deepspeed_tpu.ops.pallas import grouped_expert_glu as glu

N, D, F, E, HELD, K = 40, 32, 128, 16, 4, 3
TOL = 2e-5


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _weights(seed=0, stack=None):
    key = jax.random.split(jax.random.key(seed), 6)
    lead = () if stack is None else (stack,)
    experts = {
        "w_gate": jax.random.normal(key[2], lead + (HELD, D, F)) / D ** .5,
        "w_up": jax.random.normal(key[3], lead + (HELD, D, F)) / D ** .5,
        "w_down": jax.random.normal(key[4], lead + (HELD, F, D)) / F ** .5}
    return (jax.random.normal(key[0], (N, D)),
            0.3 * jax.random.normal(key[1], (D, E)), experts,
            jax.random.normal(key[5], (N, D)))


def _plain(h, chosen, weights, experts, first, target):
    """Every held expert on every row, its chosen rows kept."""
    out = jnp.zeros_like(h)
    for e in range(HELD):
        y = (jax.nn.silu(h @ experts["w_gate"][e]) * (h @ experts["w_up"][e])
             ) @ experts["w_down"][e]
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        out = out + mine[:, None] * y
    return jnp.sum(out * target)


def _close(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert float(jnp.max(jnp.abs(x - y))) <= TOL * max(
            1.0, float(jnp.max(jnp.abs(x)))), (x.shape,)


@pytest.mark.parametrize("impl,interpret,tile,first", [
    ("jnp", False, None, 0), ("jnp", False, 8, 4), ("pallas", True, 8, 0),
    ("pallas", True, 8, 12)])
def test_gradients_of_rows_router_and_leaves(impl, interpret, tile, first):
    h, wg, experts, target = _weights()

    def routed(fn, h, wg, experts):
        chosen, weights = dropless_route(h, wg, None, K, scoring="softmax")
        return fn(h, chosen, weights, experts)

    def program(h, chosen, weights, experts):
        out, _, _ = dropless_held_experts(
            h, chosen, weights, experts, jax.nn.silu, first=first,
            impl=impl, interpret=interpret, tile=tile)
        return jnp.sum(out * target)

    plain = lambda h, c, w, ex: _plain(h, c, w, ex, first, target)  # noqa
    want = jax.value_and_grad(lambda *a: routed(plain, *a), (0, 1, 2))(
        h, wg, experts)
    got = jax.jit(jax.value_and_grad(lambda *a: routed(program, *a),
                                     (0, 1, 2)))(h, wg, experts)
    _close(want, got)


@pytest.mark.parametrize("impl,interpret", [("jnp", False), ("pallas", True)])
def test_one_held_expert_takes_every_token_and_none_is_dropped(impl,
                                                               interpret):
    """Every token to held experts 2 and 1 (and one held elsewhere): 80
    pairs in tiles of 8 are two chunks of the sorted list; no capacity, so
    each token gets both terms; expert 3, which nobody chose, gets a zero
    gradient, and so does expert 0."""
    h, _, experts, target = _weights(1)
    chosen = jnp.tile(jnp.asarray([[2, 1, 9]], jnp.int32), (N, 1))
    weights = jax.random.uniform(jax.random.key(3), (N, K)) + 0.1

    def program(h, weights, experts):
        out, load, rows = dropless_held_experts(
            h, chosen, weights, experts, jax.nn.silu, impl=impl,
            interpret=interpret, tile=8)
        return jnp.sum(out * target), (load, rows)

    (value, (load, rows)), grads = jax.jit(jax.value_and_grad(
        program, (0, 1, 2), has_aux=True))(h, weights, experts)
    assert load.tolist() == [0, N, N, 0] and int(rows) == 2 * N
    want = jax.value_and_grad(
        lambda h, w, ex: _plain(h, chosen, w, ex, 0, target), (0, 1, 2))(
            h, weights, experts)
    _close(want, (value, grads))
    for leaf in grads[2].values():
        assert not np.any(np.asarray(leaf[3])) \
            and not np.any(np.asarray(leaf[0]))
    # the pair of the expert held elsewhere has no gradient here
    assert not np.any(np.asarray(grads[1][:, 2]))


def test_stacked_leaves_are_read_and_differentiated_in_place():
    h, wg, stack, target = _weights(2, stack=3)
    chosen, weights = dropless_route(h, wg, None, K, scoring="softmax")

    def program(experts, layer):
        out, _, _ = dropless_held_experts(h, chosen, weights, experts,
                                          jax.nn.silu, layer=layer,
                                          impl="jnp")
        return jnp.sum(out * target)

    got = jax.jit(jax.grad(program))(stack, jnp.int32(1))
    listed = {k: v[1] for k, v in stack.items()}
    want = jax.grad(lambda ex: _plain(h, chosen, weights, ex, 0, target))(
        listed)
    for name, leaf in got.items():
        _close(want[name], leaf[1])
        assert not np.any(np.asarray(leaf[0])) \
            and not np.any(np.asarray(leaf[2]))


def _tiles(rows=8, n=4, seed=5):
    key = jax.random.split(jax.random.key(seed), 8)
    R = rows * n
    x, g = (jax.random.normal(k, (R, D)) for k in key[:2])
    w = jax.random.uniform(key[2], (R,))
    leaves = _weights(seed)[2]
    acc = tuple(jax.random.normal(k, s) for k, s in zip(
        key[3:6], ((HELD, D, F), (HELD, D, F), (HELD, F, D))))
    return x, g, w, leaves, acc, jnp.asarray([1, 1, 3, 3, 3], jnp.int32)


def test_the_kernels_agree_with_their_jnp_cousins_on_the_live_tiles():
    """Both backward kernels through the interpreter against the plain
    products, two live tiles of four: the dead tiles' rows are undefined
    in the kernel's results and are left out; in the accumulators nothing
    but experts 1 and 3's blocks may move (a dead tile adds nothing, an
    expert without a tile keeps what the call found)."""
    x, g, w, leaves, acc, tile_expert = _tiles()
    live, rows = jnp.int32(3), 8
    args = (x, g, w, leaves["w_gate"], leaves["w_up"], leaves["w_down"],
            tile_expert, live, jax.nn.silu, rows)
    kernel = glu.grouped_expert_glu_dx(*args, 128, interpret=True)
    plain = glu.grouped_glu_dx_jnp(*args, 128)
    for a, b in zip(kernel, plain):
        _close(b[:24], a[:24])
    dw_args = (x, g) + plain[1:4] + (acc, tile_expert, live, rows)
    kernel = glu.grouped_expert_glu_dw(*dw_args, 128, interpret=True)
    plain = glu.grouped_glu_dw_jnp(*dw_args, 128)
    _close(plain, kernel)
    for before, after in zip(acc, plain):
        assert np.array_equal(np.asarray(before[0]), np.asarray(after[0]))
        assert np.array_equal(np.asarray(before[2]), np.asarray(after[2]))
        assert not np.array_equal(np.asarray(before[3]),
                                  np.asarray(after[3]))


def test_padding_rows_with_a_zero_cotangent_add_nothing():
    """A tile's padding rows arrive with their cotangent zeroed (and a
    weight of 0): whatever their ``x`` holds, no gradient moves."""
    x, g, w, leaves, acc, tile_expert = _tiles(seed=6)
    pad = jnp.arange(x.shape[0]) % 8 >= 5
    g, w = jnp.where(pad[:, None], 0.0, g), jnp.where(pad, 0.0, w)
    noisy = jnp.where(pad[:, None], 100.0 * x, x)

    def both(x):
        args = (x, g, w, leaves["w_gate"], leaves["w_up"], leaves["w_down"],
                tile_expert, jnp.int32(4), jax.nn.silu, 8, 128)
        dx, d_gate, d_up, inner, dw = glu.grouped_glu_dx_jnp(*args)
        return dx, dw, glu.grouped_glu_dw_jnp(
            x, g, d_gate, d_up, inner, acc, tile_expert, jnp.int32(4), 8,
            128)

    (dx, dw, grown), (dx_n, dw_n, grown_n) = both(x), both(noisy)
    keep = ~np.asarray(pad)
    _close(dx[keep], dx_n[keep])
    _close(dw[keep], dw_n[keep])
    _close(grown, grown_n)
    assert not np.any(np.asarray(dx_n)[~keep])


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Experts 0-3, 4-7, 8-11 and 12-15 of one layer, each as a chip's
    share: their terms, and the gradients with respect to the rows and
    the router, sum to the reference's layer with all 16 held."""
    key = jax.random.split(jax.random.key(7), 6)
    h, wg = jax.random.normal(key[0], (N, D)), \
        0.3 * jax.random.normal(key[1], (D, E))
    whole = {
        "wg": wg,
        "w_gate": jax.random.normal(key[2], (E, D, F)) / D ** .5,
        "w_up": jax.random.normal(key[3], (E, D, F)) / D ** .5,
        "w_down": jax.random.normal(key[4], (E, F, D)) / F ** .5}
    target = jax.random.normal(key[5], (N, D))

    class sizes:
        per_token, norm_topk, held = K, True, E

    def reference(h, wg):
        out, _ = mellum.expert_layer_terms(h, dict(whole, wg=wg), sizes)
        return jnp.sum(out * target)

    def shares(h, wg):
        chosen, weights = dropless_route(h, wg, None, K, scoring="softmax")
        total = 0.0
        for first in range(0, E, HELD):
            part = {k: whole[k][first:first + HELD]
                    for k in ("w_gate", "w_up", "w_down")}
            out, _, _ = dropless_held_experts(h, chosen, weights, part,
                                              jax.nn.silu, first=first,
                                              impl="jnp")
            total = total + jnp.sum(out * target)
        return total

    _close(jax.value_and_grad(reference, (0, 1))(h, wg),
           jax.jit(jax.value_and_grad(shares, (0, 1)))(h, wg))


def test_the_balance_statistic_is_the_references_and_one_when_uniform():
    h, wg, _, _ = _weights(8)

    class sizes:
        per_token, norm_topk, held = K, True, 0

    def program(wg):
        chosen, _, scores = dropless_route(h, wg, None, K, scoring="softmax",
                                           with_scores=True)
        return balance_statistic(chosen, scores)

    def reference(wg):
        return mellum.expert_layer_terms(h, {"wg": wg}, sizes)[1]

    _close(jax.value_and_grad(reference)(wg),
           jax.value_and_grad(program)(wg))
    chosen = jnp.arange(E * K, dtype=jnp.int32).reshape(E, K) % E
    assert abs(float(balance_statistic(
        chosen, jnp.full((E, E), 1.0 / E))) - 1.0) < 1e-6
